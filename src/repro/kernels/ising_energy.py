"""Pallas TPU kernel: batched Ising energy  E_r = h.s_r + s_r^T J s_r.

This is the paper's per-iteration FP objective evaluation (18.9 us/iteration
on their host CPU) as a bilinear-form kernel: one (BR,N)@(N,N) MXU matmul per
replica block with J resident in VMEM, then an elementwise multiply-reduce.
Outputs are written as (BR, LANE) tiles with the energy broadcast across the
lane dim; ops.py slices column 0 (keeps the store layout tile-aligned).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

LANE = 128
DEFAULT_REPLICA_BLOCK = 512


def _energy_core(s, h, j):
    """Shared bilinear form: identical op sequence in the single and batched
    kernels so packed-instance scores match per-instance scores exactly."""
    sj = jnp.dot(s, j, preferred_element_type=jnp.float32,
                 precision=jax.lax.Precision.HIGHEST)  # MXU, full f32
    return jnp.sum(s * sj, axis=-1, keepdims=True) + jnp.sum(s * h, axis=-1, keepdims=True)


def _energy_kernel(s_ref, h_ref, j_ref, out_ref):
    s = s_ref[...]  # (BR, N) in {-1, 0, +1}; 0 = padding column
    h = h_ref[...]  # (1, N)
    j = j_ref[...]  # (N, N)
    out_ref[...] = jnp.broadcast_to(_energy_core(s, h, j), out_ref.shape)


def _energy_batched_kernel(s_ref, h_ref, j_ref, out_ref):
    s = s_ref[0]  # (BR, N) — one instance's replica block
    h = h_ref[0]  # (1, N)
    j = j_ref[0]  # (N, N)
    out_ref[0] = jnp.broadcast_to(_energy_core(s, h, j), out_ref.shape[1:])


def ising_energy_pallas(
    spins: Array,  # (R, N) f32 in {-1, 0, +1}; R % BR == 0, N % LANE == 0
    h: Array,  # (1, N)
    j: Array,  # (N, N)
    *,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> Array:
    r, n = spins.shape
    assert n % LANE == 0 and r % replica_block == 0
    grid = (r // replica_block,)
    out = pl.pallas_call(
        _energy_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((replica_block, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((n, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((replica_block, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, LANE), jnp.float32),
        interpret=interpret,
    )(spins.astype(jnp.float32), h.astype(jnp.float32), j.astype(jnp.float32))
    return out[:, 0]


def ising_energy_batched_pallas(
    spins: Array,  # (B, R, N) f32 in {-1, 0, +1}; R % BR == 0, N % LANE == 0
    h: Array,  # (B, 1, N)
    j: Array,  # (B, N, N)
    *,
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> Array:
    """Energies of a stack of B instances in one launch; returns (B, R) f32."""
    b, r, n = spins.shape
    assert n % LANE == 0 and r % replica_block == 0, spins.shape
    assert j.shape == (b, n, n) and h.shape == (b, 1, n), (j.shape, h.shape)
    grid = (b, r // replica_block)
    out = pl.pallas_call(
        _energy_batched_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, replica_block, n), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, 1, n), lambda bi, i: (bi, 0, 0)),
            pl.BlockSpec((1, n, n), lambda bi, i: (bi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, replica_block, LANE), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, LANE), jnp.float32),
        interpret=interpret,
    )(spins.astype(jnp.float32), h.astype(jnp.float32), j.astype(jnp.float32))
    return out[:, :, 0]
