"""Pallas TPU kernels: batched asynchronous-sweep MCMC (Metropolis) annealer.

The second solver family next to the COBI oscillator kernels: R independent
Metropolis replicas anneal down a geometric per-sweep temperature ladder with
Snowball-style dual-mode spin selection -- ``mode="sweep"`` proposes positions
strictly in order within each chunk (every replica updates the same spin, so
the J row is one shared (1, N) gather), ``mode="random"`` draws each
replica's position uniformly (a per-replica one-hot row gather on the MXU).

VMEM residency mirrors cobi_dynamics: the ORIGINAL couplings J (one copy --
Metropolis needs no dynamics rescale, so the same matrix drives proposals and
scores energies) and h stay resident for the whole anneal; the grid is
(instance, replica-block) with replicas innermost.  State per block is
(s, f = s @ J, e) plus the best-visited (e, s): each proposal is a rank-1
f update + O(BR) acceptance test, so HBM traffic is one J/h load plus one
s0 load per replica block regardless of sweep count.

Randomness is COUNTER-BASED (kernels/ref.py: ``mcmc_u01``): acceptance and
pick uniforms are pure hashes of (seed, global replica, sweep, proposal) --
never of grid coordinates or a carried RNG state -- so any (replica_block,
chunk) decomposition visits identical logical triples and reproduces
``ref_mcmc_sweep`` bit for bit.  Proposals at positions >= n_real (lane
padding) are exact no-ops via a 0.0 flip factor.

The ``*_fused_best`` variant reuses the cobi epilogue pattern
(``_block_best`` / ``_carry_best``): each replica block folds its best
replica into a revisited (1, N) output block, replicas past the read budget
masked to +inf, strict < keeping the earliest replica on ties -- bit-identical
to host ``np.argmin`` over all reads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.cobi_dynamics import (
    _EXACT, LANE, _block_best, _carry_best, _row_index,
)
from repro.kernels.ref import mcmc_temperature, mcmc_u01

Array = jax.Array

DEFAULT_REPLICA_BLOCK = 256
DEFAULT_CHUNK = LANE


def _mcmc_loop(
    j, h, s0, seed_pick, seed_acc, rep, t_hi, t_lo, n_live,
    *, sweeps: int, chunk: int, mode: str,
):
    """Shared sweep loop: identical per-proposal op sequence to
    ``kernels/ref.py::ref_mcmc_sweep`` (the flat proposal loop there and the
    chunked nest here visit the same (sweep, t) sequence, and every op is
    row-independent, so any replica-block split matches the oracle bitwise).

    ``rep`` is (BR, 1) uint32 GLOBAL replica indices -- the counter axis that
    makes randomness independent of the grid decomposition.
    """
    n = s0.shape[-1]
    assert n % chunk == 0, (n, chunk)
    n_chunks = n // chunk
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1).astype(jnp.float32)
    f0 = jnp.dot(s0, j, preferred_element_type=jnp.float32, precision=_EXACT)
    e0 = jnp.sum(s0 * h + s0 * f0, axis=1, keepdims=True)
    log_ratio = jnp.log(t_lo / t_hi)
    denom = jnp.float32(max(sweeps - 1, 1))

    def sweep_body(ts, carry):
        temp = mcmc_temperature(t_hi, log_ratio, ts, denom)
        ts_u = ts.astype(jnp.uint32)

        def t_body(t, carry):
            s, f, e, best_e, best_s = carry
            tf = t.astype(jnp.float32)
            u_acc = mcmc_u01(seed_acc, rep, ts_u, t.astype(jnp.uint32))
            if mode == "random":
                u_pick = mcmc_u01(seed_pick, rep, ts_u, t.astype(jnp.uint32))
                k = jnp.floor(u_pick * n_live)  # (BR, 1)
                onehot = (lanes == k).astype(jnp.float32)  # (BR, N)
            else:
                onehot = (lanes == tf).astype(jnp.float32)  # (1, N)
            s_k = jnp.sum(s * onehot, axis=1, keepdims=True)
            f_k = jnp.sum(f * onehot, axis=1, keepdims=True)
            h_k = jnp.sum(h * onehot, axis=1, keepdims=True)
            j_k = jnp.dot(onehot, j, preferred_element_type=jnp.float32, precision=_EXACT)
            de = -2.0 * s_k * (h_k + 2.0 * f_k)
            accept = u_acc < jnp.exp(
                jnp.minimum(-de / jnp.maximum(temp, 1e-9), 0.0)
            )
            flip = jnp.where(accept & (tf < n_live), 1.0, 0.0)
            s_new = s * (1.0 - 2.0 * onehot * flip)
            f_new = f - 2.0 * (s_k * flip) * j_k
            e_new = e + de * flip
            better = e_new < best_e
            return (
                s_new,
                f_new,
                e_new,
                jnp.where(better, e_new, best_e),
                jnp.where(better, s_new, best_s),
            )

        def chunk_body(c, carry):
            return jax.lax.fori_loop(
                c * chunk, (c + 1) * chunk, t_body, carry
            )

        return jax.lax.fori_loop(0, n_chunks, chunk_body, carry)

    _, _, _, best_e, best_s = jax.lax.fori_loop(
        0, sweeps, sweep_body, (s0, f0, e0, e0, s0)
    )
    return best_e, best_s


def _unpack(seeds_row, params_row):
    """Per-instance scalars: seed words [init, pick, acc] (uint32) and
    params [t_hi, t_lo, n_real, reads] (f32)."""
    return (
        seeds_row[0, 1], seeds_row[0, 2],
        params_row[0, 0], params_row[0, 1], params_row[0, 2], params_row[0, 3],
    )


def _mcmc_sweep_kernel(
    j_ref, h_ref, s0_ref, seeds_ref, params_ref, e_ref, s_ref,
    *, sweeps: int, chunk: int, mode: str,
):
    """All-replica variant: every replica's best-visited (energy, spins)."""
    i = pl.program_id(1)
    br = s0_ref.shape[1]
    seed_pick, seed_acc, t_hi, t_lo, n_live, _ = _unpack(
        seeds_ref[0], params_ref[0]
    )
    rep = (i * br).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (br, 1), 0
    )
    best_e, best_s = _mcmc_loop(
        j_ref[0], h_ref[0], s0_ref[0], seed_pick, seed_acc, rep,
        t_hi, t_lo, n_live, sweeps=sweeps, chunk=chunk, mode=mode,
    )
    e_ref[0] = jnp.broadcast_to(best_e, e_ref.shape[1:])
    s_ref[0] = best_s


def _mcmc_fused_best_kernel(
    j_ref, h_ref, s0_ref, seeds_ref, params_ref, e_ref, s_ref,
    *, sweeps: int, chunk: int, mode: str,
):
    """Fused best-of variant: the cobi revisited-output epilogue with one
    slot -- only each instance's winning (energy, spin row) reaches HBM."""
    i = pl.program_id(1)
    br = s0_ref.shape[1]
    seed_pick, seed_acc, t_hi, t_lo, n_live, reads = _unpack(
        seeds_ref[0], params_ref[0]
    )
    rep = (i * br).astype(jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (br, 1), 0
    )
    best_e, best_s = _mcmc_loop(
        j_ref[0], h_ref[0], s0_ref[0], seed_pick, seed_acc, rep,
        t_hi, t_lo, n_live, sweeps=sweeps, chunk=chunk, mode=mode,
    )
    local = _row_index((br, 1))
    rep_base = (i * br).astype(jnp.float32)
    e_slots = jnp.where(local + rep_base < reads, best_e, jnp.inf)
    blk_min, rows = _block_best(best_s, e_slots, local)
    _carry_best(i, blk_min, rows, e_ref.at[0], s_ref.at[0])


def mcmc_sweep_batched_pallas(
    j: Array,  # (B, N, N) original couplings (no dynamics rescale)
    h: Array,  # (B, 1, N)
    s0: Array,  # (B, R, N) +-1 initial spins, R a replica-block multiple
    seeds: Array,  # (B, 1, LANE) uint32 [init, pick, acc] per instance
    params: Array,  # (B, 1, LANE) f32 [t_hi, t_lo, n_real, reads]
    *,
    sweeps: int,
    chunk: int = DEFAULT_CHUNK,
    mode: str = "sweep",
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Anneal B instances; returns (energies (B, R, LANE) broadcast, spins
    (B, R, N) f32 +-1) -- each replica's best-visited state."""
    b, r, n = s0.shape
    assert n % LANE == 0 and (b, n, n) == j.shape, (s0.shape, j.shape)
    assert r % replica_block == 0, (r, replica_block)
    grid = (b, r // replica_block)
    kernel = functools.partial(
        _mcmc_sweep_kernel, sweeps=sweeps, chunk=chunk, mode=mode
    )
    per_inst = lambda bi, i: (bi, 0, 0)
    per_block = lambda bi, i: (bi, i, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, n), per_inst),  # J resident per instance
            pl.BlockSpec((1, 1, n), per_inst),
            pl.BlockSpec((1, replica_block, n), per_block),
            pl.BlockSpec((1, 1, LANE), per_inst),
            pl.BlockSpec((1, 1, LANE), per_inst),
        ],
        out_specs=[
            pl.BlockSpec((1, replica_block, LANE), per_block),
            pl.BlockSpec((1, replica_block, n), per_block),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, r, LANE), jnp.float32),
            jax.ShapeDtypeStruct((b, r, n), jnp.float32),
        ],
        interpret=interpret,
    )(
        j.astype(jnp.float32), h.astype(jnp.float32), s0.astype(jnp.float32),
        seeds.astype(jnp.uint32), params.astype(jnp.float32),
    )


def mcmc_fused_best_batched_pallas(
    j: Array,  # (B, N, N)
    h: Array,  # (B, 1, N)
    s0: Array,  # (B, R, N)
    seeds: Array,  # (B, 1, LANE) uint32
    params: Array,  # (B, 1, LANE) f32 [t_hi, t_lo, n_real, reads]
    *,
    sweeps: int,
    chunk: int = DEFAULT_CHUNK,
    mode: str = "sweep",
    replica_block: int = DEFAULT_REPLICA_BLOCK,
    interpret: bool = False,
) -> tuple[Array, Array]:
    """Fused best-of anneal: (energies (B, 1, LANE), spins (B, 1, N)) --
    the first replica attaining each instance's minimum among the first
    ``reads`` replicas, carried across replica blocks in VMEM."""
    b, r, n = s0.shape
    assert n % LANE == 0 and (b, n, n) == j.shape, (s0.shape, j.shape)
    assert r % replica_block == 0, (r, replica_block)
    grid = (b, r // replica_block)
    kernel = functools.partial(
        _mcmc_fused_best_kernel, sweeps=sweeps, chunk=chunk, mode=mode
    )
    per_inst = lambda bi, i: (bi, 0, 0)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n, n), per_inst),
            pl.BlockSpec((1, 1, n), per_inst),
            pl.BlockSpec((1, replica_block, n), lambda bi, i: (bi, i, 0)),
            pl.BlockSpec((1, 1, LANE), per_inst),
            pl.BlockSpec((1, 1, LANE), per_inst),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, LANE), per_inst),  # revisited across blocks
            pl.BlockSpec((1, 1, n), per_inst),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, 1, LANE), jnp.float32),
            jax.ShapeDtypeStruct((b, 1, n), jnp.float32),
        ],
        interpret=interpret,
    )(
        j.astype(jnp.float32), h.astype(jnp.float32), s0.astype(jnp.float32),
        seeds.astype(jnp.uint32), params.astype(jnp.float32),
    )
