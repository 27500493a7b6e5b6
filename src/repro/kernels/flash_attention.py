"""Pallas TPU kernel: blocked online-softmax attention (FlashAttention-style),
with causal masking, sliding windows, and GQA head mapping.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
innermost (sequential) axis, accumulating into VMEM scratch:
  m  -- running row max        (BQ, LANE)
  l  -- running softmax denom  (BQ, LANE)
  acc-- running weighted sum   (BQ, D)
Each (b, h, qb) output tile is written once, on the last kv step.  GQA maps
query head h to kv head h // (H // KV) purely via the BlockSpec index_map --
no repeated K/V materialization in HBM.

The kernel works on head-major (B, H, S, D) arrays so every block's last two
dims are a (rows, D) tile: Mosaic refuses a block that puts a unit head axis
among the last two dims of the caller's (B, S, H, D) layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

LANE = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window, bq: int, bk: int,
               sq: int, skv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (BQ, D)
    k = k_ref[0, 0]  # (BK, D)
    v = v_ref[0, 0]  # (BK, D)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (BQ, BK)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + (skv - sq)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # (BQ, 1) value replicated across lanes
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (BQ, BK)
    alpha = jnp.exp(m_prev - m_new)  # (BQ, 1)
    l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention(
    q: Array,  # (B, Sq, H, D)
    k: Array,  # (B, Skv, KV, D)
    v: Array,
    *,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> Array:
    b, sq, h, d = q.shape
    _, skv, kv, _ = k.shape
    assert h % kv == 0
    rep = h // kv
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    grid = (b, h, sq // bq, skv // bk)
    scale = d**-0.5

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, sq=sq, skv=skv,
    )
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)  # (B, S, H, D) <-> (B, H, S, D)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, qi, ki, rep=rep: (b_, h_ // rep, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, qi, ki, rep=rep: (b_, h_ // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, LANE), jnp.float32),  # m
            pltpu.VMEM((bq, LANE), jnp.float32),  # l
            pltpu.VMEM((bq, d), jnp.float32),  # acc
        ],
        interpret=interpret,
    )(heads_major(q), heads_major(k), heads_major(v))
    return heads_major(out)
