"""Attention: GQA / MQA, QKV bias, sliding window, cross-attention, RoPE,
full and ring-buffer KV caches, and DeepSeek-style multi-head latent
attention (encode path).  Pure functions; params are dicts.

Cache protocol (decode): a dict {"k": (B, S_c, KV, HD), "v": ..., "pos":
(S_c,) int32 absolute position per slot, -1 = empty}.  Full caches have
S_c = max_seq; sliding-window caches are rings of S_c = window slots.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models.common import apply_rope, dense_init, rmsnorm, rmsnorm_init

Array = jax.Array
NEG_INF = -1e30


def attention_init(key, cfg, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    dt = cfg.dtype
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dt),
        "wk": dense_init(ks[1], (d, kv * hd), dt),
        "wv": dense_init(ks[2], (d, kv * hd), dt),
        "wo": dense_init(ks[3], (h * hd, d), dt, scale=(h * hd) ** -0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((kv * hd,), dt)
        p["bv"] = jnp.zeros((kv * hd,), dt)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)  # llama-3.2-vision tanh gate
    return p


def init_cache(cfg, batch: int, max_len: int, dtype) -> dict:
    s_c = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, s_c, kv, hd), dtype),
        "v": jnp.zeros((batch, s_c, kv, hd), dtype),
        "pos": jnp.full((s_c,), -1, jnp.int32),
    }


def _project_qkv(p, cfg, x, kv_src):
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = kv_src @ p["wk"]
    v = kv_src @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = constrain(q.reshape(b, s, h, hd), "batch", None, "model", None)
    k = constrain(k.reshape(b, kv_src.shape[1], kv, hd), "batch", None, "model", None)
    v = constrain(v.reshape(b, kv_src.shape[1], kv, hd), "batch", None, "model", None)
    return q, k, v


def _sdpa(q, k, v, mask, scale, probs_dtype=None):
    """q: (B,Sq,H,hd), k/v: (B,Skv,KV,hd), mask: (B,Sq,Skv) bool or None."""
    h, kv = q.shape[2], k.shape[2]
    rep = h // kv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(probs_dtype or q.dtype), v)


def _sdpa_chunked(q, k, v, positions, scale, *, causal, window, chunk,
                  probs_dtype=None):
    """Flash-style online-softmax attention, lax.scan over KV blocks.

    The (Sq, Skv) score matrix never exists at once -- peak temp is one
    (Sq, chunk) block (HBM-peak reduction; on TPU the Pallas kernel
    additionally keeps blocks VMEM-resident -- kernels/flash_attention.py).
    """
    b, sq, h, d = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    rep = h // kvh
    assert skv % chunk == 0, (skv, chunk)
    nb = skv // chunk
    kb = k.reshape(b, nb, chunk, kvh, d)
    vb = v.reshape(b, nb, chunk, kvh, v.shape[-1])
    q32 = q.astype(jnp.float32)
    q_pos = positions[:, :, None]  # (B, Sq, 1)

    def block(carry, inp):
        m, l, acc = carry
        kb_i, vb_i, k_pos = inp  # (B,chunk,KV,D), (B,chunk,KV,D), (B,chunk)
        kk = jnp.repeat(kb_i, rep, axis=2) if rep > 1 else kb_i
        vv = jnp.repeat(vb_i, rep, axis=2) if rep > 1 else vb_i
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kk.astype(jnp.float32)) * scale
        mask = jnp.ones((b, sq, chunk), bool)
        if causal:
            mask &= k_pos[:, None, :] <= q_pos
        if window is not None:
            mask &= k_pos[:, None, :] > q_pos - window
        s = jnp.where(mask[:, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        pv = jnp.einsum(
            "bhqk,bkhd->bqhd", p.astype(probs_dtype or q.dtype), vv
        ).astype(jnp.float32)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, h, v.shape[-1]), jnp.float32)
    k_pos_b = positions[:, :skv].reshape(b, nb, chunk) if positions.shape[1] == skv \
        else jnp.broadcast_to(jnp.arange(skv)[None], (b, skv)).reshape(b, nb, chunk)
    (m, l, acc), _ = jax.lax.scan(
        block, (m0, l0, acc0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.moveaxis(k_pos_b, 1, 0)),
    )
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(q.dtype)


def _attend(cfg, q, k, v, positions, scale, *, mode, causal):
    """Attention over the whole sequence (train / prefill / encode)."""
    b, s = q.shape[:2]
    w = cfg.sliding_window
    probs_dtype = jnp.bfloat16 if getattr(cfg, "attn_probs_bf16", False) else None
    chunk = getattr(cfg, "attn_chunk", None)
    impl = getattr(cfg, "attn_impl", "auto")
    # Pallas flash kernel: train-mode only (the kernel derives positions
    # from block indices, which matches the contiguous arange positions
    # of train/encode calls but not a prefill continuation), and the
    # sequence must tile into the kernel's q/kv blocks.  The encoder
    # stage's power-of-two length buckets satisfy both by construction.
    if (impl == "flash" and mode == "train"
            and s % min(128, s) == 0 and q.shape[-1] <= 128
            and v.shape[-1] == q.shape[-1]):
        from repro.kernels.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, window=w,
            interpret=jax.default_backend() != "tpu",
        )
    if impl != "sdpa" and chunk and s % chunk == 0 and s > chunk:
        return _sdpa_chunked(
            q, k, v, positions, scale, causal=causal, window=w, chunk=chunk,
            probs_dtype=probs_dtype,
        )
    q_pos = positions[:, :, None]  # (B, S, 1)
    k_pos = positions[:, None, :]  # (B, 1, S)
    mask = k_pos <= q_pos if causal else jnp.ones((b, s, s), bool)
    if w is not None and causal:
        mask &= k_pos > q_pos - w
    return _sdpa(q, k, v, mask, scale, probs_dtype=probs_dtype)


def self_attention(
    p,
    cfg,
    x: Array,
    positions: Array,  # (B, S) absolute positions
    *,
    mode: str = "train",  # train | prefill | decode
    cache: Optional[dict] = None,
    causal: bool = True,
) -> Tuple[Array, Optional[dict]]:
    b, s, d = x.shape
    q, k, v = _project_qkv(p, cfg, x, x)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    scale = cfg.hd**-0.5
    w = cfg.sliding_window

    probs_dtype = jnp.bfloat16 if getattr(cfg, "attn_probs_bf16", False) else None

    if mode in ("train", "prefill"):
        out = _attend(cfg, q, k, v, positions, scale, mode=mode, causal=causal)
        new_cache = None
        if mode == "prefill" and cache is not None:
            s_c = cache["k"].shape[1]
            if w is not None and s >= s_c:
                # keep the last `window` kv, slot = pos % window
                tail_k, tail_v = k[:, -s_c:], v[:, -s_c:]
                tail_pos = positions[0, -s_c:]
                slots = tail_pos % s_c
                new_cache = {
                    "k": cache["k"].at[:, slots].set(tail_k),
                    "v": cache["v"].at[:, slots].set(tail_v),
                    "pos": cache["pos"].at[slots].set(tail_pos),
                }
            else:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)
                    ),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)
                    ),
                    "pos": jax.lax.dynamic_update_slice(
                        cache["pos"], positions[0].astype(jnp.int32), (0,)
                    ),
                }
        out = out.reshape(b, s, -1) @ p["wo"]
        return out, new_cache

    # ---- decode: s == 1, write kv at slot, attend over cache ----
    assert mode == "decode" and cache is not None and s == 1
    s_c = cache["k"].shape[1]
    pos0 = positions[0, 0]  # same position for the whole batch (batched serve)
    slot = pos0 % s_c if w is not None else pos0
    ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    cpos = jax.lax.dynamic_update_slice(cache["pos"], pos0[None].astype(jnp.int32), (slot,))
    new_cache = {"k": ck, "v": cv, "pos": cpos}
    valid = (cpos >= 0) & (cpos <= pos0)
    if w is not None:
        valid &= cpos > pos0 - w
    mask = jnp.broadcast_to(valid[None, None, :], (b, 1, s_c))
    out = _sdpa(q, ck, cv, mask, scale, probs_dtype=probs_dtype)
    out = out.reshape(b, 1, -1) @ p["wo"]
    return out, new_cache


def cross_attention(
    p,
    cfg,
    x: Array,
    memory: Array,  # (B, T, d) frontend / encoder states
    *,
    gated: bool = False,
) -> Array:
    b, s, d = x.shape
    q, k, v = _project_qkv(p, cfg, x, memory)
    out = _sdpa(q, k, v, None, cfg.hd**-0.5)
    out = out.reshape(b, s, -1) @ p["wo"]
    if gated and "gate" in p:
        out = jnp.tanh(p["gate"]).astype(out.dtype) * out
    return out


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2/V3, q_lora_rank null)
# ---------------------------------------------------------------------------


def mla_init(key, cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    rank, nope, rope, vd = (m.kv_lora_rank, m.qk_nope_head_dim,
                            m.qk_rope_head_dim, m.v_head_dim)
    ks = jax.random.split(key, 4)
    dt = cfg.dtype
    return {
        "wq": dense_init(ks[0], (d, h * (nope + rope)), dt),
        "wkv_a": dense_init(ks[1], (d, rank + rope), dt),
        "kv_norm": rmsnorm_init(rank, dt),
        "wkv_b": dense_init(ks[2], (rank, h * (nope + vd)), dt),
        "wo": dense_init(ks[3], (h * vd, d), dt, scale=(h * vd) ** -0.5),
    }


def mla_attention(p, cfg, x: Array, positions: Array) -> Array:
    """Causal latent attention over the whole sequence (the encode path; no
    KV cache).  Per head, the query is ``[q_nope, rope(q_pe)]`` and the key
    ``[k_nope, rope(k_pe)]``, where ``k_nope`` and the value come from the
    RMS-normalised latent and the one rotary key ``k_pe`` is shared by
    every head; the softmax scale is the query width's inverse square root
    (Moonlight-16B-A3B: latent 512, query 128 + 64, value 128).  Rotary
    channels are half-split (``apply_rope``), a fixed permutation of the
    published interleaved layout's ``wq`` / ``wkv_a`` columns."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    rank, nope, rope, vd = (m.kv_lora_rank, m.qk_nope_head_dim,
                            m.qk_rope_head_dim, m.v_head_dim)
    q = (x @ p["wq"]).reshape(b, s, h, nope + rope)
    kv_a = x @ p["wkv_a"]
    latent = rmsnorm(p["kv_norm"], kv_a[..., :rank], eps=m.kv_norm_eps)
    kv = (latent @ p["wkv_b"]).reshape(b, s, h, nope + vd)
    q_pe = apply_rope(q[..., nope:], positions, cfg.rope_theta)
    k_pe = apply_rope(kv_a[..., None, rank:], positions, cfg.rope_theta)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], axis=-1)
    out = _attend(cfg, q, k, kv[..., nope:], positions, (nope + rope) ** -0.5,
                  mode="train", causal=True)
    return out.reshape(b, s, h * vd) @ p["wo"]
