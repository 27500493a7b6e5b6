"""Plain reference of the ``sbert-paper`` sentence encoder as the program
runs it, in straightforward ``jax.numpy``; it imports nothing of the program.

The architecture (the program's ``models/`` dense family): token embedding
scaled by sqrt(d_model); per layer a pre-RMSNorm causal self-attention with
rotary positions (half-split, theta 10000) and a pre-RMSNorm SwiGLU MLP,
each added to the residual; no final norm before pooling.  A sentence's
embedding is the mean of the hidden states of its tokens, then scaled to
unit norm.  Tokens are UTF-8 bytes + 4, with BOS 1 before the first
sentence, SEP 3 after each, PAD 0, cut at ``max_seq_len``.

Weights are made here from a key, in the layout the program's encoder
reads (``embed``, ``final_norm``, ``layers`` stacked over depth), so the
benchmark hands the same arrays to the program and to this reference.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD, BOS, SEP, N_SPECIAL = 0, 1, 3, 4


def padded_vocab(enc: dict) -> int:
    return ((enc["vocab_size"] + 255) // 256) * 256


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(enc_items: tuple, key, dtype: str):
    enc = dict(enc_items)
    d, f, n_l = enc["d_model"], enc["d_ff"], enc["n_layers"]
    hd = d // enc["n_heads"]
    h_all, kv_all = enc["n_heads"] * hd, enc["n_kv_heads"] * hd
    ks = iter(jax.random.split(key, 8))

    def w(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)  # noqa: E731
    return {
        "embed": w((padded_vocab(enc), d), 1.0),
        "final_norm": {"scale": ones(d)},
        "layers": {
            "attn_norm": {"scale": ones(n_l, d)},
            "mlp_norm": {"scale": ones(n_l, d)},
            "attn": {
                "wq": w((n_l, d, h_all), d),
                "wk": w((n_l, d, kv_all), d),
                "wv": w((n_l, d, kv_all), d),
                "wo": w((n_l, h_all, d), h_all),
            },
            "mlp": {
                "w_in": w((n_l, d, f), d),
                "w_gate": w((n_l, d, f), d),
                "w_out": w((n_l, f, d), f),
            },
        },
    }


def make_weights(enc: dict, key, dtype: str = "bfloat16") -> dict:
    """Seeded random weights on the device, in one jitted call."""
    keys = ("vocab_size", "d_model", "d_ff", "n_layers", "n_heads", "n_kv_heads")
    return _make(tuple((k, enc[k]) for k in keys), key, dtype)


def tokenize(sentences: Sequence[str], length: int, max_len: int
             ) -> Tuple[np.ndarray, np.ndarray]:
    """(tokens, segment ids) of one document padded to ``length``."""
    toks, segs = [BOS], [-1]
    for i, s in enumerate(sentences):
        ids = [b + N_SPECIAL for b in s.encode("utf-8")]
        toks += ids + [SEP]
        segs += [i] * len(ids) + [-1]
    toks, segs = toks[:max_len], segs[:max_len]
    pad = length - len(toks)
    return (np.asarray(toks + [PAD] * pad, np.int32),
            np.asarray(segs + [-1] * pad, np.int32))


def n_tokens(sentences: Sequence[str], max_len: int) -> int:
    """Real (non-PAD) tokens of one document after the cut."""
    return min(1 + sum(len(s.encode("utf-8")) + 1 for s in sentences), max_len)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale


def _rope(x, theta):
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _embed(enc_items: tuple, weights, tokens, segs, n_segments: int,
           quant: str):
    enc = dict(enc_items)
    q8 = _fp8 if quant == "fp8" else (lambda x: x)
    f32 = lambda x: q8(jnp.asarray(x, jnp.float32))  # noqa: E731
    d, heads = enc["d_model"], enc["n_heads"]
    hd = d // heads
    eps, theta = enc["norm_eps"], enc["rope_theta"]
    b, s = tokens.shape
    x = jnp.asarray(weights["embed"], jnp.float32)[tokens] * d ** 0.5
    lay = weights["layers"]
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(enc["n_layers"]):
        h = q8(_rms(x, jnp.asarray(lay["attn_norm"]["scale"][i], jnp.float32),
                    eps))
        a = lay["attn"]
        q = (h @ f32(a["wq"][i])).reshape(b, s, heads, hd)
        k = (h @ f32(a["wk"][i])).reshape(b, s, enc["n_kv_heads"], hd)
        v = (h @ f32(a["wv"][i])).reshape(b, s, enc["n_kv_heads"], hd)
        q, k = _rope(q, theta), _rope(k, theta)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k)) * hd ** -0.5
        logits = jnp.where(causal, logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", q8(p), q8(v)).reshape(b, s, d)
        x = x + q8(o) @ f32(a["wo"][i])
        h = q8(_rms(x, jnp.asarray(lay["mlp_norm"]["scale"][i], jnp.float32),
                    eps))
        m = lay["mlp"]
        g = jax.nn.silu(h @ f32(m["w_gate"][i])) * (h @ f32(m["w_in"][i]))
        x = x + q8(g) @ f32(m["w_out"][i])
    onehot = jax.nn.one_hot(segs, n_segments, dtype=jnp.float32)
    sums = jnp.einsum("bsd,bsg->bgd", x, onehot)
    emb = sums / jnp.maximum(onehot.sum(axis=1), 1.0)[..., None]
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)


ENC_KEYS = ("d_model", "n_heads", "n_kv_heads", "n_layers", "norm_eps",
            "rope_theta")


def embed(enc: dict, weights, tokens, segs, n_segments: int, *,
          quant: str = "none"):
    """(B, n_segments, d) unit-norm sentence embeddings in float32.

    The caller sets the matmul precision (``"highest"`` for the reference).
    ``quant="fp8"`` rounds the weights and every matmul input to
    float8_e4m3fn: the control, one precision step below the bfloat16 the
    configuration serves in."""
    return _embed(tuple((k, enc[k]) for k in ENC_KEYS), weights,
                  jnp.asarray(tokens), jnp.asarray(segs), int(n_segments),
                  quant)


def embed_documents(enc: dict, weights, docs: List[List[str]], *,
                    block: int = 4, quant: str = "none") -> List[np.ndarray]:
    """Reference embeddings of whole documents, ``block`` rows at a time at
    the shortest 64-multiple power-of-two length that holds each block."""
    max_len = enc["max_seq_len"]
    out: List[np.ndarray] = [None] * len(docs)
    order = sorted(range(len(docs)), key=lambda i: n_tokens(docs[i], max_len))
    with jax.default_matmul_precision("highest"):
        for start in range(0, len(order), block):
            idx = order[start:start + block]
            need = max(n_tokens(docs[i], max_len) for i in idx)
            length = 64
            while length < need:
                length *= 2
            length = min(length, max_len)
            g = 8
            while g < max(len(docs[i]) for i in idx):
                g *= 2
            rows = [tokenize(docs[i], length, max_len) for i in idx]
            while len(rows) < block:
                rows.append((np.zeros(length, np.int32),
                             np.full(length, -1, np.int32)))
            e = np.asarray(embed(enc, weights, np.stack([r[0] for r in rows]),
                                 np.stack([r[1] for r in rows]), g,
                                 quant=quant))
            for j, i in enumerate(idx):
                out[i] = e[j, :len(docs[i])]
    return out
