"""Plain reference of the ``moonlight-16b-a3b`` sentence encoder (DeepSeek-V3
block) as the program serves it, in straightforward ``jax.numpy`` and
float32; it imports nothing of the program.

The architecture (``model_type`` deepseek_v3; sizes from the configuration
file's ``encoder`` block and its ``arch_keys``): the token embedding,
unscaled; per layer a pre-RMSNorm latent attention and a pre-RMSNorm
feed-forward, each added to the residual; no final norm before pooling.

* Latent attention (``q_lora_rank`` null): per head the query is
  ``x @ wq`` split into 128 ``nope`` and 64 rotary channels; ``x @ wkv_a``
  gives a 512-wide latent and one 64-wide rotary key shared by every head;
  the latent goes through its own RMSNorm (epsilon ``kv_norm_eps``) and
  ``wkv_b`` into each head's 128 ``nope`` key channels and 128-wide value.
  Causal softmax over ``[q_nope, rope(q_pe)] . [k_nope, rope(k_pe)]`` at
  scale 192^-0.5, then ``wo``.  Rotary positions are half-split (theta
  50000), a fixed permutation of the published interleaved layout.
* The first ``first_k_dense`` layers: a SwiGLU of ``d_ff``.
* The rest: the router's float32 logits over all ``num_experts`` experts
  through a sigmoid; the top ``top_k`` of score plus the score-correction
  bias are chosen; their unbiased scores, normalised to sum 1 and times
  ``routed_scaling``, weight the experts' SwiGLUs of ``d_ff_expert``.
  Only the ``experts_held`` experts of share ``expert_shard`` are here:
  each is computed densely for every token and weighted by its gate (zero
  where the token did not choose it).  The shared experts (one SwiGLU of
  ``d_ff_shared``) add in ungated.

A sentence's embedding is the mean of its tokens' hidden states, scaled to
unit norm.  Tokens are UTF-8 bytes + 4, with BOS 1 before the first
sentence, SEP 3 after each, PAD 0, cut at ``max_seq_len``.

Weights are made here from a key, leaf by leaf on the device, in the layout
the program's encoder reads, so the benchmark hands the same arrays to the
program and to this reference.  The forward runs one layer per jitted call,
so that it fits on the chip beside the weights.
"""

from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PAD, BOS, SEP, N_SPECIAL = 0, 1, 3, 4
BIAS_STD = 0.1  # the score-correction bias, drawn from the seed


def padded_vocab(enc: dict) -> int:
    return ((enc["vocab_size"] + 255) // 256) * 256


def _shapes(enc: dict) -> dict:
    """(shape, fan-in scale, dtype) of every weight leaf; None scale means
    ones."""
    a = enc["arch_keys"]
    d, f, n_l = enc["d_model"], enc["d_ff"], enc["n_layers"]
    heads = enc["n_heads"]
    rank, nope, rope, vd = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                            a["qk_rope_head_dim"], a["v_head_dim"])
    e, fe, fs = a["experts_held"], a["d_ff_expert"], a["d_ff_shared"]

    def block(n, ffn):
        return {
            "attn_norm": {"scale": ((n, d), None)},
            "mlp_norm": {"scale": ((n, d), None)},
            "attn": {
                "wq": ((n, d, heads * (nope + rope)), d),
                "wkv_a": ((n, d, rank + rope), d),
                "kv_norm": {"scale": ((n, rank), None)},
                "wkv_b": ((n, rank, heads * (nope + vd)), rank),
                "wo": ((n, heads * vd, d), heads * vd),
            },
            **ffn,
        }

    def swiglu(lead, width):
        return {"w_in": (lead + (d, width), d), "w_gate": (lead + (d, width), d),
                "w_out": (lead + (width, d), width)}

    k_d = a["first_k_dense"]
    n_moe = n_l - k_d
    return {
        "embed": ((padded_vocab(enc), d), 1.0),
        "final_norm": {"scale": ((d,), None)},
        "dense": block(k_d, {"mlp": swiglu((k_d,), f)}),
        "layers": block(n_moe, {"moe": {
            "router": ((n_moe, d, a["num_experts"]), d),
            "router_bias": ((n_moe, a["num_experts"]), "bias"),
            **swiglu((n_moe, e), fe),
            "shared": swiglu((n_moe,), fs),
        }}),
    }


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _leaf(key, shape: tuple, scale, dtype: str):
    if scale is None:
        return jnp.ones(shape, dtype)
    if scale == "bias":
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    return (jax.random.normal(key, shape, jnp.float32)
            * float(scale) ** -0.5).astype(dtype)


def make_weights(enc: dict, key, dtype: str = "bfloat16") -> dict:
    """Seeded random weights on the device, one jitted call per leaf; the
    router and its bias are float32, the rest ``dtype``."""
    shapes = _shapes(enc)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple) and isinstance(x[0], tuple))[0]]
    out = []
    for i, ((shape, scale), path) in enumerate(zip(leaves, paths)):
        dt = "float32" if "router" in path else dtype
        out.append(_leaf(jax.random.fold_in(key, i), shape, scale, dt))
    return jax.tree.unflatten(tree, out)


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


def _swiglu(h, w, q8, i=None):
    f32 = lambda x: q8(jnp.asarray(x if i is None else x[i], jnp.float32))  # noqa: E731
    g = jax.nn.silu(h @ f32(w["w_gate"])) * (h @ f32(w["w_in"]))
    return q8(g) @ f32(w["w_out"])


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(enc_items: tuple, w, x, quant: str):
    """One decoder block, float32: x (B, S, d) -> x."""
    enc = dict(enc_items)
    q8 = _fp8 if quant == "fp8" else (lambda x: x)
    f32 = lambda x: q8(jnp.asarray(x, jnp.float32))  # noqa: E731
    heads, eps, theta = enc["n_heads"], enc["norm_eps"], enc["rope_theta"]
    rank, nope, rope, vd = (enc["kv_lora_rank"], enc["qk_nope_head_dim"],
                            enc["qk_rope_head_dim"], enc["v_head_dim"])
    b, s, d = x.shape
    a = w["attn"]
    h = q8(_rms(x, jnp.asarray(w["attn_norm"]["scale"], jnp.float32), eps))
    q = (h @ f32(a["wq"])).reshape(b, s, heads, nope + rope)
    kv_a = h @ f32(a["wkv_a"])
    lat = q8(_rms(kv_a[..., :rank], jnp.asarray(a["kv_norm"]["scale"],
                                                 jnp.float32),
                  enc["kv_norm_eps"]))
    kv = (lat @ f32(a["wkv_b"])).reshape(b, s, heads, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k_pe = jnp.broadcast_to(_rope(kv_a[:, :, None, rank:], theta),
                            (b, s, heads, rope))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q8(q), q8(k)) * (nope + rope) ** -0.5
    logits = jnp.where(jnp.tril(jnp.ones((s, s), bool)), logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", q8(p), q8(kv[..., nope:]))
    x = x + q8(o.reshape(b, s, heads * vd)) @ f32(a["wo"])
    h = q8(_rms(x, jnp.asarray(w["mlp_norm"]["scale"], jnp.float32), eps))
    if "mlp" in w:
        return x + _swiglu(h, w["mlp"], q8)
    return x + moe(enc, w["moe"], h, q8)


def route(enc: dict, m, h, q8=lambda x: x):
    """The router: (gates, experts), each (..., top_k), of hidden ``h``."""
    scores = jax.nn.sigmoid(h @ q8(jnp.asarray(m["router"], jnp.float32)))
    _, idx = jax.lax.top_k(scores + m["router_bias"], enc["top_k"])
    gate = jnp.take_along_axis(scores, idx, axis=-1)
    return gate / (gate.sum(-1, keepdims=True) + 1e-20) * enc["routed_scaling"], idx


def moe(enc: dict, m, h, q8=lambda x: x):
    """One MoE feed-forward of normalised hidden ``h``: the shared experts
    plus each held expert, computed for every token, times its gate."""
    gate, idx = route(enc, m, h, q8)
    y = _swiglu(h, m["shared"], q8)
    for j in range(enc["experts_held"]):
        e = enc["expert_shard"] * enc["experts_held"] + j
        g = jnp.sum(jnp.where(idx == e, gate, 0.0), axis=-1)  # (B, S)
        y = y + g[..., None] * _swiglu(h, m, q8, j)
    return y


@functools.partial(jax.jit, static_argnums=(2,))
def _pool(x, segs, n_segments: int):
    onehot = jax.nn.one_hot(segs, n_segments, dtype=jnp.float32)
    sums = jnp.einsum("bsd,bsg->bgd", x, onehot)
    emb = sums / jnp.maximum(onehot.sum(axis=1), 1.0)[..., None]
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)


ENC_KEYS = ("n_heads", "norm_eps", "rope_theta")
ARCH_KEYS = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "kv_norm_eps", "top_k", "routed_scaling",
             "experts_held", "expert_shard")


def embed(enc: dict, weights, tokens, segs, n_segments: int, *,
          quant: str = "none"):
    """(B, n_segments, d) unit-norm sentence embeddings in float32.

    The caller sets the matmul precision (``"highest"`` for the reference).
    ``quant="fp8"`` rounds the weights and every matmul input to
    float8_e4m3fn: the control, one precision step below the bfloat16 the
    configuration serves in."""
    items = tuple((k, enc[k]) for k in ENC_KEYS) + tuple(
        (k, enc["arch_keys"][k]) for k in ARCH_KEYS)
    x = jnp.asarray(weights["embed"], jnp.float32)[jnp.asarray(tokens)]
    for stack in ("dense", "layers"):
        n = jax.tree.leaves(weights[stack])[0].shape[0]
        for i in range(n):
            w = jax.tree.map(lambda a, i=i: a[i], weights[stack])
            x = _layer(items, w, x, quant)
    return _pool(x, jnp.asarray(segs), int(n_segments))


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
