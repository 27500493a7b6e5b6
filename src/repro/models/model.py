"""Model assembly: every assigned architecture as one parameterized stack.

Heterogeneous stacks are expressed as GROUPED scans (DESIGN.md sec. 3): the
layer stack is G structurally-identical super-blocks; each super-block may
contain several sub-layers (e.g. llama-3.2-vision: 4 self-attention layers +
1 gated cross-attention layer).  HLO size is then independent of depth and
per-group remat gives the classic scan-over-layers memory profile.

Entry points (all pure):
  init_params(cfg, key)
  forward(cfg, params, tokens, positions, mode=train|prefill|decode,
          cache=..., frontend=...) -> (logits, new_cache, aux)
  train_loss / embed_sentences
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import ssd, xlstm
from repro.models.common import dense_init, rmsnorm, rmsnorm_init, stack_layer_params

Array = jax.Array


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _attn_block_init(key, cfg, *, cross=False, use_moe=False, with_cross=False):
    """One transformer block.  ``cross=True`` -> the attention itself is
    cross-attention (vlm gated layers); ``with_cross=True`` -> a decoder block
    with self-attention followed by encoder cross-attention (whisper)."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "attn_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": attn.attention_init(k1, cfg, cross=cross),
        "mlp_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if with_cross:
        p["cross_norm"] = rmsnorm_init(cfg.d_model, cfg.dtype)
        p["cross_attn"] = attn.attention_init(k4, cfg, cross=False)
    if use_moe:
        p["moe"] = mlp_mod.moe_init(k2, cfg)
    elif cfg.d_ff:
        p["mlp"] = mlp_mod.mlp_init(k3, cfg)
    if cross:
        p["ffn_gate"] = jnp.zeros((), jnp.float32)
    return p


def _attn_block_apply(p, cfg, x, positions, *, mode, cache, memory=None, cross=False,
                      causal=True):
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["attn_norm"], x, eps=cfg.norm_eps)
    if cross:
        a = attn.cross_attention(p["attn"], cfg, h, memory, gated=True)
        new_cache = cache  # cross layers keep no kv cache (memory is static)
    else:
        mode_eff = "train" if (not causal and mode != "decode") else mode
        a, new_cache = attn.self_attention(
            p["attn"], cfg, h, positions, mode=mode_eff, cache=cache, causal=causal
        )
        if new_cache is None or not causal:
            new_cache = cache  # train mode / encoder: carry cache through
    x = x + a
    if "cross_attn" in p:  # enc-dec decoder block
        h = rmsnorm(p["cross_norm"], x, eps=cfg.norm_eps)
        x = x + attn.cross_attention(p["cross_attn"], cfg, h, memory)
    h = rmsnorm(p["mlp_norm"], x, eps=cfg.norm_eps)
    if "moe" in p:
        m, aux = mlp_mod.moe_apply(p["moe"], cfg, h)
    elif "mlp" in p:
        m = mlp_mod.mlp_apply(p["mlp"], cfg, h)
    else:
        m = jnp.zeros_like(h)
    if cross and "ffn_gate" in p:
        m = jnp.tanh(p["ffn_gate"]).astype(m.dtype) * m
    return x + m, new_cache, aux


def _mla_block_init(key, cfg, *, dense: bool):
    """One DeepSeek-V3 decoder block: latent attention, then a dense SwiGLU
    (the leading layers) or the dropless routed experts."""
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": attn.mla_init(k1, cfg),
        "mlp_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if dense:
        p["mlp"] = mlp_mod.mlp_init(k2, cfg)
    else:
        p["moe"] = mlp_mod.moe_init(k2, cfg)
    return p


def _mla_block_apply(p, cfg, x, positions):
    """-> (x, route); route is None for a dense block."""
    h = rmsnorm(p["attn_norm"], x, eps=cfg.norm_eps)
    x = x + attn.mla_attention(p["attn"], cfg, h, positions)
    h = rmsnorm(p["mlp_norm"], x, eps=cfg.norm_eps)
    if "moe" in p:
        m, route = mlp_mod.moe_dropless(p["moe"], cfg, h)
        return x + m, route
    return x + mlp_mod.mlp_apply(p["mlp"], cfg, h), None


def _mla_moe_stack(cfg, params, x, positions):
    """Leading dense blocks, then the scanned MoE blocks -> (x, route) with
    route's leaves stacked over the MoE layers."""
    def dense_fn(x, p):
        return _mla_block_apply(p, cfg, x, positions)[0], None

    def moe_fn(x, p):
        return _mla_block_apply(p, cfg, x, positions)

    if cfg.remat:
        dense_fn, moe_fn = jax.checkpoint(dense_fn), jax.checkpoint(moe_fn)
    if "dense" in params:
        x, _ = jax.lax.scan(dense_fn, x, params["dense"])
    return jax.lax.scan(moe_fn, x, params["layers"])


def _mamba_block_init(key, cfg):
    return {
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "mixer": ssd.mamba2_init(key, cfg),
    }


def _mamba_block_apply(p, cfg, x, *, mode, cache):
    h = rmsnorm(p["norm"], x, eps=cfg.norm_eps)
    y, new_cache = ssd.mamba2_apply(p["mixer"], cfg, h, mode=mode, cache=cache)
    return x + y, (cache if new_cache is None else new_cache)


def _mlstm_block_init(key, cfg):
    return {
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "mixer": xlstm.mlstm_init(key, cfg),
    }


def _mlstm_block_apply(p, cfg, x, *, mode, cache):
    h = rmsnorm(p["norm"], x, eps=cfg.norm_eps)
    y, new_cache = xlstm.mlstm_apply(p["mixer"], cfg, h, mode=mode, cache=cache)
    return x + y, (cache if new_cache is None else new_cache)


def _slstm_block_init(key, cfg):
    return {
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "cell": xlstm.slstm_init(key, cfg),
    }


def _slstm_block_apply(p, cfg, x, *, mode, cache):
    h = rmsnorm(p["norm"], x, eps=cfg.norm_eps)
    y, new_cache = xlstm.slstm_apply(p["cell"], cfg, h, mode=mode, cache=cache)
    return x + y, (cache if new_cache is None else new_cache)


# ---------------------------------------------------------------------------
# Super-block (group) definitions per family
# ---------------------------------------------------------------------------


def _group_init(key, cfg):
    fam = cfg.family
    g = cfg.group_size
    if fam in ("dense", "moe", "encdec"):
        assert g == 1
        return _attn_block_init(
            key, cfg, use_moe=cfg.moe is not None, with_cross=(fam == "encdec")
        )
    if fam == "mla_moe":
        return _mla_block_init(key, cfg, dense=False)
    if fam == "vlm":
        k1, k2 = jax.random.split(key)
        n_self = g - 1
        return {
            "self": stack_layer_params(
                lambda k: _attn_block_init(k, cfg), k1, n_self
            ),
            "cross": _attn_block_init(k2, cfg, cross=True),
        }
    if fam == "hybrid":
        return {
            "mamba": stack_layer_params(lambda k: _mamba_block_init(k, cfg), key, g)
        }
    if fam == "ssm":  # xlstm
        k1, k2 = jax.random.split(key)
        return {
            "mlstm": stack_layer_params(
                lambda k: _mlstm_block_init(k, cfg), k1, g - 1
            ),
            "slstm": _slstm_block_init(k2, cfg),
        }
    raise ValueError(fam)


def _group_apply(cfg, gp, shared, x, positions, *, mode, cache, memory):
    """Apply one super-block.  cache is this group's slice; returns new slice."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    # Pin activations to batch sharding at every super-block boundary so the
    # 2-D weight sharding resolves to FSDP gathers, not batch replication.
    x = constrain(x, "batch", None, None)

    def scan_sub(apply_fn, params, sub_cache, x):
        def body(carry, xs):
            x, aux = carry
            p, c = xs
            x, new_c, a = apply_fn(p, x, c)
            return (x, aux + a), new_c

        (x, aux_s), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                             (params, sub_cache))
        return x, new_cache, aux_s

    if fam in ("dense", "moe", "encdec"):
        x, new_c, aux = _attn_block_apply(
            gp, cfg, x, positions, mode=mode, cache=cache, memory=memory
        )
        return x, new_c, aux
    if fam == "vlm":
        def self_fn(p, x, c):
            x, nc, a = _attn_block_apply(p, cfg, x, positions, mode=mode, cache=c)
            return x, nc, a

        x, new_self, aux = scan_sub(self_fn, gp["self"], cache["self"], x)
        x, new_cross, a2 = _attn_block_apply(
            gp["cross"], cfg, x, positions, mode=mode, cache=cache["cross"],
            memory=memory, cross=True,
        )
        return x, {"self": new_self, "cross": new_cross}, aux + a2
    if fam == "hybrid":
        def mamba_fn(p, x, c):
            x, nc = _mamba_block_apply(p, cfg, x, mode=mode, cache=c)
            return x, nc, jnp.zeros((), jnp.float32)

        x, new_mamba, aux = scan_sub(mamba_fn, gp["mamba"], cache["mamba"], x)
        # Shared attention block (zamba2): one weight set reused per group.
        x, new_attn, a2 = _attn_block_apply(
            shared["attn"], cfg, x, positions, mode=mode, cache=cache["shared_attn"]
        )
        return x, {"mamba": new_mamba, "shared_attn": new_attn}, aux + a2
    if fam == "ssm":
        def mlstm_fn(p, x, c):
            x, nc = _mlstm_block_apply(p, cfg, x, mode=mode, cache=c)
            return x, nc, jnp.zeros((), jnp.float32)

        x, new_m, aux = scan_sub(mlstm_fn, gp["mlstm"], cache["mlstm"], x)
        x, new_s = _slstm_block_apply(gp["slstm"], cfg, x, mode=mode, cache=cache["slstm"])
        return x, {"mlstm": new_m, "slstm": new_s}, aux
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# Cache construction (mirrors group structure; leading dim = n_groups)
# ---------------------------------------------------------------------------


def _group_cache_init(cfg, batch, max_len, dtype):
    fam = cfg.family
    g = cfg.group_size
    if fam in ("dense", "moe", "encdec"):
        return attn.init_cache(cfg, batch, max_len, dtype)
    if fam == "vlm":
        one = attn.init_cache(cfg, batch, max_len, dtype)
        return {
            "self": jax.tree.map(lambda x: jnp.stack([x] * (g - 1)), one),
            "cross": jnp.zeros((0,), dtype),  # cross layers are cacheless
        }
    if fam == "hybrid":
        one = ssd.mamba2_cache_init(cfg, batch, dtype)
        return {
            "mamba": jax.tree.map(lambda x: jnp.stack([x] * g), one),
            "shared_attn": attn.init_cache(cfg, batch, max_len, dtype),
        }
    if fam == "ssm":
        one = xlstm.mlstm_cache_init(cfg, batch, dtype)
        return {
            "mlstm": jax.tree.map(lambda x: jnp.stack([x] * (g - 1)), one),
            "slstm": xlstm.slstm_cache_init(cfg, batch, dtype),
        }
    raise ValueError(fam)


def init_cache(cfg, batch: int, max_len: int, dtype=None) -> dict:
    dtype = dtype or cfg.dtype
    one = _group_cache_init(cfg, batch, max_len, dtype)
    cache = {"layers": jax.tree.map(lambda x: jnp.stack([x] * cfg.n_groups), one)}
    if cfg.family in ("vlm", "encdec"):
        t = cfg.n_frontend_tokens
        cache["memory"] = jnp.zeros((batch, t, cfg.d_model), dtype)
    return cache


# ---------------------------------------------------------------------------
# Whisper encoder (non-causal self-attention over frontend embeddings)
# ---------------------------------------------------------------------------


def _encoder_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "blocks": stack_layer_params(
            lambda k: _attn_block_init(k, cfg), k1, cfg.encoder_layers
        ),
        "norm": rmsnorm_init(cfg.d_model, cfg.dtype),
    }


def encode(cfg, params, frontend: Array) -> Array:
    """frontend: (B, T, d) stub conv/patch embeddings -> encoder states."""
    enc = params["encoder"]
    b, t, _ = frontend.shape
    positions = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    x = frontend

    def body(x, p):
        x, _, _ = _attn_block_apply(
            p, cfg, x, positions, mode="train", cache=None, causal=False
        )
        return x, None

    x, _ = jax.lax.scan(body, x, enc["blocks"])
    return rmsnorm(enc["norm"], x, eps=cfg.norm_eps)


# ---------------------------------------------------------------------------
# Top-level model
# ---------------------------------------------------------------------------


def init_params(cfg, key) -> dict:
    ks = jax.random.split(key, 6)
    params = {
        "embed": dense_init(ks[0], (cfg.padded_vocab, cfg.d_model), cfg.dtype, scale=1.0),
        "final_norm": rmsnorm_init(cfg.d_model, cfg.dtype),
        "layers": stack_layer_params(
            lambda k: _group_init(k, cfg), ks[1], cfg.n_groups
        ),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(ks[2], (cfg.d_model, cfg.padded_vocab), cfg.dtype)
    if cfg.family == "hybrid":
        params["shared"] = {"attn": _attn_block_init(ks[3], cfg)}
    if cfg.family == "encdec":
        params["encoder"] = _encoder_init(ks[4], cfg)
    if cfg.first_k_dense:
        params["dense"] = stack_layer_params(
            lambda k: _mla_block_init(k, cfg, dense=True), ks[5],
            cfg.first_k_dense)
    return params


def _logits(cfg, params, x: Array) -> Array:
    x = rmsnorm(params["final_norm"], x, eps=cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["unembed"]
    logits = constrain(logits, "batch", None, "model")
    # Mask padded vocab columns so they never win.
    pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
    return jnp.where(pad_mask, logits.astype(jnp.float32), -1e30)


def forward(
    cfg,
    params,
    tokens: Array,  # (B, S) int32
    positions: Optional[Array] = None,  # (B, S)
    *,
    mode: str = "train",
    cache: Optional[dict] = None,
    frontend: Optional[Array] = None,  # (B, T, d) vlm/audio stub embeddings
    return_hidden: bool = False,
) -> Tuple[Array, Optional[dict], Array]:
    x, new_cache, aux, _ = _backbone(cfg, params, tokens, positions, mode=mode,
                                     cache=cache, frontend=frontend)
    if return_hidden:
        return x, new_cache, aux
    return _logits(cfg, params, x), new_cache, aux


def _backbone(cfg, params, tokens, positions, *, mode, cache, frontend):
    """Hidden states before the final norm -> (x, new_cache, aux, route);
    ``route`` is the MoE routing record of an ``mla_moe`` stack, else None."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model**0.5, cfg.dtype)
    x = constrain(x, "batch", None, None)
    if cfg.family == "mla_moe":
        if mode != "train":
            raise NotImplementedError(
                "mla_moe serves the encode path only (no latent KV cache)")
        x, route = _mla_moe_stack(cfg, params, x, positions)
        return x, None, jnp.zeros((), jnp.float32), route

    memory = None
    if cfg.family in ("vlm", "encdec"):
        if mode in ("train", "prefill"):
            assert frontend is not None, "vlm/encdec need frontend embeddings"
            memory = (
                encode(cfg, params, frontend) if cfg.family == "encdec" else frontend
            )
        else:
            assert cache is not None
            memory = cache["memory"]

    shared = params.get("shared")
    layer_cache = cache["layers"] if cache is not None else jax.tree.map(
        lambda x: x, _dummy_cache(cfg, b, s)
    )

    def group_fn(carry, xs):
        x, aux = carry
        gp, gc = xs
        x, new_gc, a = _group_apply(
            cfg, gp, shared, x, positions, mode=mode, cache=gc, memory=memory
        )
        return (x, aux + a), new_gc

    if cfg.remat:
        group_fn = jax.checkpoint(group_fn)

    (x, aux), new_layer_cache = jax.lax.scan(
        group_fn, (x, jnp.zeros((), jnp.float32)), (params["layers"], layer_cache)
    )

    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"layers": new_layer_cache}
        if memory is not None:
            new_cache["memory"] = memory
    return x, new_cache, aux, None


def _dummy_cache(cfg, batch, seq):
    """Train mode has no real cache, but the scan signature still carries one;
    use zero-size slots to keep HLO clean."""
    return init_cache(cfg, batch, max_len=_train_cache_len(cfg), dtype=cfg.dtype)["layers"]


def _train_cache_len(cfg):
    # Attention caches are unused in train mode; keep them minimal.
    return 8


def train_loss(cfg, params, batch: dict) -> Tuple[Array, Array]:
    """Next-token cross-entropy.  batch: tokens (B,S), targets (B,S) with -1
    for masked positions, optional frontend."""
    logits, _, aux = forward(
        cfg, params, batch["tokens"], mode="train", frontend=batch.get("frontend")
    )
    targets = batch["targets"]
    mask = targets >= 0
    safe = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return loss + aux, loss


def prefill(cfg, params, tokens, cache, *, frontend=None):
    logits, new_cache, _ = forward(
        cfg, params, tokens, mode="prefill", cache=cache, frontend=frontend
    )
    return logits, new_cache


def decode_step(cfg, params, tokens, positions, cache):
    """tokens: (B, 1); positions: (B, 1) absolute position of the new token."""
    logits, new_cache, _ = forward(
        cfg, params, tokens, positions, mode="decode", cache=cache
    )
    return logits[:, -1], new_cache


def embed_sentences(cfg, params, tokens: Array, seg_ids: Array, n_segments: int,
                    *, frontend=None, with_route: bool = False):
    """Mean-pool hidden states per sentence segment -> (B, n_segments, d).

    This is the bridge from any backbone to the paper's mu/beta scores
    (DESIGN.md: the technique is a post-encoder combinatorial head).
    seg_ids: (B, S) int32 sentence id per token, -1 for padding.
    ``with_route=True`` returns ``(embeddings, route)``, route being the
    MoE routing record of an ``mla_moe`` backbone (None otherwise).
    """
    hidden, _, _, route = _backbone(cfg, params, tokens, None, mode="train",
                                    cache=None, frontend=frontend)
    b, s, d = hidden.shape
    onehot = jax.nn.one_hot(seg_ids, n_segments, dtype=jnp.float32)  # (B,S,G)
    sums = jnp.einsum("bsd,bsg->bgd", hidden.astype(jnp.float32), onehot)
    counts = jnp.maximum(onehot.sum(axis=1), 1.0)  # (B,G)
    emb = sums / counts[..., None]
    return (emb, route) if with_route else emb
