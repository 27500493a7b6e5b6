"""MLPs (SwiGLU / GeGLU / plain) and Mixture-of-Experts: GShard-style
capacity dispatch (shardable one-hot einsums; see DESIGN.md sec. 5), and
DeepSeek-V3's dropless sigmoid routing over a device's share of the
experts (``moe_dropless``)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models.common import activation, dense_init

Array = jax.Array


def mlp_init(key, cfg, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    dt = cfg.dtype
    ks = jax.random.split(key, 3)
    p = {
        "w_in": dense_init(ks[0], (d, f), dt),
        "w_out": dense_init(ks[1], (f, d), dt, scale=f**-0.5),
    }
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(ks[2], (d, f), dt)
    return p


def mlp_apply(p, cfg, x: Array) -> Array:
    act = activation(cfg.act)
    h = constrain(x @ p["w_in"], "batch", None, "model")
    if "w_gate" in p:
        h = act(x @ p["w_gate"]) * h
    else:
        h = act(h)
    return h @ p["w_out"]


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


def moe_init(key, cfg):
    assert cfg.moe is not None
    m = cfg.moe
    d, fe = cfg.d_model, m.d_ff_expert
    dt = cfg.dtype
    ks = jax.random.split(key, 6)
    e = m.num_experts

    def expert_leaf(k, shape, scale=None):
        return jax.vmap(lambda kk: dense_init(kk, shape, dt, scale))(
            jax.random.split(k, m.held)
        )

    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_in": expert_leaf(ks[1], (d, fe)),
        "w_gate": expert_leaf(ks[2], (d, fe)),
        "w_out": expert_leaf(ks[3], (fe, d), scale=fe**-0.5),
    }
    if m.d_ff_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=m.d_ff_shared)
    if cfg.family == "mla_moe":
        # score-correction bias (trained for load balance; random here)
        p["router_bias"] = 0.1 * jax.random.normal(ks[5], (e,), jnp.float32)
    elif m.d_ff_shared:
        p["shared_gate"] = dense_init(ks[5], (d, 1), dt)
    return p


def _route(p, cfg, x):
    """Shared router: returns (gate_vals, expert_idx, pos, keep, aux)."""
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    cap = int(m.capacity_factor * k * s / e) or 1
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)  # (B,S,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (B,S,k,E)
    flat = onehot.reshape(b, s * k, e)  # choices in priority order
    pos_in_e = jnp.cumsum(flat, axis=1) - flat  # (B, S*k, E)
    pos = jnp.sum(pos_in_e * flat, axis=-1).reshape(b, s, k).astype(jnp.int32)
    keep = pos < cap
    gate_vals = gate_vals * keep

    density = jnp.mean(onehot.sum(2), axis=(0, 1))
    router_mean = jnp.mean(probs, axis=(0, 1))
    aux = m.router_aux_weight * e * jnp.sum(density / k * router_mean)
    return gate_vals, expert_idx, pos, keep, onehot, cap, aux


def _expert_ffn(p, cfg, xe):
    """xe: (B, E, C, D) -> (B, E, C, D) through per-expert gated MLP."""
    act = activation(cfg.act)
    hidden = act(jnp.einsum("becd,edf->becf", xe, p["w_gate"])) * jnp.einsum(
        "becd,edf->becf", xe, p["w_in"]
    )
    hidden = constrain(hidden, "batch", None, None, "model")
    return jnp.einsum("becf,efd->becd", hidden, p["w_out"])


def moe_apply(p, cfg, x: Array) -> Tuple[Array, Array]:
    """x: (B, S, D) -> (out, aux_loss).

    Two dispatch implementations (cfg.moe_impl):
      * "einsum"  -- GShard one-hot dispatch/combine matmuls.  Paper-era
        baseline; shards cleanly but costs 2*T*E*C*D dispatch flops, which
        DOMINATES compute at E=60 (qwen2-moe: ~100x the expert flops).
      * "scatter" -- positions from the same cumsum routing, but tokens move
        via scatter-add into the (B,E,C,D) buffer and gather back: zero
        dispatch matmul flops (EXPERIMENTS.md section Perf, hillclimb B).
    """
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.num_experts, m.top_k
    gate_vals, expert_idx, pos, keep, onehot, cap, aux = _route(p, cfg, x)

    impl = getattr(cfg, "moe_impl", "einsum")
    if impl == "scatter":
        # Each (expert, position) slot receives exactly ONE token (positions
        # are a per-expert cumsum), so the scatter-add never accumulates and
        # the capacity buffer can stay in the compute dtype (bf16).
        buf = jnp.zeros((b, e, cap, d), x.dtype)
        bi = jnp.broadcast_to(jnp.arange(b)[:, None, None], (b, s, k))
        pos_c = jnp.minimum(pos, cap - 1)
        contrib = (x[:, :, None, :] * keep[..., None].astype(x.dtype))  # (B,S,k,D)
        buf = buf.at[bi, expert_idx, pos_c].add(contrib, mode="drop")
        ye = _expert_ffn(p, cfg, buf)  # (B,E,C,D)
        back = ye.astype(jnp.float32)[bi, expert_idx, pos_c]  # (B,S,k,D)
        y = jnp.einsum("bskd,bsk->bsd", back, gate_vals * keep).astype(x.dtype)
    else:
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32) * keep[..., None]
        dispatch = jnp.einsum("bske,bskc->bsec", onehot, pos_oh)
        combine = jnp.einsum("bsec,bsk,bske->bsec", dispatch, gate_vals, onehot)
        xe = jnp.einsum("bsd,bsec->becd", x.astype(jnp.float32), dispatch).astype(x.dtype)
        xe = constrain(xe, "batch", None, None, None)
        ye = _expert_ffn(p, cfg, xe)
        y = jnp.einsum("becd,bsec->bsd", ye.astype(jnp.float32), combine).astype(x.dtype)

    if "shared" in p:
        sg = jax.nn.sigmoid(x @ p["shared_gate"]).astype(x.dtype)
        y = y + sg * mlp_apply(p["shared"], cfg, x)
    return y, aux


# ---------------------------------------------------------------------------
# Dropless sigmoid routing over this device's share of the experts
# ---------------------------------------------------------------------------


def route_sigmoid(p, cfg, x: Array) -> Tuple[Array, Array]:
    """DeepSeek-V3 router (scoring_func sigmoid, topk_method noaux_tc, one
    group): x (T, D) -> (weights (T, k) float32, experts (T, k) int32).

    Scores are float32 sigmoids of float32 logits (full-precision matmul);
    the correction bias steers the top-k choice only, and the chosen
    unbiased scores are normalised to sum 1 and scaled."""
    m = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["router_bias"], m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * m.routed_scaling, idx


def moe_dropless(p, cfg, x: Array) -> Tuple[Array, dict]:
    """x: (B, S, D) -> (out, route).

    Routes every token over all ``num_experts`` and computes, for every
    choice that lands on one of this device's ``held`` experts, that
    expert's SwiGLU with no capacity: choices are sorted by held expert,
    the held ones fill the front of a buffer sized for the worst case (all
    of a token's choices held), and three ``ragged_dot`` grouped matmuls
    run over the groups.  Each token's result is the float32 sum of its
    own choices' weighted outputs in choice order, so it never depends on
    batch-mates or padding.  Choices for absent experts contribute nothing
    here (their devices would add them).  The shared experts add in
    ungated.  ``route`` holds each token's choices (``experts``, (B, S,
    k) int32) and whether each was computed here (``computed``: held and
    inside the buffer, which every held choice is by construction)."""
    m = cfg.moe
    b, s, d = x.shape
    t, k, held = b * s, m.top_k, m.held
    xf = x.reshape(t, d)
    w, idx = route_sigmoid(p, cfg, xf)
    local = idx - m.first_held
    is_held = (local >= 0) & (local < held)
    group = jnp.where(is_held, local, held).reshape(-1)  # absent last
    order = jnp.argsort(group, stable=True)  # (T*k,) choice slots
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    n_held = sizes.sum()
    rows = t * min(k, held)  # most held choices T tokens can make
    slot = order[:rows]
    xs = xf[slot // k]
    act = activation(cfg.act)
    hid = act(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) * \
        jax.lax.ragged_dot(xs, p["w_in"], sizes)
    ys = jax.lax.ragged_dot(hid, p["w_out"], sizes)
    ys = jnp.where((jnp.arange(rows) < n_held)[:, None], ys, 0)
    # back to (token, choice): each held slot's row in the sorted buffer
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    pos = pos.reshape(t, k)
    take = is_held & (pos < rows)
    pos = jnp.minimum(pos, rows - 1)
    y = jnp.zeros((t, d), jnp.float32)
    for c in range(k):
        yc = ys[pos[:, c]].astype(jnp.float32) * w[:, c, None]
        y = y + jnp.where(take[:, c, None], yc, 0.0)
    y = y.astype(x.dtype).reshape(b, s, d)
    if "shared" in p:
        y = y + mlp_apply(p["shared"], cfg, x)
    return y, {"experts": idx.reshape(b, s, k).astype(jnp.int32),
               "computed": take.reshape(b, s, k)}
