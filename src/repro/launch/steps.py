"""jit-able distributed step functions + ShapeDtypeStruct input specs.

These are what the trainer, the serving engine, and the multi-pod dry-run all
share: the dry-run lowers exactly the functions production runs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell
from repro.distributed import sharding as shd
from repro.distributed.context import activation_mesh
from repro.models import model as M
from repro.train import optimizer as opt

Array = jax.Array


# ---------------------------------------------------------------------------
# Steps
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, opt_cfg: opt.OptConfig, mesh=None):
    """Microbatched (grad-accumulation) train step: loss -> AdamW update."""

    def body(params, opt_state, batch):
      with activation_mesh(mesh):
        mb = cfg.microbatch

        def reshape_mb(x):
            return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

        mbatch = jax.tree.map(reshape_mb, batch)
        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )

        def acc(carry, mb_batch):
            grads_acc, loss_acc = carry
            (loss, _), grads = jax.value_and_grad(
                lambda p: M.train_loss(cfg, p, mb_batch), has_aux=True
            )(params)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / mb, grads_acc, grads
            )
            return (grads_acc, loss_acc + loss / mb), None

        (grads, loss), _ = jax.lax.scan(acc, (zero_grads, 0.0), mbatch)
        params, opt_state, metrics = opt.apply_updates(params, grads, opt_state, opt_cfg)
        metrics["loss"] = loss
        return params, opt_state, metrics

    def train_step(params, opt_state, batch):
        if not jax.sharding.get_abstract_mesh().explicit_axes:
            return body(params, opt_state, batch)
        # The step relies on GSPMD propagation between its sharding
        # constraints; under a mesh with explicit axes (the default of
        # jax.make_mesh) run it in auto mode, handing params and optimizer
        # state back sharded as they came in.
        spec = lambda x: jax.typeof(x).sharding.spec
        out_sharding = (jax.tree.map(spec, params), jax.tree.map(spec, opt_state), P())
        return jax.sharding.auto_axes(body, out_sharding=out_sharding)(
            params, opt_state, batch)

    return train_step


def make_prefill_step(cfg: ModelConfig, mesh=None):
    def prefill_step(params, tokens, cache, frontend=None):
        with activation_mesh(mesh):
            return M.prefill(cfg, params, tokens, cache, frontend=frontend)

    return prefill_step


def make_decode_step(cfg: ModelConfig, mesh=None):
    def decode_step(params, tokens, positions, cache):
        with activation_mesh(mesh):
            logits, new_cache = M.decode_step(cfg, params, tokens, positions, cache)
            next_token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_token, logits, new_cache

    return decode_step


def make_embed_step(cfg: ModelConfig, n_segments: int, mesh=None):
    """The paper's bridge: backbone -> per-sentence embeddings (mu/beta feed)."""

    def embed_step(params, tokens, seg_ids):
        with activation_mesh(mesh):
            return M.embed_sentences(cfg, params, tokens, seg_ids, n_segments)

    return embed_step


def make_ising_solve_step(*, steps: int = 1000, dt: float = 0.35, ks_max: float = 1.2):
    """Fleet-scale COBI simulation: (docs, replicas) oscillator anneals.

    This is the paper's workload at datacenter scale -- thousands of
    documents' subproblem instances annealed in parallel, sharded docs over
    (pod, data) and replicas over model.  Pure XLA (the Pallas kernel is the
    single-chip version; this lowering targets the full mesh).
    """
    from repro.kernels import ref as kref

    def ising_solve_step(h, j, phi0):
        # h: (D, N), j: (D, N, N), phi0: (D, R, N)
        def one_doc(h_d, j_d, phi_d):
            phi = kref.ref_cobi_trajectory(
                j_d, h_d, phi_d, steps=steps, dt=dt, ks_max=ks_max
            )
            spins = jnp.where(jnp.cos(phi) >= 0.0, 1.0, -1.0)
            e = kref.ref_ising_energy(spins, h_d, j_d)
            best = jnp.argmin(e)
            return spins[best].astype(jnp.int8), e[best]

        return jax.vmap(one_doc)(h, j, phi0)

    return ising_solve_step


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------


def params_spec(cfg: ModelConfig):
    return jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.key(0))


def opt_state_spec(cfg: ModelConfig, opt_cfg: Optional[opt.OptConfig] = None):
    p = params_spec(cfg)
    return jax.eval_shape(lambda q: opt.init(q, opt_cfg), p)


def cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(
        functools.partial(M.init_cache, cfg, batch, max_len),
    )


def input_specs(cfg: ModelConfig, cell: ShapeCell,
                opt_cfg: Optional[opt.OptConfig] = None) -> dict:
    """All step inputs for one (arch x shape) cell, as ShapeDtypeStructs."""
    b, s = cell.global_batch, cell.seq_len
    i32 = jnp.int32
    tok = lambda shape: jax.ShapeDtypeStruct(shape, i32)
    out = {"params": params_spec(cfg)}
    if cell.kind == "train":
        batch = {"tokens": tok((b, s)), "targets": tok((b, s))}
        if cfg.n_frontend_tokens:
            batch["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype
            )
        out.update(opt_state=opt_state_spec(cfg, opt_cfg), batch=batch)
    elif cell.kind == "prefill":
        out.update(tokens=tok((b, s)), cache=cache_spec(cfg, b, s))
        if cfg.n_frontend_tokens:
            out["frontend"] = jax.ShapeDtypeStruct(
                (b, cfg.n_frontend_tokens, cfg.d_model), cfg.dtype
            )
    elif cell.kind == "decode":
        out.update(
            tokens=tok((b, 1)),
            positions=tok((b, 1)),
            cache=cache_spec(cfg, b, s),
        )
    else:
        raise ValueError(cell.kind)
    return out


def step_shardings(cfg: ModelConfig, cell: ShapeCell, mesh: Mesh,
                   *, serve_params: bool = False,
                   opt_cfg: Optional[opt.OptConfig] = None):
    """(in_shardings, out_shardings) pytrees for jax.jit, per cell kind."""
    specs = input_specs(cfg, cell, opt_cfg)
    p_sh = shd.param_sharding(
        specs["params"], mesh, serve=serve_params and cell.kind != "train"
    )
    rep = shd.replicated(mesh)
    # Batch dims shard over (pod, data) only when divisible (long_500k has
    # global_batch=1: replicate batch, keep model-axis sharding on state).
    dp_size = int(np.prod([mesh.shape[a] for a in shd.dp_axes(mesh)]))
    batch_ok = cell.global_batch % dp_size == 0

    def bs(rank):
        if batch_ok:
            return shd.batch_sharding(mesh, rank)
        return NamedSharding(mesh, P(*([None] * rank)))

    if cell.kind == "train":
        o_sh = shd.opt_state_sharding(specs["opt_state"], p_sh, mesh)
        batch_sh = {"tokens": bs(2), "targets": bs(2)}
        if "frontend" in specs["batch"]:
            batch_sh["frontend"] = bs(3)
        in_sh = (p_sh, o_sh, batch_sh)
        out_sh = (p_sh, o_sh, {"loss": rep, "grad_norm": rep, "lr": rep})
        return in_sh, out_sh
    c_sh = shd.cache_sharding(specs["cache"], mesh, n_kv_heads=cfg.n_kv_heads)
    if not batch_ok:
        # Replicate batch dims of the cache too (cache rules put batch first
        # after the group stack); only model-axis sharding survives.
        def strip_batch(ns):
            spec = tuple(
                None if p in (("pod", "data"), ("data",), "data") else p
                for p in ns.spec
            )
            return NamedSharding(mesh, P(*spec))

        c_sh = jax.tree.map(strip_batch, c_sh)
    dp = shd.dp_axes(mesh) if batch_ok else None
    if cell.kind == "prefill":
        in_sh = [p_sh, bs(2), c_sh]
        if "frontend" in specs:
            in_sh.append(bs(3))
        logits_sh = NamedSharding(mesh, P(dp, None, "model"))
        return tuple(in_sh), (logits_sh, c_sh)
    # decode
    logits_sh = NamedSharding(mesh, P(dp, "model"))
    tok_sh = NamedSharding(mesh, P(dp))
    return (p_sh, bs(2), bs(2), c_sh), (tok_sh, logits_sh, c_sh)
