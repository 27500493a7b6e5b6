"""Controls and faults planted in the timed path, for the readings that set
the limits of ``correct`` (``bench/control.py``) and for the tests that see
``correct`` come out false.  The benchmark's own runs never plant anything.

Each plant is a context manager that swaps one function of the program for
the duration of a run and restores it after:

* ``fp8_encoder``: the plain reference computed with float8_e4m3fn weights
  and matmul inputs in the encoder's place (the control: one precision step
  below the configuration's bfloat16);
* ``bf16_objective``: the pipeline's float32 host objective computed in
  bfloat16 (the control of the returned objectives);
* ``state_unchanged``: the anneal kernels run zero steps and return their
  initial state;
* ``negated_spins``: the anneal kernels' answers come back with every spin
  negated where they are produced, their energies kept (a single flipped
  spin is no fault that reaches the answer under the COBI farm's
  ``validate=True``, which repairs it from the energy whenever one flip
  explains the mismatch);
* ``dropped_item``: each iteration's selection loses one sentence;
* ``half_tokens``: each encoder row is encoded over the first half of its
  positions only, the mean taken over the rest.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from repro.core import pipeline
from repro.embeddings import serving as enc_serving
from repro.kernels import ops


@contextlib.contextmanager
def _swap(module, name, fn):
    old = getattr(module, name)
    setattr(module, name, fn(old))
    try:
        yield
    finally:
        setattr(module, name, old)


def fp8_encoder(reference, enc: dict, weights):
    def make(_old):
        def embed(cfg, params, tokens, segs, n_segments):
            return reference.embed(enc, weights, tokens, segs, n_segments,
                                   quant="fp8")
        return embed
    return _swap(enc_serving, "_embed_batch", make)


def half_tokens():
    def make(old):
        def embed(cfg, params, tokens, segs, n_segments):
            keep = jnp.arange(tokens.shape[1]) < tokens.shape[1] // 2
            return old(cfg, params, jnp.where(keep, tokens, 0),
                       jnp.where(keep, segs, -1), n_segments)
        return embed
    return _swap(enc_serving, "_embed_batch", make)


def bf16_objective():
    import ml_dtypes

    bf = ml_dtypes.bfloat16

    def make(_old):
        def objective(problem, x):
            mu = np.asarray(problem.mu, np.float32).astype(bf)
            beta = np.asarray(problem.beta, np.float32).astype(bf)
            xf = x.astype(bf)
            return float(xf @ mu - bf(problem.lam) * (xf @ (beta @ xf)))
        return objective
    return _swap(pipeline, "_objective_np", make)


def dropped_item():
    def make(old):
        def repair(problem, x):
            x = old(problem, x).copy()
            x[int(np.argmax(x))] = 0
            return x
        return repair
    return _swap(pipeline, "repair_selection", make)


@contextlib.contextmanager
def state_unchanged():
    def cobi(old):
        def run(*a, **kw):
            return old(*a, **dict(kw, steps=0))
        return run

    def mcmc(old):
        def run(*a, **kw):
            return old(*a, **dict(kw, sweeps=0))
        return run
    with _swap(ops, "cobi_anneal_packed_best", cobi), \
            _swap(ops, "mcmc_anneal", mcmc):
        yield


@contextlib.contextmanager
def negated_spins():
    def cobi(old):
        def run(*a, **kw):
            e, s = old(*a, **kw)
            return e, -s
        return run

    def mcmc(old):
        def run(*a, **kw):
            s, e = old(*a, **kw)
            return -s, e
        return run
    with _swap(ops, "cobi_anneal_packed_best", cobi), \
            _swap(ops, "mcmc_anneal", mcmc):
        yield


PLANTS = {
    "fp8_encoder": None,  # needs the reference and weights: see plant()
    "bf16_objective": bf16_objective,
    "state_unchanged": state_unchanged,
    "negated_spins": negated_spins,
    "dropped_item": dropped_item,
    "half_tokens": half_tokens,
}


def plant(name: str, *, reference=None, enc=None, weights=None):
    if name == "none":
        return contextlib.nullcontext()
    if name == "fp8_encoder":
        return fp8_encoder(reference, enc, weights)
    return PLANTS[name]()
