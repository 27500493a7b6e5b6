"""Bring-up smoke run of the served path on one TPU chip.

    python chip_smoke.py [--seed 0]

One process; it starts no child processes.  Phases, in order:

1. Device guard: turns on the compile cache (``repro.launch.compile_cache``),
   then exits non-zero unless ``jax.devices()`` is a TPU.  It never
   continues on the CPU.
2. Main path at full width: a ``sbert-paper`` ``EncoderStage`` (12 x 768,
   d_ff 3072, 2048 positions, seeded random weights) in front of a
   ``SummarizationEngine`` on a 4-chip ``CobiFarm`` with host-side readout
   validation.  Both stages are prewarmed, then summarize requests of 14 to
   55 sentences, plus one of 70 that decomposes, go through
   ``submit_request``.
3. MCMC bank: a few requests through ``SolveConfig(solver="mcmc")``, so
   the MCMC Pallas kernel serves them through ``McmcPoolBackend``.
4. Checks: every request returns exactly ``m`` sentences; validation
   rejected or repaired no readout; each request's normalized objective is
   at least its CPU rehearsal value less ``QUALITY_MARGIN``; the compiled
   drain programs contain the Pallas kernels (``tpu_custom_call``); the
   served encoder agrees with a float32 forward (cosine >= ``MIN_COSINE``).

Any failure raises and exits non-zero without the final line.  The last
line of standard output is ``{"ok": true, "device": {...}}``.
``tests/test_chip_smoke.py`` rehearses phases 2-4 on the CPU at the reduced
encoder config.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core import SolveConfig
from repro.data.synthetic import synthetic_document
from repro.data.text import split_sentences
from repro.embeddings.serving import (
    BATCH_BUCKET,
    EncoderStage,
    _embed_batch,
)
from repro.farm import CobiFarm
from repro.kernels import ops
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serving import SummarizationEngine, SummarizeRequest

M = 6  # sentences per summary
# Sentence counts of the farm requests; 70 > 59 spins, so it decomposes.
COBI_SIZES = (14, 20, 26, 32, 40, 48, 55, 70)
MCMC_SIZES = (18, 36, 52)
COBI_CFG = SolveConfig(solver="cobi", iterations=4, reads=8, int_range=14,
                       steps=300, p=20, q=10)
MCMC_CFG = SolveConfig(solver="mcmc", iterations=4, reads=8, int_range=14,
                       steps=300, p=20, q=10)
N_CHIPS = 4  # simulated COBI chips; every bin runs on jax.devices()[0]

# Normalized objective of each request in the CPU rehearsal of the same
# seeds (tests/test_chip_smoke.py: reduced encoder widths, same documents,
# same engine seed, Pallas interpret mode / the MCMC oracle).  On the chip
# each request must reach its value less QUALITY_MARGIN: the full-width
# encoder builds different problems, so the values are not compared bitwise.
CPU_REHEARSAL = {
    "cobi": {14: 0.926217, 20: 0.719951, 26: 0.955194, 32: 0.998262,
             40: 0.997911, 48: 1.000876, 55: 1.0, 70: 0.994169},
    "mcmc": {18: 0.80881, 36: 1.0, 52: 1.0},
}
QUALITY_MARGIN = 0.1
MIN_COSINE = 0.99


class CheckFailed(RuntimeError):
    """A smoke check did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ phases


def device_guard() -> jax.Device:
    """The run's device; exits non-zero when it is not a TPU."""
    dev = jax.devices()[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {dev.platform!r}")
    return dev


def documents(sizes, seed: int) -> list:
    return [" ".join(synthetic_document(1000 * seed + i, n))
            for i, n in enumerate(sizes)]


def build_stage(cfg, seed: int, max_len: int) -> EncoderStage:
    params = init_params(cfg, jax.random.key(seed))
    return EncoderStage(cfg, params, max_len=max_len)


def prewarm(stage: EncoderStage, farm, docs, solve_cfg) -> None:
    """Compile the encoder shapes these documents need and the farm's drain
    lattice; prints the seconds each took."""
    shapes = {stage.job_shape(split_sentences(d)) for d in docs}
    t0 = time.perf_counter()
    n_enc = stage.prewarm(lengths=sorted({s[0] for s in shapes}),
                          segments=sorted({s[1] for s in shapes}))
    t1 = time.perf_counter()
    log(f"prewarm encoder: {n_enc} shapes in {t1 - t0:.3f} s")
    if farm is not None:
        n_farm = farm.prewarm(reads=(solve_cfg.reads,), steps=solve_cfg.steps,
                              max_bins=4 * farm.n_chips)
        log(f"prewarm farm: {n_farm} launches in "
            f"{time.perf_counter() - t1:.3f} s")


def serve(engine: SummarizationEngine, docs) -> list:
    """Submit every document, then wait on every future, in order."""
    futures = [engine.submit_request(SummarizeRequest(text=d, m=M))
               for d in docs]
    return [f.result(timeout=900.0) for f in futures]


def report(label: str, engine, sizes, responses) -> None:
    backend = type(engine.backend).__name__
    for n, r in zip(sizes, responses):
        log(f"{label} req {r.request_id}: {n} sentences -> "
            f"{len(r.selected)} | normalized={r.normalized:.6f} | "
            f"wall={r.wall_seconds:.6f} s | solves={r.solver_invocations} | "
            f"backend={backend}")
    if engine.farm is not None:
        s = engine.farm.stats()
        log(f"{label} farm: {s.jobs_completed} jobs in {s.super_instances} "
            f"bins over {s.drains} drains | mean lane occupancy "
            f"{s.mean_occupancy:.6f} | faults {s.fault_counts}")


def check_responses(label: str, sizes, responses, floors) -> None:
    for n, r in zip(sizes, responses):
        require(len(r.selected) == M,
                f"{label} {n}-sentence request returned {len(r.selected)} "
                f"sentences, want {M}")
        floor = floors[n] - QUALITY_MARGIN
        require(r.normalized is not None and r.normalized >= floor,
                f"{label} {n}-sentence request: normalized objective "
                f"{r.normalized} below {floor:.6f}")


def check_validation(farm) -> None:
    faults = farm.stats().fault_counts
    require(not faults, f"readout validation flagged {faults}")


def check_drain_kernels() -> None:
    """The jitted drain programs, compiled for this device, hold the Pallas
    kernels rather than a fallback."""
    lanes, bins, slots, reads = 128, 4, 8, COBI_CFG.reads
    z = jnp.zeros
    cobi = ops.cobi_anneal_packed_best.lower(
        z((bins, lanes, lanes)), z((bins, lanes)), z((bins, lanes, lanes)),
        z((bins, lanes)), z((bins, lanes, slots)), z((bins, slots)),
        z((bins, reads, lanes)), steps=COBI_CFG.steps, dt=0.35, ks_max=1.2,
    ).compile().as_text()
    n = 48
    mcmc = ops.mcmc_anneal.lower(
        z((n,)), z((n, n)), jax.random.key(0), replicas=MCMC_CFG.reads,
        sweeps=MCMC_CFG.steps // 8, reduce="best",
    ).compile().as_text()
    for name, text in (("cobi_anneal_packed_best", cobi), ("mcmc_anneal", mcmc)):
        require("tpu_custom_call" in text, f"{name} holds no Pallas kernel")
    log("drain programs: cobi_anneal_packed_best and mcmc_anneal hold "
        "tpu_custom_call")


def encoder_cosines(stage: EncoderStage, docs) -> np.ndarray:
    """Per-sentence cosine between the served encoder launch and a float32
    forward of the same parameters at full matmul precision, over one
    batch of documents encoded at the stage's longest length.  Sentences
    cut off by ``max_len`` have no tokens and are left out."""
    texts = [split_sentences(d) for d in docs[:BATCH_BUCKET]]
    rows = [stage.tok.encode_sentences(t, stage.max_len) for t in texts]
    tokens = jnp.asarray(np.stack([r[0] for r in rows]))
    segs_np = np.stack([r[1] for r in rows])
    g = max(stage.job_shape(t)[1] for t in texts)
    served = np.asarray(_embed_batch(stage.cfg, stage.params, tokens,
                                     jnp.asarray(segs_np), g), np.float64)
    cfg32 = stage.cfg.replace(param_dtype="float32")
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), stage.params)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(_embed_batch(cfg32, params32, tokens,
                                      jnp.asarray(segs_np), g), np.float64)
    cos = []
    for b in range(len(texts)):
        for s in np.unique(segs_np[b][segs_np[b] >= 0]):
            u, v = served[b, s], ref[b, s]
            cos.append(float(u @ v / (np.linalg.norm(u) * np.linalg.norm(v))))
    return np.asarray(cos)


def run_cobi(cfg, seed: int, max_len: int, *, sizes=COBI_SIZES):
    """Phase 2: encoder stage -> admission -> packed farm drain -> host
    reduce, with readout validation; returns the checked responses."""
    docs = documents(sizes, seed)
    t0 = time.perf_counter()
    stage = build_stage(cfg, seed, max_len)
    farm = CobiFarm(N_CHIPS, validate=True)
    log(f"setup: encoder {cfg.name} d_model={cfg.d_model} "
        f"layers={cfg.n_layers} max_len={max_len} in "
        f"{time.perf_counter() - t0:.3f} s")
    with SummarizationEngine(COBI_CFG, encoder=stage, farm=farm,
                             score_against_exact=True, seed=seed) as engine:
        prewarm(stage, farm, docs, COBI_CFG)
        responses = serve(engine, docs)
        report("cobi", engine, sizes, responses)
        check_responses("cobi", sizes, responses, CPU_REHEARSAL["cobi"])
        check_validation(farm)
        cos = encoder_cosines(stage, docs)
    log(f"encoder vs float32 forward: {cos.size} sentences, min cosine "
        f"{cos.min():.6f}, mean {cos.mean():.6f}")
    require(cos.size > 0 and cos.min() >= MIN_COSINE,
            f"encoder cosine {cos.min()} below {MIN_COSINE}")
    return responses


def run_mcmc(cfg, seed: int, max_len: int, *, sizes=MCMC_SIZES):
    """Phase 3: the MCMC annealer bank behind the same encoder."""
    docs = documents(sizes, seed + 1)
    stage = build_stage(cfg, seed, max_len)
    with SummarizationEngine(MCMC_CFG, encoder=stage, score_against_exact=True,
                             seed=seed) as engine:
        prewarm(stage, None, docs, MCMC_CFG)
        responses = serve(engine, docs)
        report("mcmc", engine, sizes, responses)
        check_responses("mcmc", sizes, responses, CPU_REHEARSAL["mcmc"])
    return responses


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the encoder weights, documents and engine")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    dev = device_guard()
    log(f"compile cache: {cache_dir}")
    log(f"the farm's {N_CHIPS} COBI chips are simulated: every bin runs on "
        f"{dev.device_kind} jax.devices()[0]")

    cfg = get_config("sbert-paper")
    run_cobi(cfg, args.seed, cfg.max_seq_len)
    run_mcmc(cfg, args.seed, cfg.max_seq_len)
    check_drain_kernels()

    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
