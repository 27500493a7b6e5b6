"""Batched backbone-encoder serving stage: continuous batching in front of
the Ising farm.

The serving engine's hot path was hashed bag-of-words; this module puts the
real neural encoder (``models/`` + ``configs/sbert_paper.py``; optionally
the Pallas flash-attention kernel via ``cfg.attn_impl="flash"``) behind the
same submit->future discipline the COBI farm uses, as a SECOND pipeline
stage whose drains run concurrently with Ising drains:

  * ``submit(texts)`` tokenizes into a power-of-two padded-length bucket
    (chosen from the job's OWN token count -- results never depend on
    batch-mates) and returns an :class:`EncodeFuture` immediately.
  * A background drain thread grabs everything queued, groups jobs by
    length bucket, pads the batch and segment-count dimensions to
    power-of-two buckets (same jit-shape-churn discipline as the farm's
    ``BATCH_BUCKET``/``REPLICA_BUCKET``), and runs ONE jitted
    ``embed_sentences`` launch per group.
  * Padding is inert by construction: the backbone is causal, so trailing
    PAD tokens cannot affect real-token hidden states; batch rows and
    pooling one-hot columns are independent per row/segment.  Same
    sentences => identical embeddings (and identical mu/beta) regardless
    of batch composition -- tested.
  * Each job's :class:`EncodeReceipt` meters encoder wall seconds (launch
    wall time attributed by token share), h2d/d2h bytes, and the stage
    clock -- the encoder's line on the request bill, next to chip time.
  * ``prewarm()`` sweeps the (batch, length, segment) shape lattice so the
    first open-loop burst hits compiled code, exactly like the farm's.

``encode(texts)`` is the synchronous face (submit + wait), making a stage
usable anywhere a plain encoder is accepted.  ``submit_query(text)`` is the
cached face: rerank traffic re-asks the same query against many candidate
sets, so the stage keeps a small text-hash-keyed LRU of SOLO query
embeddings (solo because the causal packing above makes a combined-encode
query row depend on its batch-mates), invalidated when ``params`` is
swapped; hit/miss counters surface through ``cache_stats()`` and the
engine's ``stats()["encoder_cache"]``.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import ByteTokenizer
from repro.models import embed_sentences
from repro.obs import NULL_SPAN, Observability
from repro.solvers.base import AwaitableFuture

# Power-of-two padding bases (the farm's BATCH_BUCKET/REPLICA_BUCKET idiom):
# batches pad to 4,8,16..., segment counts to 8,16,..., token lengths to
# 64,128,... so background drains stay within a handful of jit shapes.
BATCH_BUCKET = 4
SEG_BUCKET = 8
MIN_LEN_BUCKET = 64

# Query-embedding LRU capacity: retrieval/rerank traffic re-asks the same
# query against many candidate sets, so the solo query row is the one
# embedding that is genuinely reusable across requests.
QUERY_CACHE_SIZE = 256


def _bucket(n: int, base: int) -> int:
    b = base
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnums=(0, 4))
def _embed_batch(cfg, params, tokens, segs, n_segments):
    emb = embed_sentences(cfg, params, tokens, segs, n_segments)
    norm = jnp.linalg.norm(emb, axis=-1, keepdims=True)
    return emb / jnp.maximum(norm, 1e-9)


@dataclasses.dataclass(frozen=True)
class EncodeReceipt:
    """Per-job encoder bill, the counterpart of the farm's ``JobReceipt``."""

    job_id: int
    tag: Optional[int]
    encoder_seconds: float  # launch wall time, attributed by token share
    bytes_h2d: int  # tokens + segment ids shipped (this job's padded rows)
    bytes_d2h: int  # embeddings returned (real segments only)
    batch_jobs: int  # jobs sharing the launch that served this one
    padded_len: int  # length bucket the job encoded at
    sim_completed: float  # stage clock (seconds since stage start) at finish


class EncodeFuture(AwaitableFuture):
    """Handle to one submitted encode job; ``result()`` -> (n, d) unit-norm
    embeddings, ``receipt()`` -> :class:`EncodeReceipt` once done."""

    __slots__ = ("job_id", "_receipt")

    def __init__(self, job_id: int):
        super().__init__()
        self.job_id = job_id
        self._receipt: Optional[EncodeReceipt] = None

    def _describe(self) -> str:
        return f"encode job {self.job_id}"

    def receipt(self, timeout: Optional[float] = None) -> EncodeReceipt:
        self._wait(timeout)
        return self._receipt


@dataclasses.dataclass
class _EncodeJob:
    job_id: int
    n_items: int
    tokens: np.ndarray  # (L,) int32, padded to the length bucket
    segs: np.ndarray  # (L,) int32, -1 on pad/specials
    n_tokens: int  # real (non-PAD) token count, for share attribution
    future: EncodeFuture
    tag: Optional[int]
    # Workload label ("selection", "multidoc", ...): keys the per-workload
    # sec/token estimate -- multidoc items are systematically longer, so one
    # global EWMA under-charges them at admission.
    workload: Optional[str] = None


@dataclasses.dataclass
class EncoderStats:
    jobs: int = 0
    launches: int = 0  # jitted embed calls (one per (bucket) group)
    drains: int = 0  # drain-thread wakeups that executed work
    tokens: int = 0  # real tokens encoded
    busy_seconds: float = 0.0  # wall time inside launches
    mean_batch: float = 0.0  # jobs per launch
    sec_per_token: float = 0.0  # EWMA, feeds admission's encode estimate
    prewarmed: int = 0  # shapes compiled by prewarm()


class EncoderStage:
    """Continuous-batching serving path for a backbone sentence encoder.

    ``policy`` mirrors the backend protocol the engine's driver speaks:
    the stage is always self-draining (its own thread supplies the drain),
    so the driver only ever calls :meth:`flush_hint`.
    """

    policy = "background"

    def __init__(self, cfg, params, *, max_len: int = 1024,
                 power_w: float = 45.0, linger: float = 0.0,
                 attn_impl: Optional[str] = None, obs=None):
        """``cfg``/``params`` are the backbone config + weights
        (:func:`EncoderStage.tiny` builds the CPU-smoke pair).  ``power_w``
        prices encoder seconds into joules on receipts; ``linger`` is an
        optional batching debounce (seconds) before a drain grabs the
        queue; ``attn_impl`` overrides ``cfg.attn_impl`` (e.g. ``"flash"``
        to route through the Pallas kernel)."""
        if attn_impl is not None:
            cfg = cfg.replace(attn_impl=attn_impl)
        self.cfg, self.params = cfg, params
        self.tok = ByteTokenizer()
        self.max_len = max_len
        self.power_w = power_w
        self.linger = linger
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: List[_EncodeJob] = []
        self._inflight: List[EncodeFuture] = []
        self._driver: Optional[threading.Thread] = None
        self._closed = False
        self._flush = False
        self._job_counter = 0
        self._ewma_spt = 0.0  # global EWMA seconds per real token (fallback)
        self.obs = None
        self.attach_obs(obs if obs is not None else Observability.disabled())
        # Wall-clock (t0, t1) of each launch -- intersect with the farm's
        # busy intervals to measure encode-vs-anneal overlap.
        self._busy: deque = deque(maxlen=4096)
        # Query-embedding LRU (see submit_query): text-hash -> (1, d) row,
        # valid only for the params object it was computed with.  The
        # in-flight table coalesces concurrent same-query requests (one
        # engine round submits a whole batch before any encode finishes).
        self._query_cache: "OrderedDict[str, jnp.ndarray]" = OrderedDict()
        self._query_inflight: Dict[str, "EncodeFuture"] = {}
        self._query_cache_cap = QUERY_CACHE_SIZE
        self._query_hits = 0
        self._query_misses = 0
        self._params_token = id(params)

    def attach_obs(self, obs) -> None:
        """Bind (or rebind) the stage to an ``Observability`` bundle.

        Leaf stages start on a private disabled bundle; the serving engine
        rebinds them to its shared one.  Counter values carry over so a
        rebind never loses history."""
        carry = None
        if self.obs is not None:
            carry = {
                "jobs": self._m_jobs.value,
                "launches": self._m_launches.value,
                "drains": self._m_drains.value,
                "tokens": self._m_tokens.value,
                "busy": self._m_busy.value,
                "prewarmed": self._m_prewarmed.value,
            }
        self.obs = obs
        reg = obs.registry
        self._m_jobs = reg.counter(
            "encoder_jobs_total", "encode jobs completed")
        self._m_launches = reg.counter(
            "encoder_launches_total", "jitted embed launches")
        self._m_drains = reg.counter(
            "encoder_drains_total", "drain wakeups that executed work")
        self._m_tokens = reg.counter(
            "encoder_tokens_total", "real (non-PAD) tokens encoded")
        self._m_busy = reg.counter(
            "encoder_busy_seconds_total", "wall seconds inside embed launches")
        self._m_prewarmed = reg.counter(
            "encoder_prewarmed_total", "shapes compiled by prewarm()")
        # Per-workload sec/token: admission reads child.ewma for its encode
        # estimate (multidoc items are systematically longer than selection
        # items, so one global EWMA under-charges them).
        self._m_spt = reg.histogram(
            "encoder_sec_per_token",
            "per-launch encode seconds per real token",
            labels=("workload",))
        if carry:
            self._m_jobs.inc(carry["jobs"])
            self._m_launches.inc(carry["launches"])
            self._m_drains.inc(carry["drains"])
            self._m_tokens.inc(carry["tokens"])
            self._m_busy.inc(carry["busy"])
            self._m_prewarmed.inc(carry["prewarmed"])

    @classmethod
    def tiny(cls, seed: int = 0, **kwargs) -> "EncoderStage":
        """CPU-smoke stage: the SBERT-paper config ``reduced()`` with
        freshly initialized weights (production passes trained params)."""
        from repro.configs.base import get_config
        from repro.models import init_params

        cfg = get_config("sbert-paper").reduced()
        params = init_params(cfg, jax.random.key(seed))
        kwargs.setdefault("max_len", cfg.max_seq_len)
        return cls(cfg, params, **kwargs)

    # ------------------------------------------------------------------ API

    def submit(self, texts: Sequence[str], *, tag: Optional[int] = None,
               workload: Optional[str] = None) -> EncodeFuture:
        """Enqueue one encode job; returns immediately.

        The job's length bucket is a pure function of its own texts, so
        its embeddings never depend on what else is queued.  ``workload``
        labels the job's sec/token observation (see
        :meth:`estimate_seconds`).  Tokenizing and building the job run in
        an ``encoder.tokenize`` span on the caller's (the engine driver's)
        track."""
        texts = list(texts)
        with self._lock:
            if self._closed:
                raise RuntimeError("encoder stage is closed")
            self._job_counter += 1
            job_id = self._job_counter
        fut = EncodeFuture(job_id)
        if not texts:
            fut._receipt = EncodeReceipt(job_id, tag, 0.0, 0, 0, 0, 0,
                                         self.sim_now())
            fut._finish(jnp.zeros((0, self.cfg.d_model), jnp.float32), None)
            return fut
        tracer = self.obs.tracer
        sp = NULL_SPAN
        if tracer.enabled:
            sp = tracer.span("encoder.tokenize", trace_id=tag,
                             parent=tracer.root_id(tag), track="driver",
                             n_texts=len(texts))
        with sp:
            n_tok = self._n_tokens(texts)
            length, _ = self.job_shape(texts)
            tokens, segs = self.tok.encode_sentences(texts, length)
            job = _EncodeJob(job_id, len(texts), tokens, segs, n_tok, fut,
                             tag, workload)
        with self._cond:
            self._queue.append(job)
            if self._driver is None:
                self._driver = threading.Thread(
                    target=self._drive, name="encoder-stage-drive",
                    daemon=True,
                )
                self._driver.start()
            self._cond.notify_all()
        return fut

    def _n_tokens(self, texts: Sequence[str]) -> int:
        return min(1 + sum(len(t.encode("utf-8")) + 1 for t in texts),
                   self.max_len)

    def job_shape(self, texts: Sequence[str]) -> Tuple[int, int]:
        """(length bucket, segment bucket) a job of ``texts`` encodes at:
        the shape :meth:`prewarm` must cover for that job."""
        length = min(_bucket(self._n_tokens(texts), MIN_LEN_BUCKET),
                     self.max_len)
        return length, _bucket(len(texts), SEG_BUCKET)

    def encode(self, texts: Sequence[str]) -> jnp.ndarray:
        """Synchronous face: submit + wait.  Makes a stage usable anywhere
        a plain ``encoder.encode(texts)`` is accepted."""
        return self.submit(texts).result()

    def submit_query(self, text: str, *, tag: Optional[int] = None
                     ) -> EncodeFuture:
        """Cached solo encode of one query string; same future surface as
        :meth:`submit`.

        The query is always encoded ALONE: the backbone is causal and
        :meth:`submit` packs a job's texts into one token row, so a query
        row from a combined encode depends on whatever items preceded it --
        uncacheable across requests.  A standalone query embedding is a
        pure function of (text, params), so it lives in a small LRU keyed
        by the text hash; a params swap invalidates the whole cache.  A hit
        resolves immediately with a zero-cost receipt and is bit-identical
        to the miss that populated it (same tensor).  Concurrent requests
        for the SAME query coalesce onto one in-flight encode (the engine
        submits a whole batch round before any encode finishes)."""
        key = hashlib.blake2b(text.encode("utf-8"),
                              digest_size=16).hexdigest()
        with self._lock:
            if self._closed:
                raise RuntimeError("encoder stage is closed")
            if id(self.params) != self._params_token:
                # Params swap: everything cached or racing was computed
                # with the old weights -- drop it all.
                self._query_cache.clear()
                self._query_inflight.clear()
                self._params_token = id(self.params)
            token = self._params_token
            cached = self._query_cache.get(key)
            inflight = None if cached is not None \
                else self._query_inflight.get(key)
            if cached is not None or inflight is not None:
                if cached is not None:
                    self._query_cache.move_to_end(key)
                self._query_hits += 1
                self._job_counter += 1
                job_id = self._job_counter
            else:
                self._query_misses += 1
        if cached is not None:
            fut = EncodeFuture(job_id)
            fut._receipt = EncodeReceipt(
                job_id, tag, 0.0, 0, int(np.asarray(cached).nbytes), 0, 0,
                self.sim_now(),
            )
            fut._finish(cached, None)
            return fut
        if inflight is not None:
            # Piggyback on the racing encode: own job id + zero-cost
            # receipt (the first submitter's receipt bills the launch).
            fut = EncodeFuture(job_id)

            def _chain(f: EncodeFuture, fut: EncodeFuture = fut,
                       tag: Optional[int] = tag) -> None:
                err = f.exception(0.0)
                emb = None if err is not None else f.result(0.0)
                nbytes = 0 if emb is None else int(np.asarray(emb).nbytes)
                fut._receipt = EncodeReceipt(fut.job_id, tag, 0.0, 0,
                                             nbytes, 0, 0, self.sim_now())
                fut._finish(emb, err)

            inflight.add_done_callback(_chain)
            return fut
        fut = self.submit([text], tag=tag)
        with self._lock:
            self._query_inflight[key] = fut

        def _fill(f: EncodeFuture, key: str = key, token: int = token
                  ) -> None:
            with self._lock:
                if self._query_inflight.get(key) is f:
                    del self._query_inflight[key]
                stale = self._params_token != token \
                    or id(self.params) != token
            try:
                emb = f.result(0.0)
            except Exception:  # noqa: BLE001 -- failed encodes aren't cached
                return
            if stale:
                return
            with self._lock:
                self._query_cache[key] = emb
                self._query_cache.move_to_end(key)
                while len(self._query_cache) > self._query_cache_cap:
                    self._query_cache.popitem(last=False)

        fut.add_done_callback(_fill)
        return fut

    def cache_stats(self) -> dict:
        """Query-LRU counters (the engine surfaces these in ``stats()``)."""
        with self._lock:
            hits, misses = self._query_hits, self._query_misses
            return {
                "hits": hits,
                "misses": misses,
                "size": len(self._query_cache),
                "capacity": self._query_cache_cap,
                "hit_rate": hits / max(hits + misses, 1),
            }

    def flush_hint(self) -> None:
        """Non-blocking nudge: the current burst is over, drain what's
        queued without waiting out the linger (the engine's round hook)."""
        with self._cond:
            self._flush = True
            self._cond.notify_all()

    def drain(self, timeout: float = 60.0) -> None:
        """Block until every job submitted so far has resolved."""
        self.flush_hint()
        with self._lock:
            futures = [j.future for j in self._queue] + list(self._inflight)
        for fut in futures:
            fut.wait(timeout)

    def estimate_seconds(self, n_tokens: int,
                         workload: Optional[str] = None) -> float:
        """Predicted encode seconds for an ``n_tokens`` job; admission adds
        this to deadline-feasibility estimates.

        With a ``workload`` label the estimate reads that workload's
        sec/token EWMA from the registry histogram (populated by
        :meth:`_run_group`); an unseen workload -- or ``workload=None`` --
        falls back to the global EWMA."""
        spt = self._ewma_spt
        if workload is not None:
            child = self._m_spt.labels(workload=workload)
            if child.count:
                spt = child.ewma
        return spt * max(n_tokens, 1)

    def prewarm(self, *, lengths: Optional[Sequence[int]] = None,
                batches: Sequence[int] = (BATCH_BUCKET,),
                segments: Sequence[int] = (SEG_BUCKET,)) -> int:
        """Compile the (batch, length, segments) shape lattice up front so
        the first open-loop burst hits compiled code (the farm's
        ``prewarm()`` idiom one stage earlier).  Returns shapes compiled."""
        if lengths is None:
            lengths = []
            length = MIN_LEN_BUCKET
            while length <= min(self.max_len, 4 * MIN_LEN_BUCKET):
                lengths.append(length)
                length *= 2
        compiled = 0
        for length in lengths:
            for b in batches:
                for g in segments:
                    tokens = jnp.zeros((b, length), jnp.int32)
                    segs = jnp.full((b, length), -1, jnp.int32)
                    _embed_batch(self.cfg, self.params, tokens, segs,
                                 int(g)).block_until_ready()
                    compiled += 1
        self._m_prewarmed.inc(compiled)
        return compiled

    def busy_intervals(self) -> List[Tuple[float, float]]:
        """Wall-clock (start, end) of recent encode launches
        (``time.monotonic`` domain, same as the farm's)."""
        with self._lock:
            return list(self._busy)

    def sim_now(self) -> float:
        return time.monotonic() - self._t0

    def stats(self) -> EncoderStats:
        """Registry view: the counters live in ``obs.registry``; this
        rebuilds the legacy :class:`EncoderStats` shape from them."""
        jobs = int(self._m_jobs.value)
        launches = int(self._m_launches.value)
        return EncoderStats(
            jobs=jobs,
            launches=launches,
            drains=int(self._m_drains.value),
            tokens=int(self._m_tokens.value),
            busy_seconds=self._m_busy.value,
            mean_batch=jobs / launches if launches else 0.0,
            sec_per_token=self._ewma_spt,
            prewarmed=int(self._m_prewarmed.value),
        )

    def close(self) -> None:
        """Finish queued work, then stop the drain thread.  Idempotent."""
        with self._cond:
            self._closed = True
            driver, self._driver = self._driver, None
            self._cond.notify_all()
        if driver is not None:
            driver.join(timeout=60.0)

    def __enter__(self) -> "EncoderStage":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------------------------------------------------ internals

    def _drive(self) -> None:
        while True:
            with self._cond:
                while not self._queue and not self._closed:
                    self._cond.wait()
                if not self._queue:
                    return  # closed and empty
                if self.linger > 0.0 and not self._flush and not self._closed:
                    self._cond.wait(self.linger)
                self._flush = False
                jobs, self._queue = self._queue, []
                self._inflight = [j.future for j in jobs]
            try:
                self._run_jobs(jobs)
            except BaseException as exc:  # noqa: BLE001 -- never strand
                for job in jobs:
                    if not job.future.done():
                        job.future._finish(None, exc)
            finally:
                with self._lock:
                    self._inflight = []

    def _run_jobs(self, jobs: List[_EncodeJob]) -> None:
        self._m_drains.inc()
        groups: Dict[int, List[_EncodeJob]] = {}
        for job in jobs:
            groups.setdefault(len(job.tokens), []).append(job)
        for length in sorted(groups):
            self._run_group(length, groups[length])

    def _run_group(self, length: int, jobs: List[_EncodeJob]) -> None:
        """One launch.  With tracing on it runs in an ``encoder.batch`` span
        holding ``encoder.pack`` (padding and the host-to-device copies),
        ``encoder.launch`` (the jitted embed until its result is ready; the
        jobs' ``encode.job`` spans take its readings) and ``encoder.readout``
        (meters, per-job rows, receipts, futures)."""
        tracer = self.obs.tracer
        traced = tracer.enabled
        batch = NULL_SPAN
        if traced:
            batch = tracer.span("encoder.batch", track="encoder",
                                jobs=len(jobs), padded_len=length)
        with batch:
            tp0 = tracer.now() if traced else 0.0
            b_pad = _bucket(len(jobs), BATCH_BUCKET)
            g_pad = _bucket(max(j.n_items for j in jobs), SEG_BUCKET)
            tokens = np.zeros((b_pad, length), np.int32)
            segs = np.full((b_pad, length), -1, np.int32)
            for i, job in enumerate(jobs):
                tokens[i] = job.tokens
                segs[i] = job.segs
            tokens_d, segs_d = jnp.asarray(tokens), jnp.asarray(segs)
            tl0 = tracer.now() if traced else 0.0
            t_start = time.monotonic()
            out = _embed_batch(self.cfg, self.params, tokens_d, segs_d,
                               int(g_pad))
            out.block_until_ready()
            t_end = time.monotonic()
            readout = NULL_SPAN
            if traced:
                tl1 = tracer.now()
                tracer.emit_span("encoder.pack", parent=batch.span_id,
                                 track="encoder", t0=tp0, t1=tl0,
                                 batch_pad=b_pad)
                tracer.emit_span("encoder.launch", parent=batch.span_id,
                                 track="encoder", t0=tl0, t1=tl1)
                readout = batch.child("encoder.readout")
            with readout:
                self._read_out(length, jobs, out, t_start, t_end,
                               (tl0, tl1) if traced else None)

    def _read_out(self, length: int, jobs: List[_EncodeJob], out,
                  t_start: float, t_end: float,
                  launch: Optional[Tuple[float, float]]) -> None:
        """Meter one finished launch and resolve its jobs' futures;
        ``launch`` is its (start, end) on the tracer clock when traced."""
        wall = t_end - t_start
        total_tok = sum(j.n_tokens for j in jobs)
        spt = wall / max(total_tok, 1)
        with self._lock:
            self._busy.append((t_start, t_end))
            self._ewma_spt = (spt if self._ewma_spt == 0.0
                              else 0.7 * self._ewma_spt + 0.3 * spt)
        self._m_launches.inc()
        self._m_jobs.inc(len(jobs))
        self._m_tokens.inc(total_tok)
        self._m_busy.inc(wall)
        # Per-workload sec/token: one observation per job so a workload's
        # EWMA tracks the launches it actually rode in.
        for job in jobs:
            self._m_spt.labels(
                workload=job.workload if job.workload else "unlabeled"
            ).observe(spt)
        done = self.sim_now()
        d = int(self.cfg.d_model)
        tracer = self.obs.tracer
        for i, job in enumerate(jobs):
            emb = out[i, :job.n_items]
            receipt = EncodeReceipt(
                job_id=job.job_id,
                tag=job.tag,
                encoder_seconds=wall * (job.n_tokens / max(total_tok, 1)),
                bytes_h2d=2 * length * 4,  # this job's tokens + seg rows
                bytes_d2h=job.n_items * d * 4,
                batch_jobs=len(jobs),
                padded_len=length,
                sim_completed=done,
            )
            if launch is not None:
                # Receipt values verbatim; the wall window is the shared
                # launch's (tracer readings at its start and end), the sim
                # window the stage clock.
                tracer.emit_span(
                    "encode.job", trace_id=job.tag,
                    parent=tracer.root_id(job.tag), track="encoder",
                    t0=launch[0], t1=launch[1],
                    sim_t0=done - wall, sim_t1=done,
                    job_id=job.job_id, n_items=job.n_items,
                    n_tokens=job.n_tokens, workload=job.workload,
                    encoder_seconds=receipt.encoder_seconds,
                    bytes_h2d=receipt.bytes_h2d,
                    bytes_d2h=receipt.bytes_d2h,
                    batch_jobs=receipt.batch_jobs,
                    padded_len=receipt.padded_len,
                )
            job.future._receipt = receipt
            job.future._finish(emb, None)
