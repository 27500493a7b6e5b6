"""Drive the served path open loop and keep what the checks need.

The load loop submits each request at its due time through
``SummarizationEngine.submit_request`` from this one thread, and a done
callback stamps its completion on the host clock.  While ``Taps.active`` is
set, two taps keep references (no copies, no device reads) to what the
timed path produced: each solve job's instance and readout, and each
request's served sentence embeddings.  They are read back only after the
window has closed.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

from repro.serving import SummarizeRequest


@dataclasses.dataclass
class Served:
    index: int
    n: int
    sentences: tuple
    due: float  # host clock (perf_counter)
    submitted: float = 0.0
    done: Optional[float] = None
    rid: Optional[int] = None
    response: object = None
    error: Optional[BaseException] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done is None else self.done - self.due


@dataclasses.dataclass
class Job:
    tag: Optional[int]
    ising: object
    reads: int
    steps: int
    result: object = None
    done: Optional[float] = None


class Taps:
    """Wrap ``backend.submit`` and ``stage.submit`` of one engine."""

    def __init__(self, engine):
        self.active = False
        self.jobs: List[Job] = []
        self.embeddings: Dict[int, object] = {}  # tag -> EncodeFuture
        self._lock = threading.Lock()
        backend, stage = engine.backend, engine.stage
        submit_job, submit_enc = backend.submit, stage.submit

        def tapped_job(ising, key, **kw):
            fut = submit_job(ising, key, **kw)
            if self.active:
                job = Job(kw.get("tag"), ising, kw.get("reads", 8),
                          kw.get("steps", 400))
                with self._lock:
                    self.jobs.append(job)

                def done(f, job=job):
                    job.done = time.perf_counter()
                    try:
                        job.result = f.result(0.0)
                    except Exception:  # noqa: BLE001 -- checked later
                        job.result = None
                fut.add_done_callback(done)
            return fut

        def tapped_encode(texts, **kw):
            fut = submit_enc(texts, **kw)
            if self.active and kw.get("tag") is not None:
                with self._lock:
                    self.embeddings[kw["tag"]] = fut
            return fut

        backend.submit = tapped_job
        stage.submit = tapped_encode


def submit_schedule(engine, schedule, t0: float, m: int) -> List[Served]:
    """Submit each request of ``schedule`` at ``t0 + due``; returns at once
    after the last submission (futures complete on the engine's threads)."""
    out: List[Served] = []
    for req in schedule:
        s = Served(req.index, req.n, req.sentences, t0 + req.due)
        wait = s.due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        s.submitted = time.perf_counter()
        fut = engine.submit_request(SummarizeRequest(text=req.text, m=m))
        s.rid = fut.request_id

        def done(f, s=s):
            s.done = time.perf_counter()
        fut.add_done_callback(done)
        s.response = fut
        out.append(s)
    return out


def collect(served: List[Served], deadline: float) -> None:
    """Wait (until ``deadline`` on the host clock) for every request; keep
    each response or error.  A request still open at the deadline keeps
    ``done = None``."""
    for s in served:
        fut = s.response
        try:
            s.response = fut.result(timeout=max(deadline - time.perf_counter(), 0.0))
        except TimeoutError:
            s.response = None
        except Exception as exc:  # noqa: BLE001 -- a failed request
            s.response, s.error, s.done = None, exc, None
