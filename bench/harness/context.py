"""What a per-layer metric reader (``bench/metrics/<name>.py``) reads.

A reader is ``read(ctx) -> float | None``; ``None`` means it found nothing
to read and the metric is left out of the result line.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from harness import counts


@dataclasses.dataclass
class Context:
    cell: object  # spec.Cell
    served: List  # serve.Served of the window
    jobs: List  # serve.Job of the window's requests
    spans: List[dict]  # repro.obs records, t0/t1 moved to the host clock
    trace: Optional[object]  # trace.DeviceTrace, or None without --trace 1
    peaks: dict
    tokens: Dict[int, int]  # request id -> real encoder tokens
    window: tuple  # (start, close) on the host clock

    @property
    def enc(self) -> dict:
        return self.cell.config["encoder"]

    @property
    def solve(self) -> dict:
        return self.cell.config["solve"]

    @property
    def rids(self) -> set:
        return {s.rid for s in self.served}

    def spans_named(self, name: str, *, window_requests: bool = True) -> List[dict]:
        rids = self.rids
        return [r for r in self.spans if r["name"] == name
                and (not window_requests or r["trace"] in rids)]

    def in_trace(self, t: Optional[float]) -> bool:
        return (self.trace is not None and t is not None
                and self.trace.start <= t <= self.trace.stop)

    def job_work(self, job) -> tuple:
        """(flops, bytes) the job's solver kernel needs."""
        n = int(job.ising.n)
        if self.solve["solver"] == "mcmc":
            sweeps = max(1, job.steps // 8)
            return (counts.mcmc_flops(n, job.reads, sweeps),
                    counts.mcmc_bytes(n, job.reads))
        return (counts.cobi_flops(n, job.reads, job.steps),
                counts.cobi_bytes(n, job.reads))

    def roofline(self, op_pattern: str) -> Optional[float]:
        """Share (%) of the kernel's roofline over the traced window: the
        least time for the jobs it finished there (the larger of FLOPs over
        the bf16 peak and bytes over HBM bandwidth) over its device time."""
        if self.trace is None:
            return None
        kt = self.trace.op_time(op_pattern)
        jobs = [j for j in self.jobs if self.in_trace(j.done)]
        if kt <= 0.0 or not jobs:
            return None
        flops = sum(self.job_work(j)[0] for j in jobs)
        nbytes = sum(self.job_work(j)[1] for j in jobs)
        least = max(flops / self.peaks["bf16_flops_per_s"],
                    nbytes / self.peaks["hbm_bytes_per_s"])
        return 100.0 * least / kt

    @staticmethod
    def mean_ms(durations) -> Optional[float]:
        d = list(durations)
        return 1e3 * float(np.mean(d)) if d else None
