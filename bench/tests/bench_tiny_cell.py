"""A cell added to a copy of the benchmark from new files alone: a reduced
encoder configuration, a small traffic mix, its rate and limits and one more
per-layer metric reader.  The CPU tests drive the harness on it."""

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

CELL = "es-cobi-tiny.tiny"
REDUCED = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
           "d_ff": 128, "vocab_size": 512, "max_seq_len": 512,
           "param_dtype": "bfloat16"}
# The reduced encoder's own limit: on the CPU its bf16 program read 1 - cos
# of 3.1e-6 and 4.6e-6 against the float32 reference (seeds 5, 6), the fp8
# control 6.8e-5 and 1.0e-4.  The other limits are the full cell's.
TINY_EMBED_LIMIT = 2e-5
# Its own objective limit too: on the CPU the program's objectives read a
# gap of 5.5e-8 to 8.5e-8 (exact float32 matmuls, 3 seeds), the bf16
# control 1.04e-3 to 1.82e-3; the chip's limit sits above the chip's
# default-precision matmuls.
TINY_OBJECTIVE_LIMIT = 1e-5
READER = '''"""Requests the window served (a reader added by a file alone)."""


def read(ctx):
    return float(len(ctx.served)) if ctx.served else None
'''


SOURCE = {"cobi": "es-cobi-sbert", "mcmc": "es-mcmc-sbert"}


def cell_name(solver: str = "cobi") -> str:
    return CELL if solver == "cobi" else "es-mcmc-tiny.tiny"


def make_root(tmp: Path, *, solver: str = "cobi") -> Path:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``tmp`` and add the cell
    (``cell_name(solver)``) on the configuration of that solver family."""
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    src = SOURCE[solver]
    cell = cell_name(solver)
    name = cell.split(".")[0]
    config = json.loads((BENCH / "configs" / f"{src}.json").read_text())
    config["name"] = name
    config["encoder"].update(REDUCED)
    config["solve"].update(iterations=2)
    if solver == "cobi":
        config["backend"]["n_chips"] = 2
    else:
        config["backend"]["workers"] = 2
    (tmp / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))
    mix = json.loads((BENCH / "traffic" / "cnndm.json").read_text())
    mix.update(name="tiny", warmup_seconds=1.0,
               sentences=dict(mix["sentences"], median=9, sigma=0.4, max=64),
               warm={"encoder_batches": [4], "farm_max_bins": 2, "workers": 2})
    (tmp / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    own = json.loads((BENCH / "cells" / f"{src}.cnndm.json").read_text())
    own["rate"] = 2.0
    own["limits"]["embed_gap"] = dict(own["limits"]["embed_gap"],
                                      limit=TINY_EMBED_LIMIT)
    own["limits"]["objective_gap"] = dict(own["limits"]["objective_gap"],
                                          limit=TINY_OBJECTIVE_LIMIT)
    (tmp / "bench" / "cells" / f"{cell}.json").write_text(json.dumps(own))
    (tmp / "bench" / "metrics" / "tiny_requests.py").write_text(READER)
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "https://arxiv.org/abs/1908.10084",
                             "file": f"bench/configs/{name}.json",
                             "reduced": ["n_layers"], "why": "CPU rehearsal"})
    bench["workloads"].append({"name": cell, "config": name, "traffic": "tiny",
                               "chips": 1, "why": "CPU rehearsal"})
    # the new cell reads what its family's full cell reads, and one more
    for m in bench["per_layer"]:
        if f"{src}.cnndm" in m.get("workloads", ()):
            m["workloads"].append(cell)
    bench["per_layer"].append({"name": "tiny_requests", "unit": "req",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine and admission",
                               "moves": "summaries_per_s", "workloads": [cell]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
