"""CPU rehearsal of the ``es-cobi-moonlight.cnndm`` cell, added to a copy of
the benchmark from new files alone: the cell's configuration with the
encoder cut to a small ``mla_moe`` backbone (registered here under its own
name), a small traffic mix, and the cell's limits.  The harness runs it as
it runs the chip's cells (Pallas in interpret mode)."""

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(BENCH)]

import pytest  # noqa: E402

import control  # noqa: E402
from harness import check, mla_moe_counts, plants, runner, serve, spec, traffic  # noqa: E402
from harness.context import Context  # noqa: E402
from harness.trace import DeviceTrace, Event  # noqa: E402

from repro.configs.base import get_config, register  # noqa: E402

SOURCE = "es-cobi-moonlight"
CELL = "es-cobi-moonlight-tiny.tiny"
ARCH = register(get_config("moonlight-16b-a3b").reduced()
                .replace(name="moonlight-16b-a3b-tiny"))
REDUCED = {"arch": ARCH.name, "n_layers": ARCH.n_layers, "d_model": 64,
           "n_heads": 4, "n_kv_heads": 4, "d_ff": 128, "vocab_size": 512,
           "max_seq_len": 512, "param_dtype": "bfloat16"}
ARCH_KEYS = {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16, "num_experts": 8,
             "experts_held": 4, "top_k": 2, "d_ff_expert": 64,
             "d_ff_shared": 128}
# The small encoder's own limit: on the CPU its bf16 program read 1 - cos
# of 4.5e-4 to 2.3e-3 against the float32 reference (4 seeds; the largest
# where a near-tie routes a token to another expert than the reference
# does), the fp8 control 1.2e-2 to 3.7e-2.  The other limits are the full
# cell's but the objective's, which is the small sbert cell's
# (bench/tests/bench_tiny_cell.py).
TINY_EMBED_LIMIT = 5e-3
TINY_OBJECTIVE_LIMIT = 1e-5
METRICS = ("moe_encoder_mfu_pct", "expert_load_max_over_mean")
# The generic readers of the layers this cell runs, as the sbert cells'.
GENERIC = ("window_latency_p50_ms", "window_latency_p95_ms", "admit_wait_ms",
           "queue_wait_ms", "driver_busy_pct", "encode_ms", "encoder_host_ms",
           "solve_ms", "formulate_ms", "decomposed_solve_ms", "farm_host_ms",
           "cobi_dynamics_roofline", "device_idle_pct", "idle_host_pct",
           "idle_empty_pct")
# Silent in the rehearsal: a CPU profile has no device-program line or
# Mosaic kernel, and no tiny request spans more than one window.
CPU_SILENT = ("moe_encoder_mfu_pct", "cobi_dynamics_roofline",
              "decomposed_solve_ms")


def make_root(tmp: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    name = CELL.split(".")[0]
    config = json.loads((BENCH / "configs" / f"{SOURCE}.json").read_text())
    config["name"] = name
    config["encoder"].update(REDUCED)
    config["encoder"]["arch_keys"].update(ARCH_KEYS)
    config["solve"].update(iterations=2)
    config["backend"]["n_chips"] = 2
    (tmp / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))
    mix = json.loads((BENCH / "traffic" / "cnndm.json").read_text())
    mix.update(name="tiny", warmup_seconds=1.0,
               sentences=dict(mix["sentences"], median=9, sigma=0.4, max=64),
               warm={"encoder_batches": [4], "farm_max_bins": 2, "workers": 2})
    (tmp / "bench" / "traffic" / "tiny.json").write_text(json.dumps(mix))
    own = json.loads((BENCH / "cells" / f"{SOURCE}.cnndm.json").read_text())
    own["rate"] = 2.0
    own["limits"]["embed_gap"]["limit"] = TINY_EMBED_LIMIT
    own["limits"]["objective_gap"]["limit"] = TINY_OBJECTIVE_LIMIT
    (tmp / "bench" / "cells" / f"{CELL}.json").write_text(json.dumps(own))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "CPU rehearsal",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "CPU rehearsal"})
    bench["workloads"].append({"name": CELL, "config": name, "traffic": "tiny",
                               "chips": 1, "why": "CPU rehearsal"})
    for m in bench["per_layer"]:
        if f"{SOURCE}.cnndm" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return spec.cell(CELL, make_root(tmp_path_factory.mktemp("bench")))


def test_the_cell_resolves_with_its_metrics():
    full = spec.cell(f"{SOURCE}.cnndm")
    assert set(full.readers) == set(METRICS + GENERIC)
    assert full.reference.__name__.endswith("moonlight_mla_moe")
    assert full.config["encoder"]["arch"] == "moonlight-16b-a3b"


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_of_the_cell(cell, trace):
    result = runner.run_cell(cell, seed=2**31 + 17, seconds=3.0, trace=trace,
                             t_start=time.perf_counter(),
                             peaks=spec.peaks("TPU v5 lite", spec.ROOT),
                             ref_sample=4)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 6
    checks = result["checks"]
    assert checks["window_compiles"] == {"value": 0.0, "limit": 0}
    assert checks["energy_gap"]["value"] == 0.0
    assert checks["bad_selections"]["value"] == 0.0
    json.dumps(result)
    if trace:
        metrics = result["metrics"]
        assert metrics["expert_load_max_over_mean"]["value"] >= 1.0
        # moe_encoder_mfu_pct's arithmetic: test_mfu_reader
        assert set(metrics) == set(METRICS + GENERIC) - set(CPU_SILENT)
    else:
        assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.fixture(scope="module")
def readings(cell):
    rows = control.control(cell, [2**31 + 21], ["none", "fp8_encoder"], 3.0,
                           plants, runner, serve, traffic, ref_sample=4)
    return {r["plant"]: r for r in rows}


@pytest.mark.parametrize("plant", ["none", "fp8_encoder"])
def test_fp8_encoder_fails_the_cell(cell, readings, plant):
    row = readings[plant]
    limits = {**cell.limits, **runner.HARNESS_LIMITS}
    ok = check.verdict(row, limits) and row["failed"] == 0
    assert ok == (plant == "none"), row
    if plant == "fp8_encoder":  # by the encoder's limit
        assert row["embed_gap"] > limits["embed_gap"]["limit"]


def test_flop_count_matches_the_published_sums():
    """About 2.41 GFLOP a token at 2048 positions: every layer's latent
    attention, the dense layer, 26 layers of shared experts and router, and
    the held experts' 6 x 8 / 64 = 0.75 choices a token."""
    enc = spec.load_json(BENCH / "configs" / f"{SOURCE}.json")["encoder"]
    n = 2048
    held = 0.75 * n * (enc["n_layers"] - 1)
    per_token = (mla_moe_counts.row_flops(enc, n)
                 + mla_moe_counts.held_expert_flops(enc, held)) / n
    assert per_token == pytest.approx(2.41e9, rel=0.01)
    # causal attention grows with the row, the rest with its tokens
    quad = mla_moe_counts.row_flops(enc, 2 * n) - 2 * mla_moe_counts.row_flops(enc, n)
    assert quad > 0


def _span(i, name, t0, t1, parent=None, **attrs):
    return {"kind": "span", "id": i, "name": name, "trace": None,
            "parent": parent, "track": "encoder", "t0": t0, "t1": t1,
            "attrs": attrs}


def test_mfu_and_load_readers_on_a_constructed_trace():
    full = spec.cell(f"{SOURCE}.cnndm")
    spans = [_span(1, "encoder.batch", 0.9, 1.7),
             _span(2, "encoder.launch", 1.0, 1.5, parent=1),
             _span(3, "encoder.route", 1.5, 1.6, parent=1, held_choices=900,
                   expert_max=30, expert_mean=10.0),
             _span(4, "encode.job", 1.0, 1.5, n_tokens=700),
             _span(5, "encode.job", 1.0, 1.5, n_tokens=300),
             _span(6, "encoder.route", 5.0, 5.1, expert_max=20,
                   expert_mean=10.0)]
    trace = DeviceTrace([], [Event("jit__embed_batch", 1.0, 1.25)], 0.0, 9.0)
    peaks = spec.peaks("TPU v5 lite", spec.ROOT)
    ctx = Context(full, [], [], spans, trace, peaks, {}, (0.0, 9.0))
    enc = full.config["encoder"]
    flops = (mla_moe_counts.row_flops(enc, 700) + mla_moe_counts.row_flops(enc, 300)
             + mla_moe_counts.held_expert_flops(enc, 900))
    want = 100.0 * flops / (0.25 * peaks["bf16_flops_per_s"])
    assert full.readers["moe_encoder_mfu_pct"](ctx) == pytest.approx(want)
    assert full.readers["expert_load_max_over_mean"](ctx) == pytest.approx(2.5)
    # a program without the routed encoder's spans: nothing to read
    bare = Context(full, [], [], spans[:2] + spans[3:5], trace, peaks, {},
                   (0.0, 9.0))
    assert full.readers["moe_encoder_mfu_pct"](bare) is None
    assert full.readers["expert_load_max_over_mean"](bare) is None
