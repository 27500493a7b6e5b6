"""CPU rehearsal of a whole run through the harness's functions, on a cell
added from new files alone (reduced encoder; Pallas in interpret mode)."""

import json
import subprocess
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(Path(__file__).parent)]

import pytest  # noqa: E402

import bench_tiny_cell  # noqa: E402
from harness import check, runner, spec  # noqa: E402


def test_every_entry_resolves_by_name():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"])
        assert cell.mix["name"] == w["traffic"]
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.limits and cell.rate > 0
    for c in bench["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        assert spec.load_json(spec.ROOT / c["file"])["name"] == c["name"]
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("no such chip")


@pytest.fixture(scope="module", params=["cobi", "mcmc"])
def tiny(request, tmp_path_factory):
    root = bench_tiny_cell.make_root(tmp_path_factory.mktemp("bench"),
                                     solver=request.param)
    return request.param, spec.cell(bench_tiny_cell.cell_name(request.param), root)


LAYER = {"cobi": ("farm_host_ms",), "mcmc": ("bank_job_ms",)}


@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_of_a_new_cell(tiny, trace):
    solver, cell = tiny
    result = runner.run_cell(cell, seed=2**31 + 17, seconds=3.0, trace=trace,
                             t_start=time.perf_counter(),
                             peaks=spec.peaks("TPU v5 lite", spec.ROOT),
                             ref_sample=4)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 6
    assert list(result)[-1] == "checks"
    checks = result["checks"]
    assert checks["window_compiles"] == {"value": 0.0, "limit": 0}
    # the taps saw the family's jobs: both anneal checks read a number
    assert checks["energy_gap"]["value"] == 0.0
    assert 0.0 <= checks["anneal_rank"]["value"] < 1.0
    json.dumps(result)
    if trace:
        assert result["metrics"]["tiny_requests"]["value"] == 6.0
        for name in ("encode_ms", "solve_ms", "admit_wait_ms") + LAYER[solver]:
            assert result["metrics"][name]["value"] > 0.0
        assert 0.0 < result["device"]["busy_s"] <= result["device"]["window_s"]
        assert result["breakdown"]["device_ops"]
    else:
        names = {m["name"] for m in cell.end_to_end}
        assert set(result["metrics"]) == names
        assert 0.0 < result["metrics"]["quality_norm_obj"]["value"] <= 1.0 + 1e-9


def test_empty_job_lists_fail_the_anneal_checks():
    assert check.energy_gap([]) == float("inf")
    assert check.anneal_rank([], None) == float("inf")


def test_command_refuses_the_cpu():
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload",
           "es-cobi-sbert.cnndm", "--seed", "1", "--seconds", "1"]
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_fails_without_the_program(tmp_path):
    import shutil
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
           "es-cobi-sbert.cnndm", "--seed", "1", "--seconds", "1"]
    p = subprocess.run(cmd, capture_output=True, text=True, cwd=tmp_path,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
