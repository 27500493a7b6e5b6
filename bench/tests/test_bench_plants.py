"""The controls and faults of ``bench/harness/plants.py``, planted under a
CPU rehearsal of the tiny cell: each must make ``correct`` come out false,
and the program as configured must not."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH), str(Path(__file__).parent)]

import pytest  # noqa: E402

import bench_tiny_cell  # noqa: E402
import control  # noqa: E402
from harness import check, plants, runner, serve, spec, traffic  # noqa: E402

PLANTS = ["none", "fp8_encoder", "bf16_objective", "state_unchanged",
          "negated_spins", "dropped_item", "half_tokens"]


@pytest.fixture(scope="module", params=["cobi", "mcmc"])
def readings(request, tmp_path_factory):
    root = bench_tiny_cell.make_root(tmp_path_factory.mktemp("bench"),
                                     solver=request.param)
    cell = spec.cell(bench_tiny_cell.cell_name(request.param), root)
    rows = control.control(cell, [2**31 + 21], PLANTS, 3.0, plants, runner,
                           serve, traffic, ref_sample=4)
    return cell, {r["plant"]: r for r in rows}


@pytest.mark.parametrize("plant", PLANTS)
def test_plant_decides_correct(readings, plant):
    cell, rows = readings
    row = rows[plant]
    limits = {**cell.limits, **runner.HARNESS_LIMITS}
    ok = check.verdict(row, limits) and row["failed"] == 0
    failing = [k for k in limits if row[k] > limits[k]["limit"]]
    assert ok == (plant == "none"), (plant, failing, row)
