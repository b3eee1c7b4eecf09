"""``correct`` on the CPU at test size: a sound run passes and the
control fails (the faults are in test_bench_faults.py)."""
from __future__ import annotations

import bench_testing
import pytest


def numbers(out: dict) -> dict:
    return {k: v["value"] for k, v in out["compared"].items()}


@pytest.mark.parametrize("cell", ["t.register", "t.keys"])
def test_sound_run_is_correct(cell):
    out = bench_testing.run(cell)
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"verified_ops_per_s", "setup_s"}
    assert m["verified_ops_per_s"]["value"] > 0
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("cell", ["t.register", "t.keys"])
def test_control_is_not_correct(cell):
    """The reference with real-time order dropped, in the program's
    place, over as many checks as the pool holds: it accepts the stale
    reads."""
    pool = 4 if cell == "t.register" else 2
    out = bench_testing.run(cell, control_checks=pool)
    assert out["correct"] is False
    assert numbers(out)["verdict_mismatches"] >= 1


def test_mesh_cell_is_correct_unbroken():
    out = bench_testing.run("t.keys.mesh4")
    assert out["correct"] is True, out["compared"]
    assert out["device"]["count"] == 4
