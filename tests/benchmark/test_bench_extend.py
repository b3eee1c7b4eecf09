"""A configuration, a cell and a per-layer metric arrive as new files,
with their entries in BENCHMARK.json: the harness finds them by name and
no file it already has changes."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import bench_testing

from benchmark import harness, tracefile

# a set-full configuration: its configs/, traffic/ and checkers/ files,
# the checker module with its own generator and plain reference
SET_FULL = Path(__file__).resolve().parent / "extend_set_full"


def copy_of_benchmark(tmp_path) -> tuple[Path, dict]:
    """A copy of benchmark/ and its files' bytes as copied."""
    root = tmp_path / "benchmark"
    shutil.copytree(harness.HERE, root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return root, files_of(root)


def files_of(root: Path) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts
            and ".cache" not in p.parts}


def test_new_config_cell_and_metric_are_files_alone(tmp_path, monkeypatch):
    root, before = copy_of_benchmark(tmp_path)

    config = json.loads((root / "configs" / "cas_register.json").read_text())
    config["threads_per_key"], config["readers_per_key"] = 3, 1
    (root / "configs" / "cas_register_3.json").write_text(json.dumps(config))
    shutil.copy(bench_testing.TRAFFIC / "t_register.json",
                root / "traffic" / "t_3000.json")
    (root / "metrics" / "checks_in_window.py").write_text(
        '"""Checks the window held."""\n\n\n'
        "def read(run):\n    return float(len(run.checks))\n")

    spec = json.loads(harness.SPEC.read_text())
    spec["configs"].append({"name": "cas_register_3", "source": "x",
                            "file": "benchmark/configs/cas_register_3.json",
                            "reduced": [], "why": "three clients"})
    spec["workloads"].append({"name": "t.new", "config": "cas_register_3",
                              "traffic": "t_3000", "chips": 1,
                              "why": "added by files"})
    spec["per_layer"].append({"name": "checks_in_window", "unit": "checks",
                              "better": "higher", "source": "host_clock",
                              "layer": "entry", "moves": "verified_ops_per_s",
                              "workloads": ["t.new"]})
    bench = harness.Bench(spec, root=root)

    # the CPU has no device plane to trace: stand in a summary for it
    monkeypatch.setattr(tracefile, "read", lambda d: tracefile.Summary(
        window_s=1.0, busy_s={"/device:TPU:0": 0.25},
        op_seconds={"fusion": 0.25}, gaps=[(0.75, "benchmark.check")]))
    out = harness.run(bench, "t.new", 11, 0.1, True, require_tpu=False,
                      persistent_cache=False)
    assert out["correct"] is True, out["compared"]
    assert out["metrics"]["checks_in_window"]["value"] == out["attempted"]
    # the per-layer metrics that name other cells stay out of this one
    assert set(out["metrics"]) == {"checks_in_window"}
    assert out["device"]["busy_s"] == 0.25
    after = files_of(root)
    assert {k: v for k, v in after.items() if k in before} == before


def test_config_of_another_kind_is_files_alone(tmp_path):
    """A set-full configuration, whose checker module brings its own
    generator and plain reference, runs through the harness as new
    files: a sound run is correct, its control is not."""
    root, before = copy_of_benchmark(tmp_path)
    for kind in ("configs", "traffic", "checkers"):
        for f in (SET_FULL / kind).iterdir():
            assert not (root / kind / f.name).exists()
            shutil.copy(f, root / kind / f.name)

    spec = json.loads(harness.SPEC.read_text())
    spec["configs"].append({"name": "set_full", "source": "x",
                            "file": "benchmark/configs/set_full.json",
                            "reduced": [], "why": "a grow-only set"})
    spec["workloads"].append({"name": "t.set", "config": "set_full",
                              "traffic": "t_set", "chips": 1,
                              "why": "added by files"})
    bench = harness.Bench(spec, root=root)
    assert bench.mix(bench.cell("t.set")).pool == 2

    def run(**kw):
        return harness.run(bench, "t.set", 2**31 + 5, 0.1, False,
                           require_tpu=False, persistent_cache=False, **kw)

    out = run()
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"verified_ops_per_s", "setup_s"}
    # the control calls an element lost only where no read ever held it,
    # so it accepts the element the odd history loses
    control = run(control_checks=2)
    assert control["correct"] is False
    assert control["compared"]["verdict_mismatches"]["value"] >= 1
    assert {k: v for k, v in files_of(root).items() if k in before} == before
