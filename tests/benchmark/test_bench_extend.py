"""A configuration, a cell and a per-layer metric arrive as new files,
with their entries in BENCHMARK.json: the harness finds them by name and
no file it already has changes."""
from __future__ import annotations

import json
import shutil

import bench_testing

from benchmark import harness, tracefile


def test_new_config_cell_and_metric_are_files_alone(tmp_path, monkeypatch):
    root = tmp_path / "benchmark"
    shutil.copytree(harness.HERE, root,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    before = {p.relative_to(root): p.read_bytes()
              for p in root.rglob("*") if p.is_file()}

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
    after = {p.relative_to(root): p.read_bytes()
             for p in root.rglob("*") if p.is_file()
             and "__pycache__" not in p.parts and ".cache" not in p.parts}
    assert {k: v for k, v in after.items() if k in before} == before
