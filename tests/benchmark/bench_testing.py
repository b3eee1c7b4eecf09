"""The benchmark's cells at test size, for the CPU.

Each full-size cell of BENCHMARK.json gets a twin on the same
configuration whose traffic (``tests/benchmark/traffic``) holds what a
test run can: the same generator, plants and checker path, fewer ops.
"""
from __future__ import annotations

import copy
from pathlib import Path

from benchmark import harness

TRAFFIC = Path(__file__).resolve().parent / "traffic"

# test cell -> (configuration, test traffic, chips)
CELLS = {
    "t.register": ("cas_register", "t_register", 1),
    "t.keys": ("independent_register", "t_keys", 1),
    "t.keys.mesh4": ("independent_register", "t_keys_mesh4", 4),
}
SEED = 2**31 + 977


def bench() -> harness.Bench:
    b = harness.load()
    spec = copy.deepcopy(b.spec)
    spec["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": k, "why": "test"}
        for n, (c, t, k) in CELLS.items()]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    return harness.Bench(spec, traffic_dir=TRAFFIC)


def run(cell: str, seconds: float = 0.1, seed: int = SEED, **kw) -> dict:
    """One run of a test cell on the CPU, without the compile cache,
    whose window checks every history of the pool once."""
    b = bench()
    return harness.run(b, cell, seed, seconds, False, require_tpu=False,
                       persistent_cache=False, **kw)
