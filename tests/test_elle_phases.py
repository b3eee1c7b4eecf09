"""The Elle list-append checker's phase spans (doc/observability.md
"Checker phase spans"), the closure screen's program name, and the
count of the screen's work that its roofline reads."""
from __future__ import annotations

from collections import defaultdict

import pytest

from jepsen_tpu.workloads import append

pytestmark = pytest.mark.trace

ELLE_SPANS = {"check", "encode.ir", "encode.elle_build",
              "dispatch.elle_cluster", "dispatch.elle_screen",
              "settle.elle_classify"}


def txn_history() -> list:
    """Ten concurrent txns on five keys, then a read skew (G-single):
    T1 reads x empty and y with T2's append, T2 appends to both."""
    h: list = []

    def both(block):
        for t in ("invoke", "ok"):
            for p, mops in block:
                h.append({"type": t, "process": p, "f": "txn", "value": [
                    [f, k, None if t == "invoke" and f == "r" else v]
                    for f, k, v in mops]})

    both([(p, [["append", p % 5, p + 1], ["r", (p + 1) % 5, []]])
          for p in range(5)])
    both([(p, [["r", p % 5, [p + 1]]]) for p in range(5, 10)])
    both([(10, [["r", 100, []], ["r", 101, [1]]]),
          (11, [["append", 100, 1], ["append", 101, 1]]),
          (12, [["r", 100, [1]]])])
    return h


def profiled_spans(tmp_path, run) -> list[tuple]:
    """[(name, stats)] of the Elle phase spans ``run()`` leaves in a
    profiler trace."""
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.rglob("*.xplane.pb")
    return [(ev.name, dict(ev.stats))
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in ELLE_SPANS]


def test_traced_check_names_its_phases(tmp_path):
    h = txn_history()
    out = {}

    def run():
        out["r"] = append.checker(accelerator="tpu").check({}, h, {})

    spans = profiled_spans(tmp_path, run)
    assert out["r"]["valid?"] is False
    assert "G-single" in out["r"]["anomaly-types"]
    by_name = defaultdict(list)
    for name, stats in spans:
        by_name[name].append(stats)
    assert set(by_name) == ELLE_SPANS
    # one check: every span carries its id
    assert len({s["check"] for ss in by_name.values() for s in ss}) == 1
    [chk] = by_name["check"]
    assert chk["ops"] == len(h)
    [build] = by_name["encode.elle_build"]
    assert build["events"] == len(h) and build["txns"] == len(h) // 2
    assert build["edges"] > 0
    [cluster] = by_name["dispatch.elle_cluster"]
    assert cluster["clusters"] >= 1
    assert cluster["device_screened"] == cluster["clusters"]
    assert cluster["host_screened"] == cluster["oversized"] == 0
    for screen in by_name["dispatch.elle_screen"]:
        assert screen["b"] >= 8 and screen["v"] >= 8 and screen["e"] >= 64
        assert 2 ** screen["steps"] >= screen["v"] > 2 ** (screen["steps"] - 1)
    classify = by_name["settle.elle_classify"]
    assert len(classify) == 2      # the live clusters, then the result map
    assert sum(s.get("clusters", 0) for s in classify) >= 1


def test_auto_screens_small_clusters_on_the_host(tmp_path):
    h = txn_history()
    spans = profiled_spans(
        tmp_path, lambda: append.checker().check({}, h, {}))
    [cluster] = [s for n, s in spans if n == "dispatch.elle_cluster"]
    assert cluster["host_screened"] == cluster["clusters"] >= 1
    assert cluster["device_screened"] == 0
    assert not [n for n, _ in spans if n == "dispatch.elle_screen"]


def test_screen_program_has_its_own_name():
    import numpy as np

    from jepsen_tpu.ops import scc
    fn = scc._screen_kernel(8, 16, 64)
    z = np.zeros(64, np.int32)
    text = fn.lower(z, z, z, np.zeros(64, bool)).as_text()
    assert text.splitlines()[0].startswith("module @jit_cluster_screen")


def test_screen_steps():
    from jepsen_tpu.ops.scc import screen_steps
    assert [screen_steps(v) for v in (1, 2, 8, 9, 16, 1024)] == \
        [1, 1, 3, 4, 4, 10]


def test_screen_count_by_hand():
    """One [8, 16, 16] screen of 4 steps over 64 edge slots."""
    from benchmark import elle_screen
    assert elle_screen.flops(8, 16, 64, 4) == 2 * 8 * 16 ** 3 * 4 == 262_144
    # edges 64 x 13, adjacency 8 x 256 x 2, 4 steps x (read + write)
    # 8 x 256 x 2 each, diagonal 8 x 16 x 2, verdicts 8
    assert elle_screen.hbm_bytes(8, 16, 64, 4) == \
        832 + 4096 + 32_768 + 256 + 8 == 37_960
    # memory-bound on a v5e: the bytes set the least time
    assert elle_screen.roofline_seconds(8, 16, 64, 4, "TPU v5 lite") == \
        pytest.approx(37_960 / 819e9)
    assert elle_screen.roofline_seconds(8, 16, 64, 4, "cpu") is None
