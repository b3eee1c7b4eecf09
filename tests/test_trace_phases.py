"""Checker phase spans (doc/observability.md "Checker phase spans"): one
``trace.phase`` API whose spans reach the JAX profiler's trace and, with
a run tracer installed, the ``checker`` track of ``trace.json``."""
from __future__ import annotations

import contextvars
import json
import subprocess
import sys
import threading
from collections import defaultdict
from pathlib import Path

import pytest

from jepsen_tpu import independent
from jepsen_tpu import trace as trace_mod
from jepsen_tpu.checker.ladder import Backend, BackendLadder, Unavailable
from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.trace.flight import FlightRecorder
from jepsen_tpu.trace.perfetto import PerfettoSink

pytestmark = pytest.mark.trace

SPANS = {"check", "encode.ir", "encode.split", "encode.stream",
         "ladder.rung", "dispatch.pad", "dispatch.call",
         "dispatch.readback", "settle.report", "settle.explain"}


def register_history(rounds: int, key=None, bad: bool = False) -> list:
    """10 processes writing at once, ``rounds`` times, then reading the
    last value; ten ops in flight keep the check on the frontier scan.
    ``bad`` makes the first read return a value never written."""
    h: list = []

    def op(t, p, f, v):
        h.append({"type": t, "process": p, "f": f, "time": len(h),
                  "value": v if key is None else [key, v]})

    for r in range(rounds):
        for t in ("invoke", "ok"):
            for p in range(10):
                op(t, p, "write", (r + p) % 5)
    last = (rounds - 1 + 9) % 5
    for p in range(10):
        op("invoke", p, "read", None)
        op("ok", p, "read", 99 if bad and p == 0 else last)
    return h


def keyed_history() -> list:
    return [op for k in range(4)
            for op in register_history(2, key=k, bad=k == 1)]


def profiled_spans(tmp_path, run) -> list[tuple]:
    """[(thread line, name, stats)] of the phase spans ``run()`` leaves
    in a profiler trace."""
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
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in SPANS:
                    out.append((i, ev.name, dict(ev.stats)))
    return out


def by_check(spans) -> dict:
    out = defaultdict(list)
    for line, name, stats in spans:
        out[stats["check"]].append((line, name, stats))
    return out


def test_check_phases_reach_the_profiler(tmp_path):
    chk = LinearizableChecker(accelerator="tpu")
    bad = register_history(3, bad=True)
    results = []

    def run():
        results.append(chk.check({}, bad, {}))
        results.append(chk.check({}, bad, {}))
        results.append(independent.checker(
            LinearizableChecker(accelerator="tpu")).check(
                {}, keyed_history(), {}))

    spans = profiled_spans(tmp_path, run)
    assert [r["valid?"] for r in results] == [False, False, False]
    assert "-frontier" in results[0]["algorithm"]
    assert {name for _, name, _ in spans} == SPANS
    checks = by_check(spans)
    assert len(checks) == 3
    first, second, keyed = (checks[k] for k in sorted(checks))
    for one in (first, second, keyed):
        assert [n for _, n, _ in one].count("check") == 1
    # the rung and its dispatch run on the ladder's watchdog thread,
    # under the id of the check that dispatched them
    for one in (first, second):
        [check_line] = [ln for ln, n, _ in one if n == "check"]
        off_thread = {n for ln, n, _ in one if ln != check_line}
        assert off_thread == {"ladder.rung", "dispatch.pad",
                              "dispatch.call", "dispatch.readback"}
        [rung] = [s for _, n, s in one if n == "ladder.rung"]
        assert rung["backend"] == "jitlin-device"
        assert rung["outcome"] == "settled"
        assert {"settle.report", "settle.explain"} <= \
            {n for _, n, _ in one}

    def lowered(one):
        return [s["lowered"] for _, n, s in one if n == "dispatch.call"]

    # a fresh checker lowers its scan; a second check through it does not
    assert lowered(first) == [1]
    assert lowered(second) == [0]
    assert lowered(keyed) == [1]
    stats = {n: s for _, n, s in keyed}
    assert stats["check"]["keys"] == 4
    assert stats["check"]["ops"] == len(keyed_history())
    assert stats["encode.split"]["keys"] == 4
    assert stats["encode.stream"]["keys"] == 4
    assert stats["dispatch.pad"]["keys"] == 4
    assert stats["dispatch.pad"]["events"] == \
        stats["encode.stream"]["events"]
    # the dense scan steps over each key's 30 returns, padded to 32
    assert stats["dispatch.pad"]["returns"] == 4 * 30
    assert stats["dispatch.pad"]["steps"] == 4 * 32
    assert stats["settle.explain"]["keys"] == 1


def test_check_phases_on_the_checker_track(tmp_path):
    p = tmp_path / "trace.json"
    tracer = trace_mod.RunTracer(perfetto=PerfettoSink(p))
    with trace_mod.use(tracer):
        LinearizableChecker(accelerator="tpu").check(
            {}, register_history(3, bad=True), {})
    tracer.close()
    evs = json.loads(p.read_text())
    tids = {ev["tid"]: ev["args"]["name"] for ev in evs
            if ev.get("ph") == "M" and ev.get("name") == "thread_name"}
    slices = [ev for ev in evs if ev.get("ph") == "X"
              and tids.get(ev.get("tid")) == trace_mod.TRACK_CHECKER]
    assert {ev["name"] for ev in slices} == SPANS - {"encode.split"}
    assert len({ev["args"]["check"] for ev in slices}) == 1
    [check] = [ev for ev in slices if ev["name"] == "check"]
    for ev in slices:
        assert check["ts"] <= ev["ts"]
        assert ev["ts"] + ev["dur"] <= check["ts"] + check["dur"] + 1
    [call] = [ev for ev in slices if ev["name"] == "dispatch.call"]
    assert call["args"]["lowered"] == 1


def recorded(fn) -> list[dict]:
    """The checker-track slices ``fn()`` emits."""
    tracer = trace_mod.RunTracer(flight=FlightRecorder(256))
    with trace_mod.use(tracer):
        fn()
    return [ev for ev in tracer.flight.snapshot()
            if isinstance(ev, dict) and ev["track"] == trace_mod.TRACK_CHECKER]


def test_nested_check_is_no_span_and_keeps_the_id():
    def run():
        with trace_mod.phase("check", ops=3) as outer:
            with trace_mod.phase("check", ops=1):
                with trace_mod.phase("encode.ir", events=2):
                    pass
            outer.set(keys=2)
        with trace_mod.phase("encode.ir"):
            pass

    evs = recorded(run)
    assert [ev["name"] for ev in evs] == ["encode.ir", "check", "encode.ir"]
    inner, check, after = evs
    cid = check["args"]["check"]
    assert check["args"] == {"ops": 3, "keys": 2, "check": cid}
    assert inner["args"] == {"events": 2, "check": cid}
    # outside a check a phase carries no id
    assert after["args"] == {}


def test_each_outermost_check_takes_a_new_id():
    def run():
        for _ in range(2):
            with trace_mod.phase("check"):
                pass

    first, second = (ev["args"]["check"] for ev in recorded(run))
    assert first != second


def test_check_id_follows_a_copied_context_to_another_thread():
    seen = []

    def worker():
        with trace_mod.phase("ladder.rung", backend="b"):
            seen.append(trace_mod._CHECK_ID.get())

    def run():
        with trace_mod.phase("check"):
            t = threading.Thread(target=contextvars.copy_context().run,
                                 args=(worker,))
            t.start()
            t.join()
            bare = threading.Thread(target=worker)
            bare.start()
            bare.join()

    evs = recorded(run)
    check = [ev for ev in evs if ev["name"] == "check"][0]
    assert seen[0] == check["args"]["check"]
    assert seen[1] is None


def unavailable(ctx):
    raise Unavailable("out of regime")


def broken(ctx):
    raise RuntimeError("rung failed")


@pytest.mark.parametrize("fn,outcome", [
    (lambda ctx: "verdict", "settled"),
    (lambda ctx: None, "declined"),
    (unavailable, "unavailable"),
    (broken, "error"),
])
@pytest.mark.parametrize("device", [True, False])
def test_ladder_rung_span_outcome(fn, outcome, device):
    ladder = BackendLadder([Backend("rung-a", fn, device=device),
                            Backend("cpu", lambda ctx: "host")],
                           watchdog_s=30)
    evs = recorded(lambda: ladder.run({}))
    rungs = [ev for ev in evs if ev["name"] == "ladder.rung"]
    assert rungs[0]["args"]["backend"] == "rung-a"
    assert rungs[0]["args"]["outcome"] == outcome


def test_phase_does_not_import_jax():
    code = ("import sys\n"
            "from jepsen_tpu import trace\n"
            "with trace.phase('check', ops=1) as s:\n"
            "    with trace.phase('encode.ir'):\n"
            "        s.set(keys=1)\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True,
                   cwd=str(Path(__file__).resolve().parents[1]),
                   timeout=120)
