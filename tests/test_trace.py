"""Causal-trace tier: run-wide spans, Perfetto export, flight recorder
(doc/observability.md "Causal trace").

Covers the tentpole's load-bearing claims: concurrent emission never
tears the streamed JSON, the flight ring's wraparound is exact, a
SIGKILL'd ``--trace`` run leaves a loadable trace prefix AND a
flight-recorder dump (the stall watchdog's), the offline
``jepsen-tpu trace`` derivation mints the SAME per-op trace ids as the
live stream, and an invalid run's explain instant links back to the
anomalous op's dispatch slice by trace id. Satellite regressions:
``tracing.Tracer``'s per-tracer seeded RNG and ``TracedClient``'s
symmetric open peeling (the two-open pin).
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

import pytest

import jepsen_tpu.generator as gen
from jepsen_tpu import core, nemesis as nemesis_mod, store, tracing
from jepsen_tpu import trace as trace_mod
from jepsen_tpu.checker.linearizable import linearizable
from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test
from jepsen_tpu.trace.flight import FlightRecorder
from jepsen_tpu.trace.perfetto import PerfettoSink, read_trace_events

pytestmark = pytest.mark.trace


def _strict_load(path) -> list:
    """A cleanly-closed trace.json must be STRICT JSON."""
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    assert isinstance(data, list)
    return data


def _track_names(events) -> dict:
    return {ev["tid"]: ev["args"]["name"] for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "thread_name"}


def _tracks_used(events) -> set:
    names = _track_names(events)
    return {names[ev["tid"]] for ev in events
            if ev.get("ph") != "M" and ev.get("tid") in names}


def _op_ids(events) -> set:
    """{(f, trace_id)} of the op slices (X live/derived, B in-flight)."""
    return {(ev["args"]["f"], ev["args"]["trace_id"]) for ev in events
            if ev.get("ph") in ("B", "X")
            and "trace_id" in (ev.get("args") or {})}


# ---------------------------------------------------------------------------
# Model basics
# ---------------------------------------------------------------------------

class TestTraceIds:
    def test_pure_function_of_process_and_time(self):
        assert trace_mod.trace_id_for(3, 12345) == \
            trace_mod.trace_id_for(3, 12345)
        assert trace_mod.trace_id_for(3, 12345) != \
            trace_mod.trace_id_for(4, 12345)
        assert trace_mod.trace_id_for(3, 12345) != \
            trace_mod.trace_id_for(3, 12346)

    def test_null_tracer_is_inert(self):
        t = trace_mod.NULL_TRACER
        assert not t.enabled and t.op_sink() is None
        t.instant("scheduler", "stall")  # no-ops, never raises
        t.window_begin("nemesis", "net", wid="w")
        with t.span("checker-ladder", "rung"):
            pass
        assert t.dump_flight("/nonexistent/x", reason="test") is False


class TestKnobs:
    def test_trace_enabled_coercion(self, monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_TRACE", raising=False)
        assert trace_mod.trace_enabled({"trace": True}) is True
        assert trace_mod.trace_enabled({"trace": "yes"}) is True
        assert trace_mod.trace_enabled({"trace": 0}) is False
        assert trace_mod.trace_enabled({}) is False
        # garbage reads as unset, then the env twin decides
        monkeypatch.setenv("JEPSEN_TPU_TRACE", "1")
        assert trace_mod.trace_enabled({"trace": "banana"}) is True
        assert trace_mod.trace_enabled({}) is True
        monkeypatch.setenv("JEPSEN_TPU_TRACE", "off")
        assert trace_mod.trace_enabled({}) is False

    def test_flight_capacity_coercion(self, monkeypatch):
        monkeypatch.delenv("JEPSEN_TPU_FLIGHT_RECORDER_EVENTS",
                           raising=False)
        assert trace_mod.flight_recorder_events({}) == \
            trace_mod.DEFAULT_FLIGHT_EVENTS
        assert trace_mod.flight_recorder_events(
            {"flight_recorder_events": 16}) == 16
        assert trace_mod.flight_recorder_events(
            {"flight_recorder_events": "64"}) == 64
        assert trace_mod.flight_recorder_events(
            {"flight_recorder_events": 0}) == 0
        assert trace_mod.flight_recorder_events(
            {"flight_recorder_events": "garbage"}) == \
            trace_mod.DEFAULT_FLIGHT_EVENTS
        monkeypatch.setenv("JEPSEN_TPU_FLIGHT_RECORDER_EVENTS", "8")
        assert trace_mod.flight_recorder_events({}) == 8

    def test_preflight_rows(self):
        from jepsen_tpu.analysis import preflight as preflight_mod
        t = core.prepare_test(noop_test(
            flight_recorder_events="garbage", trace="banana"))
        codes = {d.code for d in preflight_mod.preflight(t)}
        assert "KNB001" in codes

    def test_zero_capacity_disables_recorder(self):
        t = trace_mod.for_test({"flight_recorder_events": 0})
        assert t is trace_mod.NULL_TRACER


# ---------------------------------------------------------------------------
# Perfetto sink
# ---------------------------------------------------------------------------

class TestPerfettoSink:
    def test_strict_json_on_close_and_prefix_without(self, tmp_path):
        p = tmp_path / "t.json"
        t = trace_mod.RunTracer(perfetto=PerfettoSink(p))
        t.instant("scheduler", "stall", args={"idle_s": 1})
        with t.span("checker-ladder", "rung", args={"backend": "cpu"}):
            pass
        t.close()
        evs = _strict_load(p)
        assert {e.get("ph") for e in evs} >= {"M", "i", "X"}
        # a torn file (simulated kill: drop the terminator and half a
        # line) still yields every complete line
        torn = tmp_path / "torn.json"
        body = p.read_text().splitlines()
        torn.write_text("\n".join(body[:-2]) + '\n{"ph":"i","na')
        assert len(read_trace_events(torn)) == len(evs) - 1

    def test_concurrent_emission_never_tears(self, tmp_path):
        """Scheduler-style op sink + nemesis windows + checker instants
        from concurrent threads: every line parses, nothing interleaves
        mid-line."""
        p = tmp_path / "t.json"
        tracer = trace_mod.RunTracer(perfetto=PerfettoSink(p),
                                     flight=FlightRecorder(4096))
        tracer.set_op_origin(0)
        sink = tracer.op_sink()
        n_ops, n_aux = 500, 200

        def scheduler():
            for i in range(n_ops):
                op = {"process": i % 5, "f": "write", "time": i * 1000,
                      "type": "invoke"}
                sink((trace_mod.OP_BEGIN, i % 5, op))
                comp = {**op, "type": "ok", "time": i * 1000 + 500}
                sink((trace_mod.OP_COMPLETE, i % 5, comp, i * 1000))

        def nemesis():
            for i in range(n_aux):
                tracer.window_begin("nemesis", "net", wid=f"fault-{i}")
                tracer.window_end("nemesis", "net", wid=f"fault-{i}")

        def checker():
            for i in range(n_aux):
                tracer.instant("checker-ladder", "demote",
                               args={"backend": "b", "reason": "r"})

        threads = [threading.Thread(target=f)
                   for f in (scheduler, nemesis, checker)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        tracer.close()
        evs = _strict_load(p)
        by_ph: dict = {}
        for ev in evs:
            by_ph[ev["ph"]] = by_ph.get(ev["ph"], 0) + 1
        assert by_ph["X"] == n_ops          # one slice per completed op
        assert by_ph["b"] == by_ph["e"] == n_aux
        assert by_ph["i"] == n_aux

    def test_op_slice_carries_dispatch_trace_id(self, tmp_path):
        p = tmp_path / "t.json"
        tracer = trace_mod.RunTracer(perfetto=PerfettoSink(p))
        tracer.set_op_origin(1_000_000)
        op = {"process": 2, "f": "read", "time": 5_000_000,
              "type": "invoke"}
        comp = {**op, "type": "ok", "time": 7_000_000}
        tracer.op_sink()((trace_mod.OP_COMPLETE, 2, comp, 5_000_000))
        tracer.close()
        (x,) = [e for e in _strict_load(p) if e["ph"] == "X"]
        assert x["args"]["trace_id"] == trace_mod.trace_id_for(2, 5_000_000)
        assert x["ts"] == 1_000_000 + 5_000
        assert x["dur"] == 2_000
        assert x["name"] == "read"


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_wraparound_exactness(self, tmp_path):
        fr = FlightRecorder(16)
        for i in range(40):
            fr.record({"ph": "i", "track": "scheduler", "name": "stall",
                       "ts": i, "args": {"i": i}})
        snap = fr.snapshot()
        assert [e["args"]["i"] for e in snap] == list(range(24, 40))
        assert fr.recorded == 16
        out = tmp_path / "fr.jsonl"
        assert fr.dump(out, reason="test")
        lines = [json.loads(x) for x in out.read_text().splitlines()]
        header, rows = lines[0], lines[1:]
        assert header["flight_recorder"] and header["reason"] == "test"
        assert header["capacity"] == 16 and header["retained"] == 16
        assert [r["args"]["i"] for r in rows] == list(range(24, 40))

    def test_dump_expands_tuples_and_subsumes_completed(self, tmp_path):
        fr = FlightRecorder(32)
        fr.op_origin_us = 10_000_000
        done = {"process": 0, "f": "write", "time": 1_000_000,
                "type": "invoke"}
        fr.record((trace_mod.OP_BEGIN, 0, done))
        fr.record((trace_mod.OP_COMPLETE, 0,
                   {**done, "type": "ok", "time": 2_000_000}, 1_000_000))
        hung = {"process": 1, "f": "read", "time": 1_500_000,
                "type": "invoke"}
        fr.record((trace_mod.OP_BEGIN, 1, hung))  # still in flight
        out = tmp_path / "fr.jsonl"
        assert fr.dump(out, reason="stall")
        rows = [json.loads(x) for x in out.read_text().splitlines()][1:]
        phs = [(r["ph"], r.get("name")) for r in rows]
        # the completed op is ONE X slice; the hung op stays an open B
        assert phs == [("X", "write"), ("B", "read")]
        assert rows[0]["args"]["trace_id"] == \
            trace_mod.trace_id_for(0, 1_000_000)
        assert rows[1]["args"]["trace_id"] == \
            trace_mod.trace_id_for(1, 1_500_000)
        assert rows[1]["ts"] == 10_000_000 + 1_500

    def test_appender_is_ring_append(self):
        fr = FlightRecorder(4)
        app = fr.appender()
        for i in range(6):
            app(("B", 0, {"time": i}))
        assert fr.recorded == 4
        assert [ev[2]["time"] for ev in fr.snapshot()] == [2, 3, 4, 5]


# ---------------------------------------------------------------------------
# E2E: traced fake runs
# ---------------------------------------------------------------------------

def _register_test(tmp, n_ops=60, client=None, checker=None, **overrides):
    db = AtomDB()
    return noop_test(
        name="traced", db=db,
        client=client if client is not None else AtomClient(db),
        concurrency=5, store_dir=str(tmp), trace=True,
        generator=gen.clients(gen.limit(n_ops, gen.mix([
            gen.repeat({"f": "read"}),
            lambda test, ctx: {"f": "write",
                               "value": ctx.rng.randrange(5)},
        ]))),
        checker=checker if checker is not None
        else linearizable(accelerator="cpu"),
        **overrides)


class TestTracedRun:
    def test_clean_run_trace_and_no_flight_dump(self, tmp_path):
        result = core.run(_register_test(tmp_path))
        assert result["results"]["valid?"] is True
        d = store.test_dir(result)
        evs = _strict_load(d / "trace.json")
        tracks = _tracks_used(evs)
        assert {"worker-0", "scheduler", "checker-ladder"} <= tracks
        assert len(_op_ids(evs)) == 60
        # clean run: the flight recorder never dumps
        assert not (d / "flight-recorder.jsonl").exists()
        # the legacy client span log carries the run-trace id attribute
        spans = [json.loads(x) for x in
                 (d / "trace.jsonl").read_text().splitlines()]
        traced = [s for s in spans if "trace-id" in s["attributes"]]
        assert traced, "client spans must carry the causal trace id"
        live_ids = {tid for _f, tid in _op_ids(evs)}
        assert {s["attributes"]["trace-id"] for s in traced} <= live_ids

    def test_offline_derive_matches_live_ids(self, tmp_path):
        result = core.run(_register_test(tmp_path))
        d = store.test_dir(result)
        live = _op_ids(_strict_load(d / "trace.json"))
        from jepsen_tpu.trace.derive import derive_run_trace
        out = derive_run_trace(d)
        # a live trace.json exists, so the derived one must not clobber
        assert out.name == "trace-derived.json"
        assert _op_ids(_strict_load(out)) == live

    def test_derive_concurrency_fallback_survives_renumbering(self):
        """No test.json: concurrency falls back to peak-in-flight,
        which crash renumbering cannot inflate (review pin)."""
        from jepsen_tpu.trace.derive import _concurrency
        ops = []
        for p in (0, 1, 2):
            ops.append({"type": "invoke", "process": p, "f": "r",
                        "time": p * 10})
        for p in (0, 1, 2):
            ops.append({"type": "info" if p == 0 else "ok",
                        "process": p, "f": "r", "time": 100 + p})
        # worker 0 renumbers 0 -> 3 -> 6 across two crashes
        for p in (3, 6):
            ops.append({"type": "invoke", "process": p, "f": "r",
                        "time": 200 + p})
            ops.append({"type": "info", "process": p, "f": "r",
                        "time": 300 + p})
        assert _concurrency({}, ops) == 3

    def test_derive_late_rows_join_on_invoke_time(self, tmp_path):
        """late.jsonl rows re-stamp "time" at quarantine; the derived
        instant must mint its id from the preserved invoke_time so it
        joins the dispatch slice (review pin)."""
        (tmp_path / "test.json").write_text(json.dumps(
            {"concurrency": 2, "start_time": "20260804T000000.000"}))
        (tmp_path / "history.jsonl").write_text(
            json.dumps({"type": "invoke", "process": 0, "f": "read",
                        "time": 1_000_000}) + "\n"
            + json.dumps({"type": "ok", "process": 0, "f": "read",
                          "time": 2_000_000}) + "\n")
        (tmp_path / "late.jsonl").write_text(json.dumps(
            {"type": "ok", "process": 7, "f": "read", "late": True,
             "worker": 1, "invoke_time": 123_000,
             "time": 999_000}) + "\n")
        from jepsen_tpu.trace.derive import derive_run_trace
        evs = _strict_load(derive_run_trace(tmp_path))
        (late,) = [e for e in evs if e.get("ph") == "i"
                   and e.get("name") == "late-completion"]
        assert late["args"]["trace_id"] == \
            trace_mod.trace_id_for(7, 123_000)

    def test_trace_cli_on_untraced_run(self, tmp_path):
        t = _register_test(tmp_path)
        t["trace"] = False
        result = core.run(t)
        d = store.test_dir(result)
        assert not (d / "trace.json").exists()
        from jepsen_tpu.cli import noop_main
        rc = noop_main(["trace", str(d)])
        assert rc == 0
        evs = _strict_load(d / "trace.json")  # retroactively traceable
        assert len(_op_ids(evs)) == 60

    def test_explain_instant_links_to_dispatch_slice(self, tmp_path):
        class StaleReadClient(AtomClient):
            """Reads return a value nobody ever wrote: the planted
            linearizability anomaly."""

            def invoke(self, test, op):
                if op.get("f") == "read":
                    return {**op, "type": "ok", "value": 4}
                return super().invoke(test, op)

        db = AtomDB()
        t = _register_test(tmp_path, client=StaleReadClient(db))
        result = core.run(t)
        assert result["results"]["valid?"] is False
        d = store.test_dir(result)
        evs = _strict_load(d / "trace.json")
        explains = [e for e in evs if e.get("ph") == "i"
                    and e.get("name") == "explain"]
        assert explains, "invalid run must emit the explain instant"
        link = explains[0]["args"]["trace_id"]
        dispatch = {tid: f for f, tid in _op_ids(evs)}
        assert link in dispatch, \
            "explain must link to a dispatched op's trace id"
        assert dispatch[link] == explains[0]["args"]["f"]
        assert "checker" in _tracks_used(evs)

    def test_six_tracks_with_nemesis_and_live_daemon(self, tmp_path):
        """The acceptance e2e: one --trace run with a nemesis and a
        concurrently-polling live daemon leaves >= 6 distinct tracks
        spanning workers, scheduler, nemesis, checker ladder, and
        live (the checkpoint track is pinned separately at unit level
        — a quick-lane history never spans a frontier chunk)."""

        class PacedClient(AtomClient):
            def invoke(self, test, op):
                time.sleep(0.004)
                return super().invoke(test, op)

        db = AtomDB()
        g = gen.Seq([
            gen.nemesis_gen(gen.Seq([{"type": "info",
                                      "f": "start-partition",
                                      "value": None}])),
            gen.clients(gen.limit(150, gen.mix([
                gen.repeat({"f": "read"}),
                lambda test, ctx: {"f": "write",
                                   "value": ctx.rng.randrange(5)},
            ]))),
            gen.nemesis_gen(gen.Seq([{"type": "info",
                                      "f": "stop-partition",
                                      "value": None}])),
        ])
        t = noop_test(name="traced", db=db, client=PacedClient(db),
                      concurrency=5, store_dir=str(tmp_path), trace=True,
                      nemesis=nemesis_mod.partitioner(),
                      generator=g,
                      checker=linearizable(accelerator="cpu"),
                      time_limit=120.0)
        from jepsen_tpu.live.daemon import LiveDaemon
        daemon = LiveDaemon(store_root=str(tmp_path), poll_s=0.05)
        daemon.start()
        try:
            result = core.run(t)
        finally:
            daemon.stop()
        assert result["results"]["valid?"] is True
        d = store.test_dir(result)
        evs = _strict_load(d / "trace.json")
        tracks = _tracks_used(evs)
        assert {"scheduler", "nemesis", "checker-ladder",
                "live"} <= tracks, tracks
        assert {n for n in tracks if n.startswith("worker-")}, tracks
        assert len(tracks) >= 6, tracks
        # the durable fault registry's window slices ride the nemesis
        # track: one begin at record, one end at the stop's heal-mark
        assert any(e.get("ph") == "b" for e in evs)
        assert any(e.get("ph") == "e" for e in evs)


class TestCheckpointTrack:
    def test_frontier_ckpt_write_and_resume_instants(self, tmp_path,
                                                     monkeypatch):
        from jepsen_tpu.checker import checkpoint as ckpt_mod
        from jepsen_tpu.checker.linear_cpu import cas_register_step_py
        from jepsen_tpu.checker.linear_encode import encode_register_ops
        history = []
        for i in range(200):
            history.append({"type": "invoke", "process": 0, "f": "write",
                            "value": i % 5, "time": i * 1000})
            history.append({"type": "ok", "process": 0, "f": "write",
                            "value": i % 5, "time": i * 1000 + 500})
        stream = encode_register_ops(history)
        monkeypatch.setattr(ckpt_mod, "FRONTIER_CHUNK_EVENTS", 64)
        p = tmp_path / "t.json"
        tracer = trace_mod.RunTracer(perfetto=PerfettoSink(p))
        with trace_mod.use(tracer):
            cs = ckpt_mod.CheckpointStore(tmp_path / "check.ckpt",
                                          interval_s=0.0)
            res = ckpt_mod.checkpointed_check_stream(
                stream, cas_register_step_py, 0, cs)
            assert res.valid is True and cs.writes >= 1
            # the surviving (uncleared) ckpt resumes -> resume instant
            cs2 = ckpt_mod.CheckpointStore(tmp_path / "check.ckpt",
                                           interval_s=None)
            res2 = ckpt_mod.checkpointed_check_stream(
                stream, cas_register_step_py, 0, cs2)
            assert res2.valid is True
        tracer.close()
        evs = _strict_load(p)
        names = [e.get("name") for e in evs if e.get("ph") == "i"]
        assert "ckpt-write" in names and "ckpt-resume" in names
        assert _tracks_used(evs) == {"checkpoint"}


# ---------------------------------------------------------------------------
# SIGKILL chaos: loadable prefix + stall flight dump
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_sigkill_leaves_loadable_trace_and_flight_dump(tmp_path):
    """A hung --trace run trips the stall watchdog (flight dump) and is
    then SIGKILLed: trace.json's complete-line prefix must stay
    Perfetto-loadable and flight-recorder.jsonl must hold the last ~N
    events of causal context."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "trace_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, worker, str(tmp_path)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)

    def complete_dump(path):
        """The dump's rows once it is whole: its last line parses and
        the stall watchdog's instant is in it; else None."""
        try:
            rows = [json.loads(x) for x in path.read_text().splitlines()]
        except (OSError, ValueError):
            return None
        if any(r.get("ph") == "i" and r.get("name") == "stall"
               for r in rows):
            return rows
        return None

    flight = None
    deadline = time.monotonic() + 120
    try:
        while time.monotonic() < deadline:
            dumps = list(tmp_path.glob("noop/*/flight-recorder.jsonl"))
            if dumps and complete_dump(dumps[0]) is not None:
                flight = dumps[0]
                break
            if proc.poll() is not None:
                out = proc.stdout.read()
                pytest.fail(f"worker exited early ({proc.returncode}):\n"
                            f"{out[-4000:]}")
            time.sleep(0.05)
        assert flight is not None, "stall watchdog never dumped"
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)

    run_dir = flight.parent
    # the streamed trace: no terminator (the run was killed), but every
    # complete line parses and the op slices are there
    raw = (run_dir / "trace.json").read_text()
    assert not raw.rstrip().endswith("]")
    evs = read_trace_events(run_dir / "trace.json")
    assert evs, "trace prefix must hold events"
    assert any(ev.get("ph") == "X" for ev in evs)
    assert any(ev.get("ph") == "M" for ev in evs)
    # the flight dump: header + expanded events, hung op still open
    rows = complete_dump(flight)
    assert rows is not None
    assert rows[0]["flight_recorder"] is True
    assert rows[0]["reason"] == "stall"
    assert any(r.get("ph") == "X" for r in rows[1:])
    assert any(r.get("ph") == "B" for r in rows[1:]), \
        "the hung op must appear as an open dispatch slice"
    # the stall watchdog's own instant rides the scheduler track
    assert any(r.get("ph") == "i" and r.get("name") == "stall"
               for r in rows[1:])


# ---------------------------------------------------------------------------
# Fatal-path dump
# ---------------------------------------------------------------------------

def test_fatal_run_dumps_flight_recorder(tmp_path):
    class ExplodingDB(AtomDB):
        def setup(self, test, node):
            raise RuntimeError("db refused to start (as designed)")

    db = ExplodingDB()
    t = noop_test(name="fatal", db=db, client=AtomClient(db),
                  concurrency=2, store_dir=str(tmp_path),
                  generator=gen.clients(gen.limit(
                      5, gen.repeat({"f": "read"}))))
    with pytest.raises(Exception):
        core.run(t)
    dumps = list(tmp_path.glob("fatal/*/flight-recorder.jsonl"))
    assert dumps, "a fatal run must leave a flight dump"
    rows = [json.loads(x) for x in dumps[0].read_text().splitlines()]
    assert rows[0]["reason"] == "fatal"


def test_preflight_failure_is_dump_exempt(tmp_path):
    from jepsen_tpu.analysis.preflight import PreflightFailed
    t = noop_test(name="rejected", store_dir=str(tmp_path),
                  op_timeout_s="banana",
                  generator=gen.clients(gen.limit(
                      5, gen.repeat({"f": "read"}))))
    with pytest.raises(PreflightFailed):
        core.run(t)
    assert not list(tmp_path.glob("rejected/*/flight-recorder.jsonl"))


# ---------------------------------------------------------------------------
# Satellites: the legacy client-span tracer
# ---------------------------------------------------------------------------

class TestLegacyTracerSatellites:
    def test_seeded_rng_is_per_tracer_and_deterministic(self, tmp_path):
        a = tracing.Tracer(str(tmp_path / "a.jsonl"), seed=42)
        b = tracing.Tracer(str(tmp_path / "b.jsonl"), seed=42)
        ids_a = [a._new_id() for _ in range(5)]
        ids_b = [b._new_id() for _ in range(5)]
        assert ids_a == ids_b
        # consuming the GLOBAL random module must not perturb a tracer
        import random
        c = tracing.Tracer(str(tmp_path / "c.jsonl"), seed=42)
        random.random()
        assert [c._new_id() for _ in range(5)] == ids_a
        for t in (a, b, c):
            t.close()

    def test_two_open_keeps_tracing_without_double_wrap(self, tmp_path):
        class SelfWrappingClient(AtomClient):
            """A suite shape that hands back an ALREADY-traced client
            from open() — the re-open path that used to drop/stack
            tracers."""

            def open(self, test, node):
                fresh = super().open(test, node)
                return tracing.TracedClient(
                    fresh, tracing.Tracer(None), node)

        db = AtomDB()
        tracer = tracing.Tracer(str(tmp_path / "t.jsonl"), seed=7)
        c0 = tracing.TracedClient(SelfWrappingClient(db), tracer)
        c1 = c0.open({}, "n1")
        c2 = c1.open({}, "n1")
        for c in (c1, c2):
            assert isinstance(c, tracing.TracedClient)
            # exactly ONE wrapper layer, and it is OUR tracer
            assert not isinstance(c.inner, tracing.TracedClient)
            assert c.tracer is tracer
        c2.invoke({}, {"f": "read", "process": 0, "time": 1})
        tracer.close()
        spans = [json.loads(x) for x in
                 (tmp_path / "t.jsonl").read_text().splitlines()]
        assert [s["name"] for s in spans] == ["invoke/read"]


# ---------------------------------------------------------------------------
# Web summary
# ---------------------------------------------------------------------------

def test_web_trace_section_renders_summary(tmp_path):
    p = tmp_path / "trace.json"
    tracer = trace_mod.RunTracer(perfetto=PerfettoSink(p))
    tracer.set_op_origin(0)
    sink = tracer.op_sink()
    op = {"process": 0, "f": "write", "time": 1_000_000, "type": "invoke"}
    sink((trace_mod.OP_COMPLETE, 0, {**op, "type": "ok",
                                     "time": 3_000_000}, 1_000_000))
    tracer.instant("checker-ladder", "demote",
                   args={"backend": "pallas-matrix",
                         "reason": "watchdog-timeout"})
    tracer.close()
    from jepsen_tpu.web import _trace_section
    html = _trace_section("traced/20260101T000000.000", tmp_path)
    assert "causal trace" in html and "trace.json" in html
    assert "worker-0" in html
    assert "pallas-matrix (watchdog-timeout)" in html
