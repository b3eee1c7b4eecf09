"""Anomaly forensics: localization differentials, witness shrink,
artifacts, and surfaces (doc/observability.md "Anomaly forensics").

The acceptance bar: on a planted-anomaly history, every matrix-family
backend — single-device, segmented, sharded-mesh, live screen — reports
the SAME exact ``first_anomaly_op`` as the exact CPU frontier, writes
``anomaly.json`` + a witness timeline, and the web run page links both.
"""
import json

import numpy as np
import pytest

pytestmark = pytest.mark.explain

N_PROCS, N_VALUES = 3, 5


def _history(n_blocks, plant_anomaly_at=None, seed=3, with_times=False):
    """Write/read blocks over a rand-int-5 register domain; planting an
    anomaly makes one read observe a value that was NOT the concurrent
    or previous write (non-linearizable at that read's return)."""
    rng = np.random.default_rng(seed)
    ops = []
    t = 0
    for b in range(n_blocks):
        p = int(rng.integers(N_PROCS))
        v = int(rng.integers(N_VALUES))
        p2 = int(rng.integers(N_PROCS))
        rv = (v + 1) % N_VALUES if b == plant_anomaly_at else v
        block = [
            {"process": p, "type": "invoke", "f": "write", "value": v},
            {"process": p, "type": "ok", "f": "write", "value": v},
            {"process": p2, "type": "invoke", "f": "read", "value": None},
            {"process": p2, "type": "ok", "f": "read", "value": rv},
        ]
        for op in block:
            if with_times:
                op["time"] = t * 1_000_000
                t += 1
            ops.append(op)
    return ops


def _stream(history):
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    return encode_register_ops(history)


def _cpu(history):
    from jepsen_tpu.checker.linear_cpu import check_stream
    return check_stream(_stream(history))


# ---------------------------------------------------------------------------
# localization differentials (the acceptance bar's bit-identity half)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plant", [0, 1, 700, 1500, 2047])
def test_matrix_localize_matches_frontier(plant):
    """Single-device: the device bisection's failed event/op must be
    bit-identical to the exact CPU frontier's first rejection."""
    from jepsen_tpu.ops.jitlin import matrix_localize

    h = _history(2048, plant_anomaly_at=plant)
    cpu = _cpu(h)
    assert cpu.valid is False
    loc = matrix_localize(_stream(h))
    assert loc is not None
    assert loc.failed_event == cpu.failed_event
    assert loc.failed_op_index == cpu.failed_op_index
    assert loc.bisect_steps >= 1


def test_matrix_localize_valid_returns_none():
    from jepsen_tpu.ops.jitlin import matrix_localize

    h = _history(2048)
    assert _cpu(h).valid is True
    assert matrix_localize(_stream(h)) is None


def test_matrix_localize_segmented_chain():
    """Segmented backend: a failing segment localizes against the
    carried operator product (tot0) and reports the same absolute op as
    the CPU frontier over the whole chain — no chain re-scan."""
    from jepsen_tpu.ops import jitlin
    from jepsen_tpu.ops.jitlin import _slice_stream

    h = _history(4096, plant_anomaly_at=3000)
    s = _stream(h)
    cpu = _cpu(h)
    cuts = jitlin.quiescent_cuts(np.asarray(s.kind), 1 << 13)
    assert len(cuts) >= 2, "chain must span several segments"
    tot, base, found = None, 0, None
    for end in cuts:
        seg = _slice_stream(s, base, end)
        alive, inexact, tot2 = jitlin.matrix_check_resume(
            seg, tot, n_slots=s.n_slots, num_states=len(s.intern))
        assert not bool(np.asarray(inexact).any())
        if not bool(np.asarray(alive).all()):
            loc = jitlin.matrix_localize(seg, tot0=tot,
                                         num_states=len(s.intern),
                                         n_slots=s.n_slots)
            assert loc is not None
            found = (base + loc.failed_event, loc.failed_op_index)
            break
        tot, base = tot2, end
    assert found == (cpu.failed_event, cpu.failed_op_index)


def test_matrix_localize_sharded_mesh_checker():
    """Sharded-mesh backend: a checker forced onto the mesh rung
    settles the planted anomaly at the matrix rung with the exact CPU
    op — no demotion to the scan just to find it."""
    import jax

    from jepsen_tpu.checker.linearizable import LinearizableChecker

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest forces 8 virtual)")
    h = _history(2048, plant_anomaly_at=1500)
    cpu = _cpu(h)
    res = LinearizableChecker(accelerator="tpu").check(
        {}, h, {"checker_sharded": True})
    assert res["valid?"] is False
    assert res["algorithm"] == "jitlin-cpu-matrix-sharded", res["algorithm"]
    assert res["explain"]["first-anomaly-op"] == cpu.failed_op_index


def test_ladder_settles_invalid_at_matrix_rung():
    """The single-device matrix rung attaches localization to an
    invalid verdict instead of demoting: algorithm stays matrix, the
    failed op is the frontier's, and the telemetry backend counter
    names the matrix rung as the settler."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    reg = telemetry.Registry()
    prev = telemetry.install(reg)
    try:
        h = _history(2048, plant_anomaly_at=700)
        cpu = _cpu(h)
        res = LinearizableChecker(accelerator="tpu").check(
            {}, h, {"checker_sharded": False})
        assert res["valid?"] is False
        assert res["algorithm"] == "jitlin-cpu-matrix", res["algorithm"]
        assert res["failed-op"] == h[cpu.failed_op_index]
        assert res["explain"]["first-anomaly-op"] == cpu.failed_op_index
        snap = {(r["name"], tuple(sorted((r.get("labels") or {}).items())))
                for r in reg.snapshot()}
        assert ("checker_backend_total",
                (("backend", "jitlin-cpu-matrix"),)) in snap
        names = {r["name"] for r in reg.snapshot()}
        assert {"explain_bisect_steps", "explain_latency_seconds",
                "witness_ops"} <= names
    finally:
        telemetry.install(prev)


def test_explain_off_restores_demotion_path():
    """``explain: False`` restores the old behavior: the matrix rung
    demotes on invalid and the frontier scan settles with the same
    exact op — the knob changes cost, never the verdict."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    h = _history(2048, plant_anomaly_at=700)
    cpu = _cpu(h)
    res = LinearizableChecker(accelerator="tpu").check(
        {"explain": False}, h, {"checker_sharded": False})
    assert res["valid?"] is False
    assert res["algorithm"] != "jitlin-cpu-matrix"
    assert "explain" not in res
    assert res["failed-op"] == h[cpu.failed_op_index]


def test_live_screen_reports_exact_first_anomaly():
    """Live-screen backend: the daemon's matrix screen reports the
    exact first_anomaly_op itself (no deferral to the CPU frontier
    rung), matching the frontier bit-for-bit."""
    from jepsen_tpu.live.sessions import LinearLiveSession

    h = _history(2048, plant_anomaly_at=1800)
    cpu = _cpu(h)
    sess = LinearLiveSession(accelerator="tpu")
    for op in h:
        sess.add(op)
    v = sess.verdict()
    assert v["valid_so_far"] is False
    assert v["backend"] == "pallas-matrix", v
    assert v["first_anomaly_op"] == cpu.failed_op_index
    # the latch answers later polls without re-screening, and finalize's
    # exact frontier pass agrees with the screen's localization
    v2 = sess.verdict()
    assert v2["first_anomaly_op"] == cpu.failed_op_index
    final = sess.finalize()
    assert final["valid?"] is False
    assert final["failed-op-index"] == cpu.failed_op_index


def test_localize_keys_distributed_single_process():
    """The multi-host forensics surface, exercised single-process (the
    allgather degenerates): invalid keys localize, valid keys don't
    appear, and the events match the CPU frontier."""
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.parallel.distributed import localize_keys_distributed

    streams = [
        _stream(_history(700, plant_anomaly_at=600, seed=10)),
        _stream(_history(700, seed=11)),
        _stream(_history(700, plant_anomaly_at=33, seed=12)),
    ]
    out = localize_keys_distributed(streams, [0, 2])
    assert set(out) == {0, 2}
    for i in (0, 2):
        cpu = check_stream(streams[i])
        assert out[i] == (cpu.failed_event, cpu.failed_op_index)


# ---------------------------------------------------------------------------
# witness shrink
# ---------------------------------------------------------------------------

def test_witness_shrink_is_bounded_and_keeps_fatal():
    from jepsen_tpu.checker.explain import explain_stream
    from jepsen_tpu.checker.linear_cpu import check_stream

    h = _history(8192, plant_anomaly_at=2000)
    s = _stream(h)
    cpu = check_stream(s)
    f = explain_stream(s, max_witness_ops=2, shrink_budget=64)
    assert f is not None
    assert f["backend"] == "matrix-bisect"
    assert f["first_anomaly"]["op_index"] == cpu.failed_op_index
    wit = f["witness"]
    # the fatal op's invoke is always part of the witness
    assert cpu.failed_op_index - 1 in wit["op_indices"]
    assert wit["candidates"] <= 64
    assert len(wit["op_indices"]) <= wit["window_op_count"]
    # the planted anomaly needs only a handful of ops to reproduce...
    assert len(wit["op_indices"]) < wit["window_op_count"]
    # ...but "minimal" is a PROOF: a shrink stopped early by the
    # max_witness_ops floor was never verified irreducible
    assert wit["minimal"] is False


def test_explain_stream_cpu_fallback():
    """Out of the matrix regime (short history) the forensics fall back
    to the exact CPU frontier: same first anomaly, frontier-derived
    witness, no device bisection."""
    from jepsen_tpu.checker.explain import explain_stream

    h = _history(40, plant_anomaly_at=35)
    s = _stream(h)
    cpu = _cpu(h)
    f = explain_stream(s)
    assert f is not None
    assert f["backend"] == "frontier-cpu"
    assert f["first_anomaly"]["op_index"] == cpu.failed_op_index
    assert cpu.failed_op_index in f["witness"]["op_indices"]


def test_explain_stream_valid_returns_none():
    from jepsen_tpu.checker.explain import explain_stream

    assert explain_stream(_stream(_history(40))) is None


# ---------------------------------------------------------------------------
# artifacts + surfaces
# ---------------------------------------------------------------------------

def _run_checker(tmp_path, h, name="explain-run", ts="20260803T000000"):
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    test = {"name": name, "start_time": ts, "store_dir": str(tmp_path)}
    res = LinearizableChecker(accelerator="tpu").check(test, h, {})
    return test, res, tmp_path / name / ts


def test_invalid_check_writes_anomaly_artifacts(tmp_path):
    h = _history(2048, plant_anomaly_at=1337, with_times=True)
    cpu = _cpu(h)
    test, res, run_dir = _run_checker(tmp_path, h)
    assert res["valid?"] is False
    a = json.loads((run_dir / "anomaly.json").read_text())
    assert a["first_anomaly"]["op_index"] == cpu.failed_op_index
    assert a["first_anomaly"]["f"] == "read"
    # the fatal op_index is the RETURN's index — its detail must still
    # resolve the full invoke+completion pair (schema promise)
    assert a["first_anomaly"]["completion_type"] == "ok"
    assert a["first_anomaly"]["latency_ns"] == 1_000_000
    assert a["witness"]["ops"], "per-op detail must be present"
    assert "fault_windows" in a
    html = (run_dir / "witness-timeline.html").read_text()
    assert "fatal" in html and "witness" in html
    assert sorted(res["explain"]["artifacts"]) == [
        "anomaly.json", "witness-timeline.html"]


def test_web_run_page_links_explain(tmp_path):
    import threading
    import urllib.request

    from jepsen_tpu import web

    h = _history(2048, plant_anomaly_at=1337, with_times=True)
    test, res, run_dir = _run_checker(tmp_path, h)
    server = web.make_server(store_dir=str(tmp_path))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        host, port = server.server_address
        base = f"http://{host}:{port}"
        page = urllib.request.urlopen(
            f"{base}/{test['name']}/{test['start_time']}/",
            timeout=10).read().decode()
        assert "anomaly.json" in page
        assert "witness-timeline.html" in page
        assert "first anomaly" in page           # the Explain panel
        home = urllib.request.urlopen(base, timeout=10).read().decode()
        assert "anomaly.json" in home            # artifact links column
        # the rendered timeline serves as html (clickable, not a blob)
        r = urllib.request.urlopen(
            f"{base}/{test['name']}/{test['start_time']}/"
            "witness-timeline.html", timeout=10)
        assert r.headers.get("Content-Type", "").startswith("text/html")
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=5)


def test_cli_explain_rederives_offline(tmp_path, capsys):
    from jepsen_tpu import cli, store

    h = _history(2048, plant_anomaly_at=900, with_times=True)
    cpu = _cpu(h)
    test = {"name": "explain-cli", "start_time": "20260803T000001",
            "store_dir": str(tmp_path), "history": h}
    store.save_1(test)
    run_dir = tmp_path / "explain-cli" / "20260803T000001"
    rc = cli.noop_main(["explain", str(run_dir)])
    out = capsys.readouterr().out
    # validity_exit_code convention: an invalid run exits EXIT_INVALID
    assert rc == cli.EXIT_INVALID, out
    assert f"first anomaly at op {cpu.failed_op_index}" in out
    a = json.loads((run_dir / "anomaly.json").read_text())
    assert a["first_anomaly"]["op_index"] == cpu.failed_op_index
    assert (run_dir / "witness-timeline.html").exists()


def test_cli_explain_valid_history(tmp_path, capsys):
    from jepsen_tpu import cli, store

    test = {"name": "explain-ok", "start_time": "20260803T000002",
            "store_dir": str(tmp_path), "history": _history(40)}
    store.save_1(test)
    rc = cli.noop_main(
        ["explain", str(tmp_path / "explain-ok" / "20260803T000002")])
    assert rc == cli.EXIT_OK
    assert "nothing to explain" in capsys.readouterr().out


def test_cli_explain_wr_run_routes_to_rw_register(tmp_path, capsys):
    """A stored rw-register (wr) run also carries f='txn' — the offline
    route must sniff the mop dialect like the live daemon and feed the
    rw_register checker, not crash in list-append."""
    from jepsen_tpu import cli, store

    h = [
        {"process": 0, "type": "invoke", "f": "txn",
         "value": [["w", "x", 1]], "time": 0},
        {"process": 0, "type": "ok", "f": "txn",
         "value": [["w", "x", 1]], "time": 1},
        {"process": 1, "type": "invoke", "f": "txn",
         "value": [["r", "x", None]], "time": 2},
        {"process": 1, "type": "ok", "f": "txn",
         "value": [["r", "x", 1]], "time": 3},
    ]
    test = {"name": "explain-wr", "start_time": "20260803T000006",
            "store_dir": str(tmp_path), "history": h}
    store.save_1(test)
    rc = cli.noop_main(
        ["explain", str(tmp_path / "explain-wr" / "20260803T000006")])
    out = capsys.readouterr().out
    assert rc == cli.EXIT_OK, out
    assert "nothing to explain" in out


def test_elle_artifacts_witness_timeline(tmp_path):
    """Elle cycle explanations gain the same witness-window timeline."""
    from jepsen_tpu.elle import artifacts

    history = [
        {"index": 0, "type": "invoke", "process": 0, "f": "txn",
         "value": [["append", 1, 10]], "time": 0},
        {"index": 1, "type": "ok", "process": 0, "f": "txn",
         "value": [["append", 1, 10]], "time": 1},
        {"index": 2, "type": "invoke", "process": 1, "f": "txn",
         "value": [["r", 1, None]], "time": 2},
        {"index": 3, "type": "ok", "process": 1, "f": "txn",
         "value": [["r", 1, [10]]], "time": 3},
    ]
    result = {
        "valid?": False,
        "anomalies": {"G1c": [[
            {"from": [["append", 1, 10]], "type": "wr",
             "to": [["r", 1, [10]]]},
            {"from": [["r", 1, [10]]], "type": "rw",
             "to": [["append", 1, 10]]},
        ]]},
    }
    test = {"name": "elle-wit", "start_time": "20260803T000003",
            "store_dir": str(tmp_path)}
    artifacts.write_for_test(test, result, history=history)
    d = tmp_path / "elle-wit" / "20260803T000003" / "elle"
    assert (d / "G1c.txt").exists()
    html = (d / "witness-timeline.html").read_text()
    assert "witness" in html
    assert "witness-timeline.html" in (d / "index.txt").read_text()


# ---------------------------------------------------------------------------
# satellites: timeline truncation, fault shading, knobs
# ---------------------------------------------------------------------------

def test_timeline_windowed_truncation_banner():
    from jepsen_tpu.checker import timeline

    h = _history(200, with_times=True)
    total = len(timeline.pairs(h))
    html = timeline.render({"name": "t"}, h, max_ops=50)
    assert "truncated — showing" in html
    assert f"of {total} ops" in html
    # windowed, not clipped: the LAST block's ops still render
    assert "whole run windowed" in html
    small = timeline.render({"name": "t"}, _history(5, with_times=True))
    assert "truncated" not in small


def test_batched_independent_writes_per_key_forensics(tmp_path):
    """The batched device lane (the default independent path) attaches
    per-key forensics and writes artifacts under independent/<k>,
    matching the per-key lane's lift."""
    from jepsen_tpu import independent as ind
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    h = []
    for k in range(4):
        plant = 80 if k == 2 else None
        for i, op in enumerate(_history(128, plant_anomaly_at=plant,
                                        seed=20 + k, with_times=True)):
            op = dict(op)
            if op.get("value") is not None or op["f"] == "read":
                op["value"] = [f"k{k}", op.get("value")]
            h.append(op)
    test = {"name": "ind-explain", "start_time": "20260803T000005",
            "store_dir": str(tmp_path)}
    chk = ind.checker(LinearizableChecker(accelerator="tpu"))
    r = chk.check(test, h, {})
    assert r["valid?"] is False
    assert set(r["failures"]) == {"k2"}
    bad = r["results"]["k2"]
    # the BATCHED lane settled this key (per-key fallback results carry
    # the full _finish surface instead of the bare batch verdict)
    assert "configs-max" in bad, bad
    assert "explain" in bad, bad
    key_dir = (tmp_path / "ind-explain" / "20260803T000005"
               / "independent" / "k2")
    assert (key_dir / "anomaly.json").exists()
    assert (key_dir / "witness-timeline.html").exists()
    # valid keys got no forensics dirs
    assert not (tmp_path / "ind-explain" / "20260803T000005"
                / "independent" / "k0" / "anomaly.json").exists()


def test_render_witness_omits_out_of_span_open_fault():
    """An open (end_time=None) fault window starting AFTER the witness
    span is omitted like a healed one — it must not stretch the page."""
    from jepsen_tpu.checker import timeline

    h = _history(20, plant_anomaly_at=15, with_times=True)
    span_end = max(op["time"] for op in h)
    payload = {
        "first_anomaly": {"op_index": 61},
        "witness": {"op_indices": [59, 61], "context_op_indices": []},
        "fault_windows": [
            {"kind": "net", "f": "start-partition", "healed": False,
             "start_time": span_end + 10**12, "end_time": None},
            {"kind": "clock", "f": "bump", "healed": True,
             "start_time": 0, "end_time": span_end + 10**12},
        ],
    }
    html = timeline.render_witness({"name": "t"}, h, payload)
    assert "start-partition" not in html      # out of span: omitted
    assert "clock" in html                    # overlapping: drawn


def test_faults_history_windows_pairing(tmp_path):
    from jepsen_tpu.nemesis import faults as faults_mod

    reg_path = tmp_path / "faults.jsonl"
    reg = faults_mod.FaultRegistry(reg_path)
    i1 = reg.record("net", f="start-partition", value=["n1", "n2"])
    reg.record("clock", f="bump", value=500)
    reg.mark_healed(i1, via="nemesis")
    # the clock fault is healed OUTSIDE the history (crash-path replay)
    reg.mark_healed(kind="clock", via="replay")
    reg.close()
    history = [
        {"process": "nemesis", "type": "info", "f": "start-partition",
         "value": ["n1", "n2"], "time": 10 * 10**9},
        {"process": 0, "type": "invoke", "f": "read", "value": None,
         "time": 11 * 10**9},
        {"process": 0, "type": "ok", "f": "read", "value": None,
         "time": 12 * 10**9},
        {"process": "nemesis", "type": "info", "f": "stop-partition",
         "value": None, "time": 20 * 10**9},
        {"process": "nemesis", "type": "info", "f": "bump",
         "value": 500, "time": 30 * 10**9},
    ]
    rows = faults_mod.load_rows(reg_path)
    wins = faults_mod.history_windows(history, rows)
    assert len(wins) == 2
    net = next(w for w in wins if w["kind"] == "net")
    assert net["start_time"] == 10 * 10**9
    assert net["end_time"] == 20 * 10**9
    assert net["healed"] is True
    clock = next(w for w in wins if w["kind"] == "clock")
    assert clock["end_time"] is None          # no closing op in history
    assert clock["healed"] is True            # ...but the registry knows
    assert clock["via"] == "replay"


def test_perf_plots_shade_registry_windows(tmp_path):
    from jepsen_tpu import store
    from jepsen_tpu.checker import perf_plots
    from jepsen_tpu.nemesis import faults as faults_mod

    test = {"name": "shade", "start_time": "20260803T000004",
            "store_dir": str(tmp_path)}
    reg = faults_mod.FaultRegistry(
        store.path_mk(test, faults_mod.FAULTS_NAME))
    reg.record("net", f="start-partition")
    reg.mark_healed(kind="net", via="teardown")
    reg.close()
    history = [
        {"process": "nemesis", "type": "info", "f": "start-partition",
         "value": None, "time": 1 * 10**9},
        {"process": 0, "type": "invoke", "f": "read", "value": None,
         "time": 2 * 10**9},
        {"process": 0, "type": "ok", "f": "read", "value": None,
         "time": 3 * 10**9},
    ]
    wins = perf_plots.registry_fault_windows(test, history)
    assert len(wins) == 1 and wins[0]["kind"] == "net"
    out = store.path_mk(test, "latency-raw.png")
    perf_plots.point_graph(test, history, out)   # shading must not crash
    assert out.exists()


def test_explain_knob_coercion_and_preflight():
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.checker import explain as explain_mod

    # tolerant runtime coercion: garbage warns and reads as default
    assert explain_mod.enabled({"explain": "garbage"}) is True
    assert explain_mod.enabled({"explain": False}) is False
    assert explain_mod.enabled({"explain": "no"}) is False
    assert explain_mod.enabled({}) is True
    assert explain_mod.shrink_budget({"explain_shrink_budget": "64"}) == 64
    assert explain_mod.shrink_budget(
        {"explain_shrink_budget": "junk"}) == explain_mod.DEFAULT_SHRINK_BUDGET
    assert explain_mod.max_witness_ops(
        {"explain_max_witness_ops": 0}) == 1   # clamped to the floor

    # preflight is where garbage becomes an error (KNB house style)
    diags = pf._check_knobs({"explain": "garbage"})
    assert any(d.code == "KNB001" and d.path == "explain" for d in diags)
    diags = pf._check_knobs({"explain_shrink_budget": -1})
    assert any(d.code == "KNB002" for d in diags)
    diags = pf._check_knobs({"explain_max_witness_ops": "junk"})
    assert any(d.code == "KNB001" for d in diags)
    assert not pf._check_knobs({"explain": True,
                                "explain_shrink_budget": 64,
                                "explain_max_witness_ops": 8})
