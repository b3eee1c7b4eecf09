"""Crash-safe resumable checking + the elastic mesh (ISSUE 13).

Pins the acceptance contract of doc/robustness.md "Resumable checks and
the elastic mesh":

* durable checker checkpoints (`check.ckpt`) — interval-gated persists
  of the segmented matrix/frontier carries and the exact CPU frontier's
  session, auto-resumed by the next check BIT-IDENTICALLY while
  re-running only the segments after the last persist;
* validity rules — a hash-mismatched or knob-drifted checkpoint is
  discarded (with the file cleared), never trusted;
* carry threading — a watchdog-demoted matrix rung's completed
  segments seed the demoted rung (down to the exact CPU frontier)
  instead of being discarded;
* the elastic mesh — an injected per-device failure shrinks the
  sharded rung's mesh 8→4 (`mesh_shrink_total`) and the check completes
  sharded, never collapsing to single-device;
* the restartable live daemon — kill/restart resumes tailing at the
  snapshot's WAL offset with divergence-checked adoption.

SIGKILL tests carry the ``chaos`` marker, mesh tests ``mesh`` (the
conftest-forced 8-virtual-CPU-device mesh), daemon tests ``live``.
"""
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from jepsen_tpu import telemetry

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from resume_worker import N_PROCS, N_VALUES, block_history  # noqa: E402


@pytest.fixture
def metrics_registry():
    reg = telemetry.Registry()
    prev = telemetry.install(reg)
    try:
        yield reg
    finally:
        telemetry.install(prev)


@pytest.fixture
def healthy_devices():
    """Device-health isolation: elastic-mesh tests mark devices failed;
    nothing may leak into later tests' meshes."""
    from jepsen_tpu import parallel
    parallel.reset_device_health()
    try:
        yield
    finally:
        parallel.reset_device_health()


def _stream(n_blocks, seed=11, plant_anomaly_at=None):
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    return encode_register_ops(
        block_history(n_blocks, seed=seed,
                      plant_anomaly_at=plant_anomaly_at))


def _resume_count(reg, source):
    return reg.counter("checker_resume_total",
                       labels=("source",)).value(source=source)


# ---------------------------------------------------------------------------
# FrontierSession snapshots
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plant", [None, 90])
def test_frontier_snapshot_roundtrip_bit_identical(plant):
    """snapshot() at an arbitrary (mid-operation) cut, restore, absorb
    the rest → the same verdict/failed_event as one uninterrupted
    absorb."""
    from jepsen_tpu.checker.linear_cpu import FrontierSession, check_stream
    s = _stream(120, plant_anomaly_at=plant)
    full = check_stream(s)
    fs = FrontierSession()
    cut = len(s.kind) // 2 + 1  # odd cut: open ops cross it
    fs.absorb(s, end=cut)
    snap = fs.snapshot()
    assert snap is not None
    restored = FrontierSession.restore(snap)
    assert restored is not None
    res = restored.absorb(s, start=restored.events_absorbed)
    assert res.valid == full.valid
    assert res.failed_event == full.failed_event
    assert res.failed_op_index == full.failed_op_index


def test_frontier_snapshot_latches_failure():
    from jepsen_tpu.checker.linear_cpu import FrontierSession
    s = _stream(60, plant_anomaly_at=20)
    fs = FrontierSession()
    res = fs.absorb(s)
    assert res.valid is False
    restored = FrontierSession.restore(fs.snapshot())
    assert restored.result().valid is False
    assert restored.result().failed_event == res.failed_event


# ---------------------------------------------------------------------------
# Segmented matrix chain: differential + durable resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plant", [None, 500])
def test_matrix_segmented_matches_oneshot(plant):
    from jepsen_tpu.ops.jitlin import matrix_check, matrix_check_segmented
    s = _stream(600, plant_anomaly_at=plant)
    one = matrix_check(s, force=True)
    seg = matrix_check_segmented(s, max_segment=512)
    assert seg[0] == one[0]
    assert bool(seg[2]) == bool(one[2])


def _count_segments(monkeypatch):
    """Counts matrix_check_resume dispatches (one per segment)."""
    from jepsen_tpu.ops import jitlin
    calls = []
    real = jitlin.matrix_check_resume

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(jitlin, "matrix_check_resume", counting)
    return calls


@pytest.mark.parametrize("plant", [None, 560])
def test_matrix_segmented_ckpt_resume_bit_identical(tmp_path, monkeypatch,
                                                    metrics_registry,
                                                    plant):
    """A chain checkpointed every segment, then re-run against the
    surviving check.ckpt: only the segments after the last persist
    re-run, and the verdict is bit-identical (valid and planted-anomaly
    variants)."""
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    from jepsen_tpu.ops.jitlin import matrix_check_segmented, quiescent_cuts
    s = _stream(600, plant_anomaly_at=plant)
    n_cuts = len(quiescent_cuts(np.asarray(s.kind), 512))
    path = tmp_path / "check.ckpt"
    full = matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=0.0, resume=False))
    assert path.exists()

    calls = _count_segments(monkeypatch)
    resumed = matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=None, resume=True))
    assert resumed == full
    # the last persist covers everything up to the final (or failing)
    # segment: the resumed run re-ran strictly fewer segments
    assert 1 <= len(calls) < n_cuts
    assert _resume_count(metrics_registry, "ckpt") == 1


def test_matrix_ckpt_hash_mismatch_discarded(tmp_path, monkeypatch,
                                             metrics_registry):
    """A checkpoint written for a DIFFERENT history (same shapes) is
    discarded, not trusted: every segment re-runs, the verdict is the
    other history's own, and the stale file is cleared."""
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    from jepsen_tpu.ops.jitlin import matrix_check_segmented, quiescent_cuts
    a = _stream(600, seed=11)
    b = _stream(600, seed=12)
    path = tmp_path / "check.ckpt"
    matrix_check_segmented(
        a, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=0.0, resume=False))
    before = path.read_bytes()

    calls = _count_segments(monkeypatch)
    out = matrix_check_segmented(
        b, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=None, resume=True))
    assert out[0] is True and not out[2]
    assert len(calls) == len(quiescent_cuts(np.asarray(b.kind), 512))
    assert _resume_count(metrics_registry, "ckpt") == 0
    # discarded AND cleared — a stale carry must not survive to mislead
    # the next analyze
    assert not path.exists() or path.read_bytes() != before


def test_matrix_ckpt_knob_drift_discarded(tmp_path, monkeypatch,
                                          metrics_registry):
    """The same history under a different segment-size knob: the
    fingerprint differs, so the checkpoint is discarded with a full
    re-run (a carry is only meaningful under the writer's exact
    config)."""
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    from jepsen_tpu.ops.jitlin import matrix_check_segmented, quiescent_cuts
    s = _stream(600)
    path = tmp_path / "check.ckpt"
    matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=0.0, resume=False))

    calls = _count_segments(monkeypatch)
    out = matrix_check_segmented(
        s, max_segment=1024,
        ckpt=CheckpointStore(path, interval_s=None, resume=True))
    assert out[0] is True
    assert len(calls) == len(quiescent_cuts(np.asarray(s.kind), 1024))
    assert _resume_count(metrics_registry, "ckpt") == 0


def test_matrix_ckpt_model_drift_discarded(tmp_path, monkeypatch,
                                           metrics_registry):
    """The config fingerprint stamps the model step's identity: the
    prefix hash covers only the encoded columns (model-independent),
    so a carry written under a different model must discard on the
    config instead of composing over the wrong operators."""
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    from jepsen_tpu.ops.jitlin import matrix_check_segmented, quiescent_cuts
    s = _stream(600)
    path = tmp_path / "check.ckpt"
    matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=0.0, resume=False))
    doc = json.loads(path.read_text())
    assert doc["config"]["step"]  # the identity is recorded
    doc["config"]["step"] = "some.other.model.step_ids"
    path.write_text(json.dumps(doc))

    calls = _count_segments(monkeypatch)
    out = matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=None, resume=True))
    assert out[0] is True
    assert len(calls) == len(quiescent_cuts(np.asarray(s.kind), 512))
    assert _resume_count(metrics_registry, "ckpt") == 0


def test_resume_check_false_ignores_ckpt(tmp_path, monkeypatch,
                                         metrics_registry):
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    from jepsen_tpu.ops.jitlin import matrix_check_segmented, quiescent_cuts
    s = _stream(600)
    path = tmp_path / "check.ckpt"
    matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=0.0, resume=False))
    calls = _count_segments(monkeypatch)
    matrix_check_segmented(
        s, max_segment=512,
        ckpt=CheckpointStore(path, interval_s=None, resume=False))
    assert len(calls) == len(quiescent_cuts(np.asarray(s.kind), 512))
    assert _resume_count(metrics_registry, "ckpt") == 0


# ---------------------------------------------------------------------------
# Segmented event-scan chain (frontier carry)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plant", [None, 110])
def test_segmented_check_ckpt_resume_bit_identical(tmp_path, monkeypatch,
                                                   metrics_registry,
                                                   plant):
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    from jepsen_tpu.ops import jitlin
    s = _stream(128, plant_anomaly_at=plant)
    path = tmp_path / "check.ckpt"
    full = jitlin.segmented_check(
        s, max_segment=128,
        ckpt=CheckpointStore(path, interval_s=0.0, resume=False))

    sliced = []
    real = jitlin._slice_stream

    def counting(stream, lo, hi):
        sliced.append((lo, hi))
        return real(stream, lo, hi)

    monkeypatch.setattr(jitlin, "_slice_stream", counting)
    resumed = jitlin.segmented_check(
        s, max_segment=128,
        ckpt=CheckpointStore(path, interval_s=None, resume=True))
    assert resumed == full
    assert sliced and sliced[0][0] > 0, \
        "resume must skip the checkpointed prefix"
    assert _resume_count(metrics_registry, "ckpt") == 1


# ---------------------------------------------------------------------------
# Matrix-carry -> CPU-frontier handoff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plant", [None, 560])
def test_matrix_carry_seeds_frontier_bit_identical(plant):
    """A segmented matrix carry at a quiescent cut seeds the exact CPU
    frontier: absorbing the remainder lands on the same verdict and the
    same failed_event as a full CPU pass — the cross-representation
    handoff the demotion path relies on."""
    from jepsen_tpu.checker.checkpoint import frontier_from_matrix_carry
    from jepsen_tpu.checker.linear_cpu import (
        cas_register_step_py, check_stream,
    )
    from jepsen_tpu.ops.jitlin import _slice_stream, matrix_check_segmented
    s = _stream(600, plant_anomaly_at=plant)
    cut = len(s.kind) // 2
    cut -= cut % 4  # block-aligned → quiescent
    carries = []
    a, _, ix, _ = matrix_check_segmented(
        _slice_stream(s, 0, cut), max_segment=512,
        carry_sink=carries.append)
    assert a and not ix and carries
    carry = carries[-1]
    assert carry["events_done"] == cut
    fs = frontier_from_matrix_carry(carry, step=cas_register_step_py,
                                    init_state=0)
    assert fs is not None
    res = fs.absorb(s, start=cut)
    full = check_stream(s)
    assert res.valid == full.valid
    assert res.failed_event == full.failed_event


def test_dead_or_nonquiescent_carry_declined():
    from jepsen_tpu.checker.checkpoint import frontier_from_matrix_carry
    from jepsen_tpu.checker.linear_cpu import cas_register_step_py
    V = 8
    # dead carry: no live column entries
    dead = {"tot0": np.zeros((1, 2 * V, 2 * V), np.float32),
            "events_done": 4, "S": 1, "V": V, "init_state": 0}
    assert frontier_from_matrix_carry(dead, cas_register_step_py, 0) is None
    # non-quiescent: a live row with a non-zero mask
    t = np.zeros((1, 2 * V, 2 * V), np.float32)
    t[0, V + 3, 0] = 1.0  # mask bit 0 set
    bad = {"tot0": t, "events_done": 4, "S": 1, "V": V, "init_state": 0}
    assert frontier_from_matrix_carry(bad, cas_register_step_py, 0) is None


# ---------------------------------------------------------------------------
# Carry threading across ladder demotions (the watchdog satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plant", [None, 900])
def test_watchdog_demotion_resumes_from_carry(monkeypatch,
                                              metrics_registry, plant):
    """A matrix rung that completes half its segments and then hangs:
    the watchdog abandons it, and the demoted CPU rung RESUMES from the
    threaded carry instead of restarting — counted in
    checker_resume_total{source="carry"}, verdict bit-identical."""
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin

    history = block_history(1100, plant_anomaly_at=plant)
    stream = _stream(1100, plant_anomaly_at=plant)
    full = check_stream(stream)

    monkeypatch.setattr(jitlin, "MATRIX_SEGMENT_EVENTS", 1024)
    real = jitlin.matrix_check_segmented
    cut = (len(stream.kind) // 2) - ((len(stream.kind) // 2) % 4)
    # warm the slice's kernel shapes OUTSIDE the watchdog: the hang must
    # land after the prefix's carries are threaded, not mid-compile
    real(jitlin._slice_stream(stream, 0, cut), max_segment=1024)

    def half_then_hang(s, **kw):
        real(jitlin._slice_stream(s, 0, cut), **kw)
        time.sleep(30)  # the watchdog abandons this thread
        return None

    monkeypatch.setattr(jitlin, "matrix_check_segmented", half_then_hang)

    def no_frontier_kernel(self, *a, **kw):
        raise RuntimeError("injected frontier-kernel failure")

    monkeypatch.setattr(jitlin.JitLinKernel, "check", no_frontier_kernel)

    chk = LinearizableChecker(accelerator="auto", watchdog_s=3.0)
    out = chk.check({}, history, {"checker_sharded": False})
    assert out["valid?"] == full.valid
    assert out["algorithm"] == "jitlin-cpu(fallback)"
    if plant is not None:
        assert (out["failed-op"] ==
                history[full.failed_op_index])
    assert _resume_count(metrics_registry, "carry") >= 1
    wd = metrics_registry.counter("checker_watchdog_timeouts_total",
                                  labels=("backend",)
                                  ).value(backend="pallas-matrix")
    assert wd == 1


# ---------------------------------------------------------------------------
# The elastic mesh
# ---------------------------------------------------------------------------

@pytest.mark.mesh
def test_shrink_mesh_unit(metrics_registry, healthy_devices):
    import jax

    from jepsen_tpu import parallel
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest-forced 8-device mesh")
    mesh = parallel.auto_mesh(8)
    assert int(mesh.devices.size) == 8

    # attributed failure: the named device is excluded and the width
    # drops to the covering power of two
    err = RuntimeError("UNAVAILABLE: device 7 lost mid collective")
    new = parallel.shrink_mesh(mesh, exc=err)
    assert int(new.devices.size) == 4
    assert 7 in parallel.failed_device_ids()
    assert all(d.id != 7 for d in new.devices.flat)
    # auto_mesh now excludes the casualty everywhere
    assert all(d.id != 7 for d in parallel.auto_mesh(8).devices.flat)
    shrunk = metrics_registry.counter(
        "mesh_shrink_total", labels=("from", "to")).value(
        **{"from": "8", "to": "4"})
    assert shrunk == 1

    # unattributable failure: halve conservatively
    new2 = parallel.shrink_mesh(new, exc=RuntimeError("collective op "
                                                      "failed"))
    assert int(new2.devices.size) == 2
    # the floor bottoms out → None (the ladder then demotes)
    assert parallel.shrink_mesh(new2, exc=err) is None


@pytest.mark.mesh
def test_regrow_mesh_unit(metrics_registry, healthy_devices):
    """The heal path: a probe-passing failed device rejoins and the
    mesh regrows to the next power-of-two width, counted in
    mesh_regrow_total{from,to} (doc/robustness.md "The elastic
    mesh")."""
    import jax

    from jepsen_tpu import parallel
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest-forced 8-device mesh")
    mesh = parallel.auto_mesh(8)
    err = RuntimeError("UNAVAILABLE: device 7 lost mid collective")
    assert int(parallel.shrink_mesh(mesh, exc=err).devices.size) == 4

    # healthy pool, nothing failed after heal -> regrow 4 -> 8
    new = parallel.regrow_mesh()
    assert new is not None and int(new.devices.size) == 8
    assert parallel.failed_device_ids() == set()
    assert all(
        any(d.id == 7 for d in new.devices.flat) for _ in (0,))
    regrown = metrics_registry.counter(
        "mesh_regrow_total", labels=("from", "to")).value(
        **{"from": "4", "to": "8"})
    assert regrown == 1

    # nothing failed: regrow is a no-op
    assert parallel.regrow_mesh() is None

    # a device that FAILS its probe stays excluded: no regrow
    parallel.mark_device_failed(7)
    assert parallel.regrow_mesh(probe=lambda d: False) is None
    assert 7 in parallel.failed_device_ids()


@pytest.mark.mesh
def test_mesh_min_devices_floor(healthy_devices):
    from jepsen_tpu import parallel
    assert parallel.mesh_min_devices(None) == 2
    assert parallel.mesh_min_devices(4) == 4
    assert parallel.mesh_min_devices("garbage") == 2  # tolerant
    mesh = parallel.auto_mesh(8)
    if mesh is None or int(mesh.devices.size) < 8:
        pytest.skip("needs the conftest-forced 8-device mesh")
    err = RuntimeError("UNAVAILABLE: device lost")
    assert parallel.shrink_mesh(mesh, exc=err, min_devices=8) is None


@pytest.mark.mesh
def test_device_failure_shrinks_mesh_bit_identical(monkeypatch,
                                                   metrics_registry,
                                                   healthy_devices):
    """The acceptance scenario: a per-device failure on the sharded
    rung shrinks the mesh 8→4 and the check COMPLETES SHARDED with a
    verdict bit-identical to single-device — no demotion to
    single-device."""
    import jax

    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest-forced 8-device mesh")

    history = block_history(1100, seed=3)
    real = jitlin.matrix_check

    def flaky_on_8(stream, *a, **kw):
        mesh = kw.get("mesh")
        if mesh is not None and int(mesh.devices.size) == 8:
            raise RuntimeError("UNAVAILABLE: device 7 lost in collective")
        return real(stream, *a, **kw)

    monkeypatch.setattr(jitlin, "matrix_check", flaky_on_8)
    chk = LinearizableChecker(accelerator="tpu")
    out = chk.check({}, history, {"checker_sharded": True})
    assert out["algorithm"] == "jitlin-cpu-matrix-sharded", \
        "the shrunken mesh must settle the check — not single-device"
    shrunk = metrics_registry.counter(
        "mesh_shrink_total", labels=("from", "to")).value(
        **{"from": "8", "to": "4"})
    assert shrunk == 1
    demoted = sum(
        r["value"] for r in metrics_registry.snapshot()
        if r.get("name") == "checker_backend_demotions_total"
        and r.get("labels", {}).get("backend") == "sharded-matrix")
    assert demoted == 0

    # bit-identity against the single-device path
    single = LinearizableChecker(accelerator="tpu").check(
        {}, history, {"checker_sharded": False})
    assert out["valid?"] == single["valid?"]


@pytest.mark.mesh
def test_oom_on_sharded_rung_never_poisons_device_health(monkeypatch,
                                                         metrics_registry,
                                                         healthy_devices):
    """A RESOURCE_EXHAUSTED whose message happens to name a device is
    an OOM, not a casualty: the cure is the element-budget halving
    (then an UNATTRIBUTED mesh shrink once the budget bottoms out) —
    the named device must stay healthy and available to future
    meshes."""
    import jax

    from jepsen_tpu import parallel
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin
    if len(jax.devices()) < 8:
        pytest.skip("needs the conftest-forced 8-device mesh")

    # monkeypatch restores the adaptive budget the halvings mutate
    monkeypatch.setattr(jitlin, "MATRIX_MAX_ELEMS",
                        jitlin.MATRIX_MAX_ELEMS)
    history = block_history(1100, seed=4)
    real = jitlin.matrix_check

    def oom_on_8(stream, *a, **kw):
        mesh = kw.get("mesh")
        if mesh is not None and int(mesh.devices.size) == 8:
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating buffer "
                "on device 3")
        return real(stream, *a, **kw)

    monkeypatch.setattr(jitlin, "matrix_check", oom_on_8)
    out = LinearizableChecker(accelerator="tpu").check(
        {}, history, {"checker_sharded": True})
    assert out["valid?"] is True
    assert 3 not in parallel.failed_device_ids(), \
        "an OOM must never mark a healthy device failed"


# ---------------------------------------------------------------------------
# Checker-level SIGKILL chaos (the acceptance scenario)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_sigkill_mid_check_resumes_bit_identical(tmp_path, monkeypatch,
                                                 metrics_registry):
    """SIGKILL a run-dir-backed segmented check between two durable
    persists; the next check auto-resumes from check.ckpt, re-runs only
    the remaining segments, settles a verdict bit-identical to an
    uninterrupted check, and clears the checkpoint."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "resume_worker.py")
    name, ts = "resume", "20260804T000000.000Z"
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JEPSEN_TPU_MATRIX_SEGMENT_EVENTS"] = "2048"
    proc = subprocess.Popen(
        [sys.executable, worker, str(tmp_path), name, ts, "0.3"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    ckpt = tmp_path / name / ts / "check.ckpt"
    deadline = time.monotonic() + 180
    try:
        while time.monotonic() < deadline:
            if ckpt.exists():
                break
            if proc.poll() is not None:
                pytest.fail(f"worker exited before a checkpoint landed "
                            f"({proc.returncode}):\n"
                            f"{proc.stdout.read()[-4000:]}")
            time.sleep(0.05)
        assert ckpt.exists(), "no durable checkpoint ever appeared"
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)

    # the interrupted check's checkpoint is a forensic artifact
    from jepsen_tpu import store
    assert "check.ckpt" in store.forensic_artifacts(tmp_path / name / ts)

    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin
    monkeypatch.setattr(jitlin, "MATRIX_SEGMENT_EVENTS", 2048)
    calls = _count_segments(monkeypatch)
    test = {"name": name, "start_time": ts, "store_dir": str(tmp_path),
            "checker_sharded": False}
    history = block_history(4096)
    n_cuts = len(jitlin.quiescent_cuts(
        np.asarray(_stream(4096).kind), 2048))
    out = LinearizableChecker(accelerator="tpu").check(test, history, {})
    assert out["valid?"] is True
    assert out["algorithm"] == "jitlin-cpu-matrix"
    assert _resume_count(metrics_registry, "ckpt") == 1
    assert 1 <= len(calls) < n_cuts, \
        f"resume re-ran {len(calls)}/{n_cuts} segments"
    assert not ckpt.exists(), "a completed check must clear check.ckpt"

    # bit-identical to an uninterrupted check (no checkpoint left, so
    # this second run is from scratch)
    calls.clear()
    scratch = LinearizableChecker(accelerator="tpu").check(
        test, history, {})
    assert len(calls) == n_cuts
    assert scratch["valid?"] == out["valid?"]
    assert scratch["algorithm"] == out["algorithm"]


# ---------------------------------------------------------------------------
# Restartable live daemon
# ---------------------------------------------------------------------------

def _live_history(n_pairs, seed=5):
    rng = np.random.default_rng(seed)
    ops = []
    for i in range(n_pairs):
        v = int(rng.integers(5))
        ops.append({"process": 0, "type": "invoke", "f": "write",
                    "value": v})
        ops.append({"process": 0, "type": "ok", "f": "write", "value": v})
        ops.append({"process": 1, "type": "invoke", "f": "read",
                    "value": None})
        ops.append({"process": 1, "type": "ok", "f": "read", "value": v})
    return ops


@pytest.mark.live
def test_daemon_restart_resumes_at_offset(tmp_path, monkeypatch,
                                          metrics_registry):
    from jepsen_tpu.live import daemon as live_daemon
    monkeypatch.setattr(live_daemon, "SNAPSHOT_MIN_INTERVAL_S", 0.0)
    ops = _live_history(100)
    half = len(ops) // 2
    run_dir = tmp_path / "r" / "20260804T000000.000Z"
    run_dir.mkdir(parents=True)
    wal = run_dir / "history.wal.jsonl"
    with open(wal, "w") as f:
        for op in ops[:half]:
            f.write(json.dumps(op) + "\n")

    d1 = live_daemon.LiveDaemon(store_root=str(tmp_path), poll_s=0.01,
                                accelerator="cpu",
                                registry=metrics_registry)
    d1.poll_once()
    tr1 = next(iter(d1.trackers.values()))
    off = tr1.tailer.offset
    assert off > 0 and tr1.ops_absorbed == half
    assert (run_dir / live_daemon.LIVE_CKPT_NAME).exists()
    d1.stop()

    # the run continues and completes while no daemon is watching
    with open(wal, "a") as f:
        for op in ops[half:]:
            f.write(json.dumps(op) + "\n")
    with open(run_dir / "history.jsonl", "w") as f:
        for op in ops:
            f.write(json.dumps(op) + "\n")

    d2 = live_daemon.LiveDaemon(store_root=str(tmp_path), poll_s=0.01,
                                accelerator="cpu",
                                registry=metrics_registry)
    d2.discover()
    tr2 = next(iter(d2.trackers.values()))
    assert tr2.resumed is True
    assert tr2.tailer.offset == off, \
        "restart must resume tailing at the snapshot's offset"
    assert tr2.ops_absorbed == half
    d2.run_until_idle(timeout_s=60)
    d2.stop()
    status = live_daemon.load_live_status(run_dir)
    assert status["state"] == "final"
    assert status["results"]["valid?"] is True
    assert status["ops_absorbed"] == len(ops)
    assert metrics_registry.counter(
        "live_session_resumes_total").value() == 1
    assert not (run_dir / live_daemon.LIVE_CKPT_NAME).exists(), \
        "a finalized run must clear its restart snapshot"


@pytest.mark.live
def test_daemon_restart_rejects_diverged_wal(tmp_path, monkeypatch,
                                             metrics_registry):
    """A rewritten WAL (different run reusing the dir) fails the
    prefix-hash check: the snapshot is rejected and the tracker
    re-ingests from zero — slower, never diverged."""
    from jepsen_tpu.live import daemon as live_daemon
    monkeypatch.setattr(live_daemon, "SNAPSHOT_MIN_INTERVAL_S", 0.0)
    ops = _live_history(60, seed=6)
    run_dir = tmp_path / "r" / "20260804T000000.000Z"
    run_dir.mkdir(parents=True)
    wal = run_dir / "history.wal.jsonl"
    with open(wal, "w") as f:
        for op in ops[:120]:
            f.write(json.dumps(op) + "\n")
    d1 = live_daemon.LiveDaemon(store_root=str(tmp_path), poll_s=0.01,
                                accelerator="cpu",
                                registry=metrics_registry)
    d1.poll_once()
    d1.stop()
    assert (run_dir / live_daemon.LIVE_CKPT_NAME).exists()

    # a different run reuses the dir: same length prefix, different ops
    other = _live_history(60, seed=7)
    with open(wal, "w") as f:
        for op in other:
            f.write(json.dumps(op) + "\n")
    with open(run_dir / "history.jsonl", "w") as f:
        for op in other:
            f.write(json.dumps(op) + "\n")

    d2 = live_daemon.LiveDaemon(store_root=str(tmp_path), poll_s=0.01,
                                accelerator="cpu",
                                registry=metrics_registry)
    d2.discover()
    tr = next(iter(d2.trackers.values()))
    assert tr.resumed is False
    assert tr.tailer.offset == 0
    d2.run_until_idle(timeout_s=60)
    d2.stop()
    status = live_daemon.load_live_status(run_dir)
    assert status["state"] == "final"
    assert status["ops_absorbed"] == len(other)
    assert metrics_registry.counter(
        "live_session_resume_rejected_total").value() == 1


@pytest.mark.live
def test_encoder_snapshot_roundtrip_differential():
    """LiveRegisterEncoder snapshot at a cut with OPEN ops: restore +
    absorb the rest → the identical encoded stream as one
    uninterrupted encoder."""
    from jepsen_tpu.history import Intern
    from jepsen_tpu.history_ir.builder import LiveRegisterEncoder
    ops = _live_history(40)
    # interleave an op pair so an invoke is open across the cut
    cut = len(ops) // 2 + 1
    full = LiveRegisterEncoder(Intern())
    for op in ops:
        full.add(op)
    full.finalize()

    enc = LiveRegisterEncoder(Intern())
    for op in ops[:cut]:
        enc.add(op)
    enc.encode_resolved()
    snap = enc.snapshot()
    assert snap is not None
    enc2 = LiveRegisterEncoder.restore(snap)
    assert enc2 is not None
    for op in ops[cut:]:
        enc2.add(op)
    enc2.finalize()
    for col in ("kind", "slot", "f", "a", "b", "op_index"):
        assert getattr(enc2.stream, col) == getattr(full.stream, col), col
    assert list(enc2.intern.table) == list(full.intern.table)


# ---------------------------------------------------------------------------
# Preflight knob coverage
# ---------------------------------------------------------------------------

def _pf(t):
    from jepsen_tpu import core
    from jepsen_tpu.analysis import preflight as pf
    return pf.preflight(core.prepare_test(t))


def _codes(diags):
    return {d.code for d in diags}


class TestResumeKnobs:
    def test_ckpt_interval_garbage(self):
        from jepsen_tpu import fakes
        diags = _pf(fakes.noop_test(check_ckpt_interval="banana"))
        assert any(d.code == "KNB001"
                   and d.path == "check_ckpt_interval" for d in diags)

    def test_ckpt_interval_numeric_clean(self):
        from jepsen_tpu import fakes
        diags = _pf(fakes.noop_test(check_ckpt_interval=2.5))
        assert "KNB001" not in _codes(diags)
        # negative disables — not a range error
        assert "KNB002" not in _codes(_pf(
            fakes.noop_test(check_ckpt_interval=-1)))

    def test_mesh_min_devices_rows(self):
        from jepsen_tpu import fakes
        assert any(d.code == "KNB001" and d.path == "mesh_min_devices"
                   for d in _pf(fakes.noop_test(mesh_min_devices="lots")))
        diags = _pf(fakes.noop_test(mesh_min_devices="4"))
        assert "KNB001" not in _codes(diags)
        assert "KNB006" in _codes(diags)  # stringly number: warn

    def test_resume_check_bool(self):
        from jepsen_tpu import fakes
        assert any(d.code == "KNB001" and d.path == "resume_check"
                   for d in _pf(fakes.noop_test(resume_check="maybe")))
        assert "KNB001" not in _codes(_pf(
            fakes.noop_test(resume_check=False)))

    def test_env_twins(self, monkeypatch):
        from jepsen_tpu import fakes
        monkeypatch.setenv("JEPSEN_TPU_CHECK_CKPT_INTERVAL", "banana")
        assert any(d.code == "KNB001"
                   and d.path == "JEPSEN_TPU_CHECK_CKPT_INTERVAL"
                   for d in _pf(fakes.noop_test()))
        monkeypatch.setenv("JEPSEN_TPU_CHECK_CKPT_INTERVAL", "7.5")
        monkeypatch.setenv("JEPSEN_TPU_RESUME_CHECK", "sometimes")
        diags = _pf(fakes.noop_test())
        assert any(d.code == "KNB007"
                   and d.path == "JEPSEN_TPU_RESUME_CHECK"
                   for d in diags)
        monkeypatch.setenv("JEPSEN_TPU_RESUME_CHECK", "0")
        monkeypatch.setenv("JEPSEN_TPU_MESH_MIN_DEVICES", "4")
        diags = _pf(fakes.noop_test())
        assert not any(d.path.startswith("JEPSEN_TPU_") for d in diags)


def test_ckpt_knob_coercion():
    from jepsen_tpu.checker import checkpoint as ckpt_mod
    assert ckpt_mod.ckpt_interval({}) == ckpt_mod.DEFAULT_CKPT_INTERVAL_S
    assert ckpt_mod.ckpt_interval({"check_ckpt_interval": 2}) == 2.0
    assert ckpt_mod.ckpt_interval({"check_ckpt_interval": 0}) is None
    assert ckpt_mod.ckpt_interval({"check_ckpt_interval": -3}) is None
    assert ckpt_mod.ckpt_interval({"check_ckpt_interval": "nope"}) \
        == ckpt_mod.DEFAULT_CKPT_INTERVAL_S
    assert ckpt_mod.resume_enabled({}) is True
    assert ckpt_mod.resume_enabled({"resume_check": False}) is False
    assert ckpt_mod.resume_enabled({"resume_check": "garbage"}) is True


def test_encode_array_roundtrip():
    from jepsen_tpu.checker.checkpoint import decode_array, encode_array
    rng = np.random.default_rng(0)
    bits = (rng.random((3, 17)) > 0.5).astype(np.float32)
    out = decode_array(encode_array(bits))
    assert out.shape == bits.shape and (out == bits).all()
    raw = rng.integers(0, 1 << 30, (5, 7)).astype(np.uint32)
    raw[0, 0] = 0xFFFFFFFF
    out = decode_array(encode_array(raw))
    assert out.dtype == np.uint32 and (out == raw).all()
