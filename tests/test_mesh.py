"""Multi-device checker sharding: mesh-vs-single-device differentials.

The tier-1 conftest forces an 8-virtual-CPU-device mesh, so every test
here exercises the REAL shard_map kernels, collectives, and padding —
the same mechanism production uses across real chips
(doc/performance.md "Multi-device sharding"). Everything asserts
bit-identity against the single-device path: sharding is a data-plane
optimization and must never change a verdict.

Run just this lane with ``-m mesh`` (conftest forces the virtual mesh
even in a ``JEPSEN_TPU_TESTS`` session).
"""
import numpy as np
import pytest

from jepsen_tpu import telemetry

pytestmark = pytest.mark.mesh

N_PROCS, N_VALUES = 3, 5


@pytest.fixture
def metrics_registry():
    """A live telemetry registry installed for the test's duration."""
    reg = telemetry.Registry()
    prev = telemetry.install(reg)
    try:
        yield reg
    finally:
        telemetry.install(prev)


def _mesh(n=8):
    import jax

    from jepsen_tpu.parallel import get_mesh
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices (forced by conftest; a "
                    f"non-conftest runner must set "
                    f"--xla_force_host_platform_device_count)")
    return get_mesh(n)


def _history(n_blocks, seed=0, plant_anomaly_at=None):
    """A register history of write/read blocks; planting an anomaly
    makes one read observe a value never written (non-linearizable)."""
    rng = np.random.default_rng(seed)
    ops = []
    for b in range(n_blocks):
        p = int(rng.integers(N_PROCS))
        v = int(rng.integers(N_VALUES))
        ops.append({"process": p, "type": "invoke", "f": "write",
                    "value": v})
        ops.append({"process": p, "type": "ok", "f": "write", "value": v})
        p2 = int(rng.integers(N_PROCS))
        rv = (v + 1) % N_VALUES if b == plant_anomaly_at else v
        ops.append({"process": p2, "type": "invoke", "f": "read",
                    "value": None})
        ops.append({"process": p2, "type": "ok", "f": "read", "value": rv})
    return ops


def _stream(n_blocks, seed=0, plant_anomaly_at=None, intern=None):
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    return encode_register_ops(
        _history(n_blocks, seed=seed, plant_anomaly_at=plant_anomaly_at),
        **({"intern": intern} if intern is not None else {}))


# ---------------------------------------------------------------------------
# Segmented path: chunk-axis sharding
# ---------------------------------------------------------------------------

def test_segmented_mesh_differential_bit_identical():
    """matrix_check_resume chains compose the same verdicts AND the same
    carry operator bits on the mesh as on one device — valid chain and a
    chain with a planted anomaly mid-segment."""
    from jepsen_tpu.history import Intern
    from jepsen_tpu.ops import jitlin

    mesh = _mesh()
    for name, anomaly in (("valid", None), ("anomalous", 250)):
        intern = Intern()
        segs = [
            _stream(500, seed=s,
                    plant_anomaly_at=(anomaly if s == 1 else None),
                    intern=intern)
            for s in range(3)
        ]
        outs = {}
        for label, m in (("single", None), ("mesh", mesh)):
            tot, alive, ix = None, None, None
            for seg in segs:
                alive, ix, tot = jitlin.matrix_check_resume(
                    seg, tot, n_slots=N_PROCS, num_states=len(intern),
                    mesh=m)
            outs[label] = (np.asarray(alive).copy(), np.asarray(ix).copy(),
                           np.asarray(tot).copy())
        a1, i1, t1 = outs["single"]
        a2, i2, t2 = outs["mesh"]
        assert np.array_equal(a1, a2), name
        assert np.array_equal(i1, i2), name
        assert np.array_equal(t1, t2), f"{name}: carry operators diverge"
        assert bool(a1[0]) is (anomaly is None), name


def test_segmented_variant_chain_matches_mesh_twin(monkeypatch):
    """ISSUE 12 mesh-twin bit-identity: a segmented chain through the
    pallas kernel variants + fused streaming combine (single device,
    interpret mode) composes the SAME verdicts and the SAME carry
    operator bits as the sharded mesh twin (XLA scan + device-side tree
    combine) — the variants change the operand representation, never
    one bit of the composed operator."""
    import jepsen_tpu.ops.pallas_matrix as pm
    from jepsen_tpu.history import Intern
    from jepsen_tpu.ops import jitlin

    mesh = _mesh()
    for variant in ("int8", "f32"):
        intern = Intern()
        segs = [_stream(120, seed=10 + s, intern=intern) for s in range(2)]
        outs = {}
        monkeypatch.setattr(pm, "FORCE_INTERPRET", True)
        try:
            tot, alive = None, None
            for seg in segs:
                alive, _, tot = jitlin.matrix_check_resume(
                    seg, tot, n_slots=N_PROCS, num_states=len(intern),
                    variant=variant, combine_fused=True)
            info = jitlin.last_dispatch_info()
            assert info == {"variant": variant, "combine": "fused"}, info
            outs["pallas"] = (np.asarray(alive).copy(),
                              np.asarray(tot).copy())
        finally:
            monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
        tot, alive = None, None
        for seg in segs:
            alive, _, tot = jitlin.matrix_check_resume(
                seg, tot, n_slots=N_PROCS, num_states=len(intern),
                mesh=mesh)
        outs["mesh"] = (np.asarray(alive).copy(), np.asarray(tot).copy())
        a1, t1 = outs["pallas"]
        a2, t2 = outs["mesh"]
        assert np.array_equal(a1, a2), variant
        assert np.array_equal(t1, t2), (
            f"{variant}: carry operators diverge from the mesh twin")
        assert bool(a1[0])


def test_segmented_mixed_chain_sharded_then_single():
    """A chain may mix sharded and single-device segments (the ladder's
    sharded→device demotion mid-chain): the carry is the same replicated
    product either way."""
    from jepsen_tpu.history import Intern
    from jepsen_tpu.ops import jitlin

    mesh = _mesh()
    intern = Intern()
    segs = [_stream(500, seed=s, intern=intern) for s in range(2)]

    tot, alive, ix = None, None, None
    for seg, m in zip(segs, (mesh, None)):
        alive, ix, tot = jitlin.matrix_check_resume(
            seg, tot, n_slots=N_PROCS, num_states=len(intern), mesh=m)
    mixed = np.asarray(tot).copy()

    tot2 = None
    for seg in segs:
        _, _, tot2 = jitlin.matrix_check_resume(
            seg, tot2, n_slots=N_PROCS, num_states=len(intern), mesh=None)
    assert bool(np.asarray(alive)[0])
    assert np.array_equal(mixed, np.asarray(tot2))


# ---------------------------------------------------------------------------
# Key batch: key-axis sharding + non-divisible padding
# ---------------------------------------------------------------------------

def test_batch_mesh_differential_nondivisible_keys(metrics_registry):
    """11 keys over 8 devices: the key axis pads to 16 (never silently
    drops sharding), verdicts — including a planted per-key anomaly —
    are identical to single-device, and the padding cost is published."""
    from jepsen_tpu.ops import jitlin

    mesh = _mesh()
    streams = [
        _stream(150, seed=100 + k,
                plant_anomaly_at=(75 if k == 7 else None))
        for k in range(11)
    ]
    r1 = jitlin.matrix_check_batch(streams)
    r2 = jitlin.matrix_check_batch(streams, mesh=mesh)
    assert r1 == r2
    assert [r[0] for r in r1] == [k != 7 for k in range(11)]
    frac = metrics_registry.gauge("checker_mesh_padding_frac").value()
    assert 0.0 < frac < 1.0  # 11 keys padded to 16: visible, not free


def test_scan_batch_mesh_differential():
    """The vmapped event-scan path (below the matrix regime) with the
    leading key axis sharded: pad_to_multiple + per-device staging give
    the same verdicts as single-device."""
    from jepsen_tpu.parallel import batch_check

    mesh = _mesh()
    streams = [
        _stream(12, seed=200 + k, plant_anomaly_at=(6 if k == 2 else None))
        for k in range(5)
    ]
    r1 = batch_check(streams, mesh=False)
    r2 = batch_check(streams, mesh=mesh)
    assert r1 == r2
    assert [r[0] for r in r1] == [k != 2 for k in range(5)]


# ---------------------------------------------------------------------------
# Ladder: the sharded rung wins, and demotes instead of failing
# ---------------------------------------------------------------------------

def _matrix_regime_history():
    # ≥ MATRIX_MIN_RETURNS returns so the matrix rungs are eligible
    from jepsen_tpu.ops.jitlin import MATRIX_MIN_RETURNS
    return _history(MATRIX_MIN_RETURNS // 2 + 50, seed=7)


def test_ladder_sharded_rung_wins(metrics_registry):
    """checker_sharded=True routes the check through the mesh rung."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    _mesh()
    chk = LinearizableChecker(accelerator="tpu")
    out = chk.check({}, _matrix_regime_history(),
                    {"checker_sharded": True})
    assert out["valid?"] is True
    assert out["algorithm"] == "jitlin-cpu-matrix-sharded"


def test_ladder_sharded_demotes_to_single_device(metrics_registry,
                                                 monkeypatch):
    """An injected collective failure demotes sharded → single-device
    (counted in checker_backend_demotions_total) instead of failing the
    check — the acceptance contract for backends without mesh support."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin

    _mesh()

    def no_collectives(*a, **kw):
        raise RuntimeError("collectives are not implemented on this "
                           "backend")

    # a fresh compile cache so the poisoned builder is actually invoked
    # (a warm mesh kernel from an earlier test would dodge the injection)
    monkeypatch.setattr(jitlin, "_MATRIX_CACHE", {})
    monkeypatch.setattr(jitlin, "_build_matrix_kernel_mesh",
                        no_collectives)
    chk = LinearizableChecker(accelerator="tpu")
    out = chk.check({}, _matrix_regime_history(),
                    {"checker_sharded": True})
    assert out["valid?"] is True
    assert out["algorithm"] == "jitlin-cpu-matrix"  # single-device won
    reg = metrics_registry
    demoted = reg.counter("checker_backend_demotions_total",
                          labels=("backend", "reason")).value(
                              backend="sharded-matrix", reason="error")
    assert demoted == 1


def test_ladder_sharded_disabled_by_knob(metrics_registry):
    """checker_sharded=False never attempts the mesh rung."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    chk = LinearizableChecker(accelerator="tpu")
    out = chk.check({}, _matrix_regime_history(),
                    {"checker_sharded": False})
    assert out["valid?"] is True
    assert out["algorithm"] == "jitlin-cpu-matrix"


# ---------------------------------------------------------------------------
# Knobs, cost model, preflight
# ---------------------------------------------------------------------------

def test_knob_coercion_tolerant():
    from jepsen_tpu import parallel

    assert parallel.coerce_flag(None) is None
    assert parallel.coerce_flag(True) is True
    assert parallel.coerce_flag(0) is False
    assert parallel.coerce_flag(" Yes ") is True
    assert parallel.coerce_flag("off") is False
    assert parallel.coerce_flag("garbage") is None  # warns, reads unset
    assert parallel.coerce_devices(None) is None
    assert parallel.coerce_devices("4") == 4
    assert parallel.coerce_devices(2.0) == 2
    assert parallel.coerce_devices(-3) == 0
    assert parallel.coerce_devices("many") is None
    assert parallel.coerce_devices(True) is None  # bool is not a count


def test_mesh_env_knobs(monkeypatch):
    from jepsen_tpu import parallel

    monkeypatch.setenv("JEPSEN_TPU_MESH_DEVICES", "nonsense")
    assert parallel.mesh_devices_limit() is None  # warns, no raise
    monkeypatch.setenv("JEPSEN_TPU_MESH_DEVICES", "4")
    assert parallel.mesh_devices_limit() == 4
    mesh = parallel.auto_mesh()
    if mesh is not None:
        assert int(mesh.devices.size) <= 4
    monkeypatch.setenv("JEPSEN_TPU_MESH_DEVICES", "1")
    assert parallel.auto_mesh() is None  # <2 devices: no mesh
    monkeypatch.delenv("JEPSEN_TPU_MESH_DEVICES")
    monkeypatch.setenv("JEPSEN_TPU_SHARDED", "0")
    assert parallel.sharded_mesh_for(1 << 30) is None


def test_cost_model_mesh_route(monkeypatch):
    """Small batches never pay mesh overhead on faith; measured rates
    flip the route once the mesh is actually faster."""
    from jepsen_tpu.parallel import pipeline

    monkeypatch.setattr(pipeline, "_DEVICE_RATE", {})
    assert not pipeline.mesh_route(100, 8)  # below MESH_MIN_EVENTS
    assert not pipeline.mesh_route(1 << 30, 1)  # one device is no mesh
    assert pipeline.mesh_route(pipeline.MESH_MIN_EVENTS, 8)
    # measured: mesh 4x faster -> route big batches to it
    pipeline.observe_device_rate(1, 100_000, 1.0)
    pipeline.observe_device_rate(8, 400_000, 1.0)
    assert pipeline.mesh_route(1_000_000, 8)
    # measured: mesh slower (collective overhead) -> stay single-device
    monkeypatch.setattr(pipeline, "_DEVICE_RATE",
                        {1: 100_000.0, 8: 50_000.0})
    assert not pipeline.mesh_route(1_000_000, 8)


@pytest.mark.lint
def test_preflight_mesh_knobs():
    from jepsen_tpu.analysis.preflight import _check_knobs

    assert _check_knobs({"mesh_devices": 4, "checker_sharded": True}) == []
    diags = _check_knobs({"mesh_devices": "many"})
    assert any(d.code == "KNB001" and d.path == "mesh_devices"
               for d in diags)
    diags = _check_knobs({"mesh_devices": -1})
    assert any(d.code == "KNB002" for d in diags)
    diags = _check_knobs({"checker_sharded": "true"})
    assert any(d.code == "KNB006" and d.path == "checker_sharded"
               for d in diags)
    diags = _check_knobs({"checker_sharded": "sideways"})
    assert any(d.code == "KNB001" and d.path == "checker_sharded"
               for d in diags)


# ---------------------------------------------------------------------------
# Multi-process seam (single-process execution of the local-mesh gate)
# ---------------------------------------------------------------------------

def test_distributed_local_mesh_gate():
    """batch_check_distributed's local-mesh gate: small batches stay
    single-device (mesh=False floor), and results match batch_check.
    The true two-process run is tests/test_distributed.py (slow lane);
    this covers the new gate logic on one process."""
    from jepsen_tpu.parallel import batch_check
    from jepsen_tpu.parallel.distributed import batch_check_distributed

    streams = [_stream(12, seed=300 + k) for k in range(3)]
    assert batch_check_distributed(streams) == batch_check(streams,
                                                           mesh=False)


def test_distributed_skip_matcher_signatures():
    """The test_distributed skip-reason matcher still recognizes the
    backend's no-multiprocess-collectives signatures (it must keep
    triggering under the forced-device-count flag, not fail the lane)."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "_td", os.path.join(os.path.dirname(__file__),
                            "test_distributed.py"))
    td = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(td)
    hit = td._missing_collective_support(
        ["jaxlib.xla_extension.XlaRuntimeError: UNIMPLEMENTED: "
         "Multiprocess computations aren't implemented on the CPU "
         "backend."])
    assert hit is not None
    assert td._missing_collective_support(
        ["AssertionError: verdicts diverged"]) is None
