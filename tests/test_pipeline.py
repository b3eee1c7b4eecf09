"""Dispatch-pipeline unit tests: deterministic overlap/coalescing on
CPU (fake two-batch overlap, the auto-routing threshold, donation
safety) plus the differential check that pipelined multikey results
match the serial path bit-for-bit."""
import numpy as np
import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.parallel import pipeline
from jepsen_tpu.parallel.pipeline import CostModel, DispatchPipeline


class FakeHandle:
    """A dispatch handle recording when it was blocked on."""

    def __init__(self, name, log):
        self.name = name
        self.log = log

    def block_until_ready(self):
        self.log.append(("block", self.name))


def test_two_batch_overlap_order():
    """With depth 2, batch 2's host prep runs BEFORE anything blocks on
    batch 0 — the overlap the pipeline exists for — and the delayed
    blocking hits the OLDEST handle exactly when depth is exceeded."""
    log = []
    pipe = DispatchPipeline(depth=2, name="t")

    def prep(i):
        def f():
            log.append(("prep", i))
            return (i,)
        return f

    def dispatch(i):
        log.append(("dispatch", i))
        return FakeHandle(i, log)

    for i in range(3):
        pipe.submit(prep(i), dispatch)
    out = pipe.results()
    assert [h.name for h in out] == [0, 1, 2]  # submission order
    # batch 0 and 1 dispatched with no blocking; block on 0 happens only
    # when batch 2 exceeds the depth, and AFTER batch 2's prep
    assert log.index(("prep", 2)) < log.index(("block", 0))
    assert ("block", 1) not in log  # depth never exceeded again
    stats = pipe.stats()
    assert stats["batches"] == 3
    assert stats["inflight_peak"] == 2
    # prep of batches 1 and 2 ran while >= 1 dispatch was in flight
    assert stats["overlap_frac"] > 0


def test_pipeline_depth_one_serializes():
    log = []
    pipe = DispatchPipeline(depth=1, name="t1")
    for i in range(2):
        pipe.submit(lambda i=i: (i,),
                    lambda i: FakeHandle(i, log))
    pipe.results()
    assert ("block", 0) in log
    assert pipe.stats()["inflight_peak"] == 1


def test_pipeline_metrics_registry():
    """Occupancy instruments land in a live registry."""
    reg = telemetry.Registry()
    with telemetry.use(reg):
        pipe = DispatchPipeline(depth=2, name="m")
        for i in range(3):
            pipe.submit(lambda i=i: (i,), lambda i: FakeHandle(i, []))
        pipe.results()
    names = {r["name"] for r in reg.snapshot()}
    assert "dispatch_batches_total" in names
    assert "dispatch_inflight_peak" in names
    assert "dispatch_overlap_frac" in names
    assert reg.counter("dispatch_batches_total",
                       labels=("queue",)).value(queue="m") == 3
    prom = reg.render_prom()
    assert 'dispatch_overlap_frac{queue="m"}' in prom


def test_cost_model_threshold():
    """Routing boundary: CPU wins exactly when its predicted time beats
    the 2x round-trip device floor."""
    m = CostModel(roundtrip_s=0.1, cpu_events_per_sec_=100_000.0)
    # floor = 0.2 s -> 20_000 events is the break-even point
    assert m.route(1_000) == "cpu"
    assert m.route(19_999) == "cpu"
    assert m.route(20_001) == "device"
    assert m.route(10_000_000) == "device"
    # zero RTT (no backend measured): never routes off the device
    z = CostModel(roundtrip_s=0.0, cpu_events_per_sec_=100_000.0)
    assert z.route(1) == "device"


def test_cost_model_ewma_feedback():
    pipeline._CPU_RATE.clear()
    try:
        assert pipeline.cpu_events_per_sec() == \
            pipeline.DEFAULT_CPU_EVENTS_PER_SEC
        pipeline.observe_cpu_rate(100_000, 1.0)
        assert pipeline.cpu_events_per_sec() == pytest.approx(100_000.0)
        pipeline.observe_cpu_rate(200_000, 1.0)
        r = pipeline.cpu_events_per_sec()
        assert 100_000 < r < 200_000  # EWMA, not last-sample
        pipeline.observe_cpu_rate(0, 0.0)  # degenerate sample ignored
        assert pipeline.cpu_events_per_sec() == r
    finally:
        pipeline._CPU_RATE.clear()


def test_rtt_env_override(monkeypatch):
    monkeypatch.setenv("JEPSEN_TPU_RTT_S", "0.25")
    assert pipeline.measured_roundtrip_s() == 0.25


def test_donation_gate_on_cpu():
    """The donation gate must be off on the CPU backend (it would warn
    per call and can't be honored) — and the donating/non-donating
    wrappers must collapse to one object there so nothing double
    compiles."""
    assert pipeline.donate_ok() is False


def _streams(n_keys, n_ops=120, n_values=5):
    # n_procs=3 keeps the matrix kernels small (MV = 2^3 * 8 = 64): the
    # differential guarantees don't depend on kernel size, and the
    # quick lane shouldn't pay S=5 compile times
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    return [encode_register_ops(_register_history(
        n_ops, n_procs=3, seed=1000 + k, n_values=n_values))
        for k in range(n_keys)]


def test_pipelined_multikey_bit_identical(monkeypatch):
    """The differential guarantee: the pipelined sub-batch path returns
    exactly what one serial dispatch returns, key for key — and both
    agree with the exact CPU lane's verdicts."""
    from jepsen_tpu.ops import jitlin

    streams = _streams(24)
    serial = jitlin.matrix_check_batch(streams)
    # force the pipelined path: tiny sub-batches -> 4 dispatches
    monkeypatch.setattr(jitlin, "MATRIX_PIPELINE_KEYS", 6)
    monkeypatch.setattr(jitlin, "MATRIX_SUB_KEYS", 6)
    pipelined = jitlin.matrix_check_batch(streams)
    assert pipelined == serial
    assert pipeline.last_stats().get("queue") == "matrix"
    assert pipeline.last_stats()["batches"] == 4
    # CPU-lane agreement on the verdicts
    from jepsen_tpu.parallel import batch_check
    cpu = batch_check(streams, mesh=False, accelerator="cpu")
    assert [r[0] for r in cpu] == [r[0] for r in serial]


def test_pipelined_multikey_invalid_key(monkeypatch):
    """A corrupted key stays False through the pipelined path, in the
    right position."""
    from jepsen_tpu.ops import jitlin

    # same key count and sub-batch size as the valid differential above,
    # so both tests share the already-compiled kernel shapes
    streams = _streams(24, n_ops=120)
    bad = streams[7]
    a = np.asarray(bad.a).copy()
    # find a read invoke (kind 0, f == READ(0)) and corrupt its value
    ks, fs = np.asarray(bad.kind), np.asarray(bad.f)
    idx = np.nonzero((ks == 0) & (fs == 0) & (np.asarray(bad.a) != 0))[0]
    a[idx[len(idx) // 2]] = (a[idx[len(idx) // 2]] % 5) + 1
    object.__setattr__(bad, "a", a)
    monkeypatch.setattr(jitlin, "MATRIX_PIPELINE_KEYS", 6)
    monkeypatch.setattr(jitlin, "MATRIX_SUB_KEYS", 6)
    piped = jitlin.matrix_check_batch(streams)
    serial_alive = [r[0] for r in jitlin.matrix_check_batch(streams)]
    assert [r[0] for r in piped] == serial_alive
    # the CPU oracle agrees on every key (including the corrupted one,
    # whatever its verdict is)
    from jepsen_tpu.checker.linear_cpu import check_stream
    oracle = [check_stream(s).valid is True for s in streams]
    assert [r[0] for r in piped] == oracle


def test_batch_check_auto_routes_small_to_cpu(monkeypatch):
    """accelerator=auto + a dominating RTT routes a small batch to the
    CPU lane (last_route() records it); verdicts match the device lane."""
    import jepsen_tpu.parallel as par
    from jepsen_tpu.parallel import batch_check

    streams = _streams(4, n_ops=60)
    monkeypatch.setenv("JEPSEN_TPU_RTT_S", "1000.0")
    out_auto = batch_check(streams, mesh=False, accelerator="auto")
    assert par.last_route() == "cpu"
    out_dev = batch_check(streams, mesh=False)
    assert par.last_route() == "device"
    assert [r[0] for r in out_auto] == [r[0] for r in out_dev]


def test_batch_check_auto_keeps_big_on_device(monkeypatch):
    import jepsen_tpu.parallel as par
    from jepsen_tpu.parallel import batch_check

    streams = _streams(4, n_ops=60)
    monkeypatch.setenv("JEPSEN_TPU_RTT_S", "0.0")
    batch_check(streams, mesh=False, accelerator="auto")
    assert par.last_route() == "device"


N_PROCS = 5


def _block_stream(n_blocks: int, n_procs: int = N_PROCS, n_values: int = 100,
                  start_block: int = 0):
    """Vectorized valid single-register event stream: block t = P invokes
    (proc 0 writes w_t = t mod V; procs 1..P-1 read w_{t-1}) then P
    returns. Reads linearize before the concurrent write, so the history
    is linearizable by construction. O(E) numpy, no Python per-op loop —
    this is what makes multi-million-event scaling runs generatable.

    ``start_block`` continues a longer logical history: block numbering
    (and so the read/write value sequence) picks up at that offset, so
    consecutive segments chain correctly through the carried frontier."""
    from jepsen_tpu.checker.linear_encode import EventStream
    from jepsen_tpu.history import Intern
    from jepsen_tpu.models import CAS_F_READ, CAS_F_WRITE

    P, V = n_procs, n_values
    intern = Intern()
    for v in range(V):
        intern.id(v)  # ids 1..V

    t = np.arange(start_block, start_block + n_blocks, dtype=np.int64)
    w_id = (t % V).astype(np.int32) + 1              # this block's write
    r_id = np.where(t > 0, ((t - 1) % V).astype(np.int32) + 1, 0)  # read

    kind = np.tile(np.concatenate([np.zeros(P, np.int8), np.ones(P, np.int8)]),
                   n_blocks)
    slot = np.tile(np.concatenate([np.arange(P), np.arange(P)]).astype(np.int32),
                   n_blocks)
    f = np.zeros((n_blocks, 2 * P), np.int32)
    f[:, 0] = CAS_F_WRITE
    f[:, 1:P] = CAS_F_READ
    a = np.zeros((n_blocks, 2 * P), np.int32)
    a[:, 0] = w_id
    a[:, 1:P] = r_id[:, None]
    E = n_blocks * 2 * P
    return EventStream(
        kind=kind, slot=slot, f=f.reshape(-1), a=a.reshape(-1),
        b=np.zeros(E, np.int32), op_index=np.arange(E, dtype=np.int32),
        n_slots=P, n_ops=n_blocks * P, intern=intern)


def test_resume_chain_after_donation_gate():
    """Segmented resume chaining stays correct under the donation
    machinery (on CPU the gate collapses both wrappers; the chain's
    verdicts must hold either way)."""
    from jepsen_tpu.ops.jitlin import matrix_check_resume

    s0 = _block_stream(300, n_procs=3, n_values=4)
    s1 = _block_stream(300, n_procs=3, n_values=4, start_block=300)
    a0, ix0, tot = matrix_check_resume(s0, None, n_slots=3, num_states=5)
    a1, ix1, tot2 = matrix_check_resume(s1, tot, n_slots=3, num_states=5)
    assert bool(np.asarray(a1).all()) and not bool(np.asarray(ix1).any())


def test_phase_attribution_recorded():
    from jepsen_tpu.ops import jitlin

    streams = _streams(2, n_ops=80)
    jitlin.matrix_check_batch(streams)
    ph = jitlin.last_phase_seconds()
    for k in ("prepass", "grids", "dispatch", "fetch"):
        assert k in ph and ph[k] >= 0


def test_checker_exports_phase_gauges(monkeypatch):
    """The linearizable checker's telemetry carries the per-phase
    attribution gauges for matrix-path checks."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin

    monkeypatch.setattr(jitlin, "MATRIX_MIN_RETURNS", 10)
    reg = telemetry.Registry()
    with telemetry.use(reg):
        chk = LinearizableChecker(accelerator="tpu")
        out = chk.check({}, _register_history(600, n_procs=3, seed=3,
                                              n_values=5), {})
    assert out["algorithm"] == "jitlin-cpu-matrix"
    phases = {r["labels"]["phase"] for r in reg.snapshot()
              if r["name"] == "checker_matrix_phase_seconds"}
    assert {"prepass", "grids", "dispatch", "fetch"} <= phases
