"""The trace reduction, and the reader of the frontier scan's time."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from benchmark import tracefile
from benchmark.metrics import scan_us_per_event


def op(t, p, f, v):
    return {"type": t, "process": p, "f": f, "value": v}


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


KERNEL = ('%run.1 = bf16[8,8]{1,0} custom-call(f32[8]{0} %p), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
WHILE = "%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t)"
BODY = "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %q), kind=kLoop"


def fake_profile():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev(tracefile.WINDOW_SPAN, 1000, 10_000),
        ev("benchmark.check", 1000, 4000),
        ev("PjitFunction(scan)", 1500, 500),
        ev("benchmark.check", 6000, 5000)])])
    dev0 = NS(name="/device:TPU:0", lines=[
        NS(name=tracefile.MODULES_LINE, events=[
            ev("jit_fn(123)", 0, 3000), ev("jit_scan(9)", 6500, 6000)]),
        NS(name=tracefile.OPS_LINE, events=[
            ev(KERNEL, 500, 1500),         # clipped to [1000, 2000)
            ev(FUSION, 1200, 400),         # nested in the kernel's span
            ev(WHILE, 7000, 1000),
            ev(BODY, 7100, 500),           # the loop's body: nested
            ev(KERNEL, 10_500, 2000)])])   # clipped to [10500, 11000)
    dev1 = NS(name="/device:TPU:1", lines=[
        NS(name=tracefile.OPS_LINE, events=[ev(FUSION, 2000, 3000)])])
    return NS(planes=[host, dev0, dev1])


def test_op_labels():
    assert tracefile.op_label("jit_fn", KERNEL) == \
        "jit_fn %run.1 custom-call:tpu_custom_call"
    assert tracefile.op_label("jit_scan", WHILE) == "jit_scan %while.2 while"
    assert tracefile.op_label("?", FUSION) == "? %fusion.1 fusion"


def test_summary_of_a_synthetic_trace():
    s = tracefile.summarize(fake_profile())
    assert s.window_s == pytest.approx(10e-6)
    # dev0 busy: [1000,2000) + [7000,8000) + [10500,11000) = 2500 ns
    assert s.busy_s["/device:TPU:0"] == pytest.approx(2.5e-6)
    assert s.busy_s["/device:TPU:1"] == pytest.approx(3e-6)
    assert s.mean_busy_s == pytest.approx(2.75e-6)
    # nested ops are not counted twice
    assert s.op_seconds == pytest.approx({
        "jit_fn %run.1 custom-call:tpu_custom_call": 1e-6,
        "jit_scan %run.1 custom-call:tpu_custom_call": 0.5e-6,
        "jit_scan %while.2 while": 1e-6,
        "? %fusion.1 fusion": 3e-6})
    assert s.program_seconds("jit_scan") == pytest.approx(0.75e-6)
    assert s.program_seconds("jit_fn") == pytest.approx(0.5e-6)
    assert s.program_seconds("jit") == 0
    assert s.top_ops(1) == [["? %fusion.1 fusion", pytest.approx(1.5e-6)]]
    longest = s.longest_gaps(3)
    # dev1's gap [5000, 11000) is the longest, then dev0's [2000, 7000),
    # whose midpoint 4500 falls in the first check, outside the pjit call
    assert longest[:2] == [["benchmark.check", pytest.approx(6e-6)],
                           ["benchmark.check", pytest.approx(5e-6)]]
    assert [g[1] for g in longest] == sorted((g[1] for g in longest),
                                             reverse=True)


def test_no_window_span_is_an_error():
    p = fake_profile()
    p.planes[0].lines[0].events.pop(0)
    with pytest.raises(RuntimeError):
        tracefile.summarize(p)


def test_summary_of_a_recorded_trace():
    """Two checks of cas_register.10k traced on a TPU v5 lite: one valid,
    one with a stale read, whose report localizes it on the device."""
    import gzip

    from jax.profiler import ProfileData

    from benchmark import harness
    raw = gzip.decompress((harness.HERE / "testdata"
                           / "v5e_10k_two_checks.xplane.pb.gz").read_bytes())
    s = tracefile.summarize(ProfileData.from_serialized_xspace(raw))
    assert list(s.busy_s) == ["/device:TPU:0"]
    assert s.window_s == pytest.approx(1.142006588)
    assert s.mean_busy_s == pytest.approx(0.052195406)
    # the pallas products and the fused combine, as the trace names them
    pallas = {k for k in s.op_seconds
              if k.endswith("custom-call:tpu_custom_call")}
    assert pallas == {"jit_fn %run.1 custom-call:tpu_custom_call",
                      "jit_combine_fused %run.1 custom-call:tpu_custom_call"}
    assert s.program_seconds("jit_fn") == pytest.approx(0.022612513)
    assert s.program_seconds("jit_run") == 0
    assert s.top_ops(2)[0][0] == "jit_products %while.1 while"
    # the longest waits are the checker re-lowering its matrix programs
    assert s.longest_gaps(1)[0][0] == "lower_sharding_computation"


def test_events_leave_failed_ops_out():
    h = [op("invoke", 0, "write", 1), op("invoke", 1, "cas", [2, 3]),
         op("ok", 0, "write", 1), op("fail", 1, "cas", [2, 3]),
         op("invoke", 1, "read", None), op("ok", 1, "read", 1)]
    assert scan_us_per_event.events(h) == 4


def scan_run(trace, histories):
    pool = [NS(history=h) for h in histories]
    return NS(trace=trace, pool=pool,
              checks=[NS(j=j) for j in (0, 1, 0)])


def test_scan_time_per_event():
    h0 = [op("invoke", 0, "write", 1), op("ok", 0, "write", 1)]
    h1 = h0 + [op("invoke", 0, "read", None), op("ok", 0, "read", 1)]
    trace = tracefile.Summary(window_s=1.0, busy_s={"a": 0.5, "b": 0.5},
                              op_seconds={"jit_run %while.1 while": 16e-6,
                                          "jit_fn %fusion.1 fusion": 1.0})
    # 16 us over two devices, over the 2 + 4 + 2 events of three checks
    assert scan_us_per_event.read(scan_run(trace, [h0, h1])) == \
        pytest.approx(1.0)


def test_scan_time_left_out_without_a_scan_or_a_trace():
    h = [op("invoke", 0, "write", 1), op("ok", 0, "write", 1)]
    trace = tracefile.Summary(window_s=1.0, busy_s={"a": 0.5},
                              op_seconds={"jit_fn %fusion.1 fusion": 1.0})
    assert scan_us_per_event.read(scan_run(trace, [h, h])) is None
    assert scan_us_per_event.read(scan_run(None, [h, h])) is None
