"""The program's checker phases read from a trace, and the per-layer
metrics that read them."""
from __future__ import annotations

from types import SimpleNamespace as NS

import pytest

from benchmark import phases, tracefile
from benchmark.metrics import (
    dispatch_host_ms_per_check, encode_ms_per_check, lowerings_per_check,
    settle_ms_per_check,
)

READERS = (encode_ms_per_check, dispatch_host_ms_per_check,
           lowerings_per_check, settle_ms_per_check)


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def fake_profile(window=(1000, 11_000)):
    main = NS(name="python", events=[
        ev(tracefile.WINDOW_SPAN, window[0], window[1] - window[0]),
        ev("benchmark.check", 500, 5000),
        # starts before the window: time clipped, stats not counted
        ev("check", 600, 4800, check=1, ops=40, keys=1),
        ev("encode.ir", 600, 600, check=1, events=40),
        ev("encode.stream", 1200, 300, check=1, keys=1, events=36),
        ev("settle.report", 4000, 1000, check=1),
        ev("benchmark.check", 6000, 5000),
        ev("check", 6000, 4000, check=2, ops=40, keys=1),
        ev("encode.ir", 6000, 100, check=2, events=40),
        ev("encode.stream", 6100, 100, check=2, keys=1, events=36),
        ev("lower_sharding_computation", 6300, 500),
        # ends after the window: clipped to it
        ev("settle.explain", 10_500, 1500, check=2, keys=1)])
    rung = NS(name="python", events=[
        ev("ladder.rung", 1600, 2300, check=1, backend="jitlin-device",
           outcome="settled"),
        ev("dispatch.pad", 1600, 100, check=1, keys=1, events=36,
           steps=64),
        ev("dispatch.call", 1700, 700, check=1, lowered=1),
        ev("dispatch.readback", 2400, 1500, check=1),
        ev("ladder.rung", 6200, 3000, check=2, backend="jitlin-device",
           outcome="settled"),
        ev("dispatch.pad", 6200, 100, check=2, keys=1, events=36,
           steps=64),
        ev("dispatch.call", 6300, 600, check=2, lowered=0)])
    dev = NS(name="/device:TPU:0", lines=[NS(name=tracefile.OPS_LINE,
                                             events=[])])
    return NS(planes=[NS(name="/host:CPU", lines=[main, rung]), dev])


def test_phase_seconds_and_stats_in_the_window():
    p = phases.summarize(fake_profile())
    assert p.seconds["check"] == pytest.approx(8.4e-6)   # 4400 + 4000
    assert p.seconds["encode.ir"] == pytest.approx(0.3e-6)  # 200 + 100
    assert p.seconds["settle.explain"] == pytest.approx(0.5e-6)
    assert "lower_sharding_computation" not in p.seconds
    assert "benchmark.check" not in p.seconds
    assert p.seconds_of("encode.") == pytest.approx(0.3e-6 + 0.4e-6)
    assert p.seconds_of("dispatch.pad", "dispatch.call") == \
        pytest.approx(1.5e-6)
    assert p.seconds_of("nothing.") is None
    # the first check starts before the window: its stats are left out
    assert p.stats["check"] == {"ops": 40, "keys": 1}
    assert p.stat_sum("dispatch.call", "lowered") == 1
    assert p.stat_sum("dispatch.pad", "steps") == 128
    # strings and the check id are no counts
    assert p.stats["ladder.rung"] == {}
    assert p.stat_sum("dispatch.readback", "lowered") == 0
    assert p.stat_sum("encode.split", "keys") is None


def test_no_window_span_is_an_error():
    data = fake_profile()
    data.planes[0].lines[0].events.pop(0)
    with pytest.raises(RuntimeError):
        phases.summarize(data)


def traced_run(monkeypatch, data, checks=2, traced=True):
    monkeypatch.setattr(phases, "read",
                        lambda log_dir: phases.summarize(data))
    return NS(trace=object() if traced else None,
              cell={"name": "t.register"},
              checks=[NS(j=i) for i in range(checks)])


def test_readers_per_check(monkeypatch):
    run = traced_run(monkeypatch, fake_profile())
    assert encode_ms_per_check.read(run) == pytest.approx(0.7e-3 / 2)
    assert dispatch_host_ms_per_check.read(run) == \
        pytest.approx(1.5e-3 / 2)
    assert lowerings_per_check.read(run) == pytest.approx(0.5)
    assert settle_ms_per_check.read(run) == pytest.approx(1.5e-3 / 2)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_reader_left_out_without_its_spans(reader, monkeypatch):
    """A program that names no phases (as before the phases were
    named), an untraced run, and a window without checks."""
    bare = fake_profile()
    for line in bare.planes[0].lines:
        line.events = [e for e in line.events
                       if not phases.is_phase(e.name)]
    assert reader.read(traced_run(monkeypatch, bare)) is None
    assert reader.read(traced_run(monkeypatch, fake_profile(),
                                  traced=False)) is None
    assert reader.read(traced_run(monkeypatch, fake_profile(),
                                  checks=0)) is None


def test_recorded_trace_without_phases():
    """The two-check TPU trace was recorded before the program named its
    phases: it has a window and none of them."""
    import gzip

    from jax.profiler import ProfileData

    from benchmark import harness
    raw = gzip.decompress((harness.HERE / "testdata"
                           / "v5e_10k_two_checks.xplane.pb.gz").read_bytes())
    p = phases.summarize(ProfileData.from_serialized_xspace(raw))
    assert p.seconds == {} and p.stats == {}
    assert p.seconds_of("encode.") is None
