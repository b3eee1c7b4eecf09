"""The ``elle_append`` configuration at test size: its twin cell through
the harness on the CPU, its plant, its plain reference against the
program, and the per-layer metrics that read the Elle checker's spans."""
from __future__ import annotations

import copy
from types import SimpleNamespace as NS

import bench_testing
import pytest

from benchmark import elle_screen, harness, list_append_reference, tracefile
from benchmark.metrics import (
    elle_build_ms_per_check, elle_classify_ms_per_check,
    elle_cluster_ms_per_check, screen_roofline,
)
from benchmark import phases

CELL = "t.append"
SEED = bench_testing.SEED


def bench() -> harness.Bench:
    b = harness.load()
    spec = copy.deepcopy(b.spec)
    spec["workloads"] = [{"name": CELL, "config": "elle_append",
                          "traffic": "t_append", "chips": 1,
                          "why": "test"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    return harness.Bench(spec, traffic_dir=bench_testing.TRAFFIC)


def pool(seed: int = SEED):
    b = bench()
    w = b.cell(CELL)
    mix, chk = b.mix(w), b.checker(w)
    return mix, chk, [chk.history(mix, seed, j) for j in range(mix.pool)]


def run(**kw) -> dict:
    return harness.run(bench(), CELL, SEED, 0.1, False, require_tpu=False,
                       persistent_cache=False, **kw)


def test_twin_cell_is_correct():
    out = run()
    assert out["correct"] is True, out["compared"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"verified_ops_per_s", "setup_s"}


def test_twin_cell_control_is_not_correct():
    """The control drops the realtime order, so it accepts the stale
    read the odd history carries."""
    out = run(control_checks=2)
    assert out["correct"] is False
    assert out["compared"]["verdict_mismatches"]["value"] == 1


def test_mix_and_ops():
    mix, _, hs = pool()
    assert (mix.pool, mix.ops_per_key, mix.threads) == (2, 400, 10)
    assert (mix.key_count, mix.max_txn_length, mix.max_writes_per_key) == \
        (3, 4, 256)
    for p in hs:
        # verified_ops_per_s counts txns: an invocation and its completion
        assert p.ops == len(p.history) // 2 == 400
        assert {op["type"] for op in p.history} == {"invoke", "ok"}
        assert {op["process"] for op in p.history} == set(range(10))


@pytest.mark.parametrize("seed", [SEED, 5, 2**40 + 3])
def test_plant_is_realtime_only_and_flagged(seed):
    """The valid history is valid; the planted one breaks strict
    serializability alone: the program and the reference find the
    realtime cycle, and the serializable control finds nothing."""
    _, chk, (valid, bad) = pool(seed)
    assert valid.plants == []
    [(key, kind, at)] = bad.plants
    assert (key, kind) == (None, "stale_read")
    assert bad.history[at]["type"] == "ok"
    for p, want in ((valid, (True, ())), (bad, (False, ("realtime-cycle",)))):
        assert chk.reference(p.history, "reference") == {"history": want}
        assert chk.reference(p.history, "control") == {"history": (True, ())}
        got = chk.answer(chk.check(p.history, {}), p.history)
        assert got == {"history": want}


def test_seed_renames_and_keeps_the_shape():
    _, _, a = pool(SEED)
    _, _, b = pool(7)
    for x, y in zip(a, b):
        assert x.plants == y.plants
        assert [(op["type"], len(op["value"])) for op in x.history] == \
            [(op["type"], len(op["value"])) for op in y.history]
    assert a[0].history != b[0].history


# ---- the plain reference against the program, one anomaly each ----------

def ok_txn(p, *mops):
    return p, list(mops)


def events(block: list, concurrent: bool, fail: set = frozenset()) -> list:
    """Op dicts of txns ``(process, micro-ops)``: all invoked, then all
    completed (``concurrent``), or one after another; a txn whose index
    is in ``fail`` fails."""
    def inv(p, mops):
        return {"type": "invoke", "process": p, "f": "txn",
                "value": [[f, k, None if f == "r" else v]
                          for f, k, v in mops]}

    def done(i, p, mops):
        return {"type": "fail" if i in fail else "ok", "process": p,
                "f": "txn", "value": [list(m) for m in mops]}

    if concurrent:
        return [inv(p, m) for p, m in block] + \
            [done(i, p, m) for i, (p, m) in enumerate(block)]
    return [op for i, (p, m) in enumerate(block)
            for op in (inv(p, m), done(i, p, m))]


X, Y = 10_001, 10_002


def A(k, v):
    return ["append", k, v]


def R(k, vs):
    return ["r", k, vs]


PLANTS = {
    "G0": (events([ok_txn(100, A(X, 1), A(Y, 1)),
                   ok_txn(101, A(X, 2), A(Y, 2)),
                   ok_txn(102, R(X, [1, 2]), R(Y, [2, 1]))], True),
           "G0"),
    "G1a": (events([ok_txn(100, A(X, 1)), ok_txn(101, R(X, [1]))], False,
                   fail={0}), "G1a"),
    "G1b": (events([ok_txn(100, A(X, 1), A(X, 2)), ok_txn(101, R(X, [1])),
                    ok_txn(102, R(X, [1, 2]))], True), "G1b"),
    "G1c": (events([ok_txn(100, A(X, 1), R(Y, [1])),
                    ok_txn(101, A(Y, 1), R(X, [1]))], True), "G1c"),
    "G-single": (events([ok_txn(100, R(X, []), R(Y, [1])),
                         ok_txn(101, A(X, 1), A(Y, 1)),
                         ok_txn(102, R(X, [1]))], True), "G-single"),
    "G2": (events([ok_txn(100, R(X, []), A(Y, 1)),
                   ok_txn(101, R(Y, []), A(X, 1)),
                   ok_txn(102, R(X, [1]), R(Y, [1]))], True), "G2"),
    "internal": (events([ok_txn(100, A(X, 1), R(X, [])),
                         ok_txn(101, R(X, [1]))], True), "internal"),
    "realtime": (events([ok_txn(100, A(X, 1)), ok_txn(101, R(X, [])),
                         ok_txn(102, R(X, [1]))], False), "realtime-cycle"),
}


@pytest.fixture(scope="module")
def planted() -> dict:
    """{plant: history}: a seeded valid 120-txn history with the plant's
    txns after it, on fresh keys and processes."""
    b = bench()
    w = b.cell(CELL)
    mix = b.mix(w)
    import dataclasses
    small = dataclasses.replace(mix, ops_per_key=120)
    chk = b.checker(w)
    out = {}
    for i, (name, (ops, _)) in enumerate(PLANTS.items()):
        base = chk.history(small, SEED + i, 0).history
        out[name] = base + copy.deepcopy(ops)
    return out


@pytest.mark.parametrize("accelerator", ["auto", "tpu", "cpu"])
@pytest.mark.parametrize("name", list(PLANTS))
def test_program_agrees_with_the_reference(planted, name, accelerator):
    from jepsen_tpu.elle import list_append
    h = planted[name]
    want = list_append_reference.check(h)
    assert want[0] is False and PLANTS[name][1] in want[1]
    got = list_append.check(h, accelerator=accelerator)
    assert (got["valid?"], tuple(sorted(got["anomaly-types"]))) == want


def test_reference_refuses_indeterminate_txns():
    h = events([ok_txn(100, A(X, 1))], False)
    h[-1]["type"] = "info"
    with pytest.raises(ValueError):
        list_append_reference.check(h)


# ---- the per-layer metrics that read the Elle checker's spans ------------

def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def fake_profile(spans: bool = True):
    """A window of two checks: each an IR build, a cluster span, a
    screen dispatch and a classify span (the first check's starts before
    the window)."""
    events = [ev(tracefile.WINDOW_SPAN, 1000, 20_000),
              # a warm-up dispatch, before the window
              ev("dispatch.elle_screen", 100, 300, b=8, v=512, e=64,
                 steps=9)]
    for c, t in ((1, 500), (2, 11_000)):
        events += [ev("benchmark.check", t, 9000),
                   ev("check", t, 9000, check=c, ops=800),
                   ev("encode.ir", t, 1000, check=c, events=800),
                   ev("encode.elle_build", t + 1000, 3000, check=c,
                      events=800, txns=400, edges=3000),
                   ev("dispatch.elle_cluster", t + 4000, 2000, check=c,
                      clusters=12, device_screened=8, host_screened=4,
                      oversized=0),
                   ev("dispatch.elle_screen", t + 6000, 1000, check=c, b=8,
                      v=16, e=64, steps=4),
                   ev("settle.elle_classify", t + 7000, 500, check=c,
                      clusters=1)]
    if not spans:
        events = [e for e in events if not phases.is_phase(e.name)]
    return NS(planes=[NS(name="/host:CPU",
                         lines=[NS(name="python", events=events)])])


def traced_run(monkeypatch, data, checks=2, traced=True,
               kind="TPU v5 lite", screen_s=2e-6):
    monkeypatch.setattr(phases, "read", lambda d: phases.summarize(data))
    monkeypatch.setattr(elle_screen, "dispatches",
                        lambda d: elle_screen.summarize(data))
    trace = tracefile.Summary(
        window_s=2e-5, busy_s={"/device:TPU:0": 4e-6},
        op_seconds={f"{elle_screen.PROGRAM} %dot.1 fusion": screen_s,
                    "jit_run %while.2 while": 2e-6})
    return NS(trace=trace if traced else None, cell={"name": CELL},
              device_kind=kind, checks=[NS(j=i) for i in range(checks)])


def test_span_readers_per_check(monkeypatch):
    run = traced_run(monkeypatch, fake_profile())
    # the first check's encode.ir crosses the window's start: half of it
    # is clipped away
    assert elle_build_ms_per_check.read(run) == \
        pytest.approx((500 + 3000 + 4000) * 1e-9 * 1e3 / 2)
    assert elle_cluster_ms_per_check.read(run) == \
        pytest.approx((3000 + 3000) * 1e-9 * 1e3 / 2)
    assert elle_classify_ms_per_check.read(run) == \
        pytest.approx(1000 * 1e-9 * 1e3 / 2)


def test_screen_roofline(monkeypatch):
    """Only the screen spans that start in the window count (two here),
    over the screen program's device time alone."""
    run = traced_run(monkeypatch, fake_profile())
    least = elle_screen.roofline_seconds(8, 16, 64, 4, "TPU v5 lite")
    assert screen_roofline.read(run) == pytest.approx(100 * 2 * least / 2e-6)
    assert 0 < screen_roofline.read(run) < 100


@pytest.mark.parametrize("reader", [
    elle_build_ms_per_check, elle_cluster_ms_per_check,
    elle_classify_ms_per_check, screen_roofline],
    ids=lambda r: r.__name__.rsplit(".", 1)[-1])
def test_readers_left_out(reader, monkeypatch):
    """A program that names no Elle phases (as before they were named),
    an untraced run, and a window without checks read nothing."""
    assert reader.read(traced_run(monkeypatch, fake_profile(False))) is None
    assert reader.read(traced_run(monkeypatch, fake_profile(),
                                  traced=False)) is None
    if reader is not screen_roofline:
        assert reader.read(traced_run(monkeypatch, fake_profile(),
                                      checks=0)) is None


def test_screen_roofline_left_out_without_its_program_or_peaks(monkeypatch):
    assert screen_roofline.read(traced_run(
        monkeypatch, fake_profile(), screen_s=0.0)) is None
    assert screen_roofline.read(traced_run(
        monkeypatch, fake_profile(), kind="TPU v9")) is None
