"""Crash-safety: WAL journal + recovery, degradation ladder, fault
registry heal, and the capped-exponential-jitter backoff schedule
(doc/robustness.md).

The kill/recover tests carry the ``chaos`` marker (run just them with
``-m chaos``); they stay fast enough for the quick lane too.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

from jepsen_tpu import telemetry


@pytest.fixture
def metrics_registry():
    """A live telemetry registry installed for the test's duration."""
    reg = telemetry.Registry()
    prev = telemetry.install(reg)
    try:
        yield reg
    finally:
        telemetry.install(prev)


# ---------------------------------------------------------------------------
# WAL + tolerant readers
# ---------------------------------------------------------------------------

def test_journal_appends_and_torn_tail(tmp_path):
    from jepsen_tpu.journal import Journal, read_wal

    p = tmp_path / "history.wal.jsonl"
    j = Journal(p, fsync_interval_s=0)
    for i in range(5):
        j.append({"type": "invoke", "f": "write", "value": i, "process": 0})
    j.close()
    ops, truncated = read_wal(p)
    assert [op["value"] for op in ops] == [0, 1, 2, 3, 4]
    assert truncated is False
    # tear the final line mid-document, as a crash would
    raw = p.read_text()
    p.write_text(raw[: len(raw) - 17])
    ops, truncated = read_wal(p)
    assert [op["value"] for op in ops] == [0, 1, 2, 3]
    assert truncated is True


def test_journal_discard(tmp_path):
    from jepsen_tpu.journal import Journal

    p = tmp_path / "w.jsonl"
    j = Journal(p)
    j.append({"a": 1})
    j.close(discard=True)
    assert not p.exists()
    j.close()  # double close is a no-op


def test_load_history_tolerates_truncated_tail(tmp_path):
    from jepsen_tpu import store

    d = tmp_path / "t" / "ts"
    d.mkdir(parents=True)
    good = json.dumps({"type": "invoke", "f": "read", "value": None})
    (d / "history.jsonl").write_text(
        good + "\n" + good + "\n" + '{"type": "ok", "f": "re')
    ops = store.load_history("t", "ts", str(tmp_path))
    assert len(ops) == 2  # torn tail dropped, no JSONDecodeError
    assert store.read_history is store.load_history


# ---------------------------------------------------------------------------
# Backoff schedule
# ---------------------------------------------------------------------------

def test_backoff_schedule_deterministic_and_capped():
    import random

    from jepsen_tpu.utils import backoff_delay

    a = [backoff_delay(n, base_s=0.1, cap_s=2.0, rng=random.Random(7))
         for n in range(8)]
    b = [backoff_delay(n, base_s=0.1, cap_s=2.0, rng=random.Random(7))
         for n in range(8)]
    assert a == b  # seeded rng -> deterministic schedule
    for n, d in enumerate(a):
        assert 0.0 <= d <= min(2.0, 0.1 * 2 ** n)
    # the ceiling grows exponentially then saturates at the cap
    rng = random.Random(0)
    big = [backoff_delay(n, base_s=0.1, cap_s=2.0, rng=rng)
           for n in range(100)]
    assert max(big) <= 2.0


def test_retry_with_backoff_retries_then_raises():
    import random

    from jepsen_tpu.utils import retry_with_backoff

    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("flake")
        return "ok"

    assert retry_with_backoff(flaky, tries=5, base_s=0.001, cap_s=0.002,
                              rng=random.Random(1)) == "ok"
    assert len(calls) == 3

    def always():
        raise RuntimeError("always")

    with pytest.raises(RuntimeError, match="always"):
        retry_with_backoff(always, tries=2, base_s=0.001, cap_s=0.002,
                           rng=random.Random(1))


def test_retry_remote_backoff_deterministic(monkeypatch):
    """RetryRemote sleeps on the capped-exponential full-jitter
    schedule, deterministic under a seeded RNG."""
    import random

    from jepsen_tpu.control import retry as retry_mod

    def run_once(seed):
        sleeps: list[float] = []
        monkeypatch.setattr(retry_mod.time, "sleep", sleeps.append)

        class Dying:
            def connect(self, spec):
                raise OSError("transport down")

        rr = retry_mod.RetryRemote(Dying(), rng=random.Random(seed))
        with pytest.raises(OSError):
            rr.connect({"host": "n1"})
        return sleeps

    a, b = run_once(42), run_once(42)
    assert a == b  # same seed -> identical schedule
    assert len(a) == retry_mod.TRIES - 1  # no sleep after the give-up try
    for n, s in enumerate(a):
        # each delay within [0, min(cap, base * 2**n)]
        assert 0.0 <= s <= min(retry_mod.BACKOFF_CAP_S,
                               retry_mod.BACKOFF_BASE_S * 2 ** n)
    assert a != run_once(7)  # different seed, different jitter


# ---------------------------------------------------------------------------
# BackendLadder
# ---------------------------------------------------------------------------

def _counter_value(reg, name, **labels):
    return reg.counter(name, labels=tuple(labels)).value(**labels)


def test_ladder_resource_exhausted_shrinks_then_demotes(metrics_registry):
    from jepsen_tpu.checker.ladder import Backend, BackendLadder

    calls = {"a": 0, "b": 0, "shrink": 0}

    def a_fn(ctx):
        calls["a"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    def a_shrink(ctx):
        calls["shrink"] += 1
        ctx["tile"] //= 2
        return True

    def b_fn(ctx):
        calls["b"] += 1
        return "b-result"

    ladder = BackendLadder([
        Backend("a", a_fn, shrink=a_shrink),
        Backend("b", b_fn),
    ], watchdog_s=0)
    ctx = {"tile": 128}
    res, backend = ladder.run(ctx)
    assert (res, backend) == ("b-result", "b")
    # demotion order: a tried, shrunk-retried once, then demoted to b
    assert calls == {"a": 2, "b": 1, "shrink": 1}
    assert ctx["tile"] == 64
    assert ctx["_attempted"] == ["a"]
    reg = metrics_registry
    assert _counter_value(reg, "checker_backend_demotions_total",
                          backend="a", reason="resource-exhausted") == 1
    assert _counter_value(reg, "checker_backend_shrink_retries_total",
                          backend="a") == 1


def test_ladder_circuit_breaker_trips(metrics_registry):
    from jepsen_tpu.checker.ladder import Backend, BackendLadder

    calls = {"a": 0}

    def a_fn(ctx):
        calls["a"] += 1
        raise RuntimeError("RESOURCE_EXHAUSTED: oom")

    ladder = BackendLadder([
        Backend("a", a_fn),
        Backend("b", lambda ctx: "b"),
    ], watchdog_s=0, breaker_threshold=2)
    for _ in range(2):
        res, backend = ladder.run({})
        assert backend == "b"
    assert ladder.broken() == {"a"}
    # breaker open: a's fn is no longer invoked at all
    res, backend = ladder.run({})
    assert backend == "b"
    assert calls["a"] == 2
    reg = metrics_registry
    assert _counter_value(reg, "checker_backend_demotions_total",
                          backend="a", reason="circuit-open") == 1
    assert reg.gauge("checker_circuit_open",
                     labels=("backend",)).value(backend="a") == 1.0
    ladder.reset()
    assert ladder.broken() == set()
    ladder.run({})
    assert calls["a"] == 3  # closed again


def test_ladder_watchdog_timeout_demotes(metrics_registry):
    from jepsen_tpu.checker.ladder import Backend, BackendLadder

    def hung(ctx):
        time.sleep(5.0)
        return "never"

    ladder = BackendLadder([
        Backend("dev", hung, device=True),
        Backend("cpu", lambda ctx: "cpu"),
    ], watchdog_s=0.05)
    t0 = time.monotonic()
    res, backend = ladder.run({})
    assert (res, backend) == ("cpu", "cpu")
    assert time.monotonic() - t0 < 2.0  # demoted, not hung
    reg = metrics_registry
    assert _counter_value(reg, "checker_watchdog_timeouts_total",
                          backend="dev") == 1
    assert _counter_value(reg, "checker_backend_demotions_total",
                          backend="dev", reason="watchdog-timeout") == 1


def test_ladder_terminal_rung_raises_and_is_breaker_exempt(
        metrics_registry):
    """A hard failure in the terminal rung propagates (check_safe wants
    the real traceback), and the terminal rung is never circuit-broken
    — a wedged breaker on the rung with no fallback would poison every
    later dispatch."""
    from jepsen_tpu.checker.ladder import Backend, BackendLadder

    calls = {"cpu": 0}

    def cpu_fn(ctx):
        calls["cpu"] += 1
        if ctx.get("explode"):
            raise ValueError("model stepped into a wall")
        return "ok"

    ladder = BackendLadder([Backend("cpu", cpu_fn)], watchdog_s=0,
                           breaker_threshold=1)
    with pytest.raises(ValueError, match="stepped into a wall"):
        ladder.run({"explode": True})
    # even after a failure past the threshold, the terminal rung still
    # runs — healthy dispatches keep settling
    res, backend = ladder.run({})
    assert (res, backend) == ("ok", "cpu")
    assert calls["cpu"] == 2


def test_ladder_decline_and_unavailable(metrics_registry):
    from jepsen_tpu.checker.ladder import (
        Backend, BackendLadder, LadderExhausted, Unavailable,
    )

    ladder = BackendLadder([
        Backend("skip", lambda ctx: None),
        Backend("unavail", lambda ctx: (_ for _ in ()).throw(Unavailable())),
        Backend("ok", lambda ctx: 42),
    ], watchdog_s=0)
    res, backend = ladder.run({})
    assert (res, backend) == (42, "ok")
    # declines never count toward the breaker
    assert ladder.broken() == set()
    with pytest.raises(LadderExhausted):
        BackendLadder([Backend("skip", lambda ctx: None)]).run({})


def _register_history(n_pairs):
    """A trivially-linearizable register history: sequential writes."""
    h = []
    for i in range(n_pairs):
        h.append({"type": "invoke", "f": "write", "value": i, "process": 0,
                  "time": 2 * i})
        h.append({"type": "ok", "f": "write", "value": i, "process": 0,
                  "time": 2 * i + 1})
    return h


def test_linearizable_forced_oom_demotes_to_cpu(metrics_registry,
                                                monkeypatch):
    """A device frontier kernel dying of RESOURCE_EXHAUSTED demotes
    (after one halved-capacity retry) to the exact CPU twin — the run
    degrades instead of crashing, with the demotion on the books."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops.jitlin import JitLinKernel

    def oom(self, stream, capacity=256):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory while "
                           "allocating frontier")

    monkeypatch.setattr(JitLinKernel, "check", oom)
    checker = LinearizableChecker(accelerator="auto", watchdog_s=0)
    out = checker.check({}, _register_history(300), {})
    assert out["valid?"] is True
    assert out["algorithm"] == "jitlin-cpu(fallback)"
    reg = metrics_registry
    assert _counter_value(reg, "checker_backend_demotions_total",
                          backend="jitlin-device",
                          reason="resource-exhausted") == 1
    assert _counter_value(reg, "checker_backend_shrink_retries_total",
                          backend="jitlin-device") == 1


@pytest.mark.parametrize("failure", ["oom", "error"])
def test_linearizable_tpu_pinned_device_failure_is_loud(failure,
                                                        monkeypatch):
    """accelerator="tpu" pins the device: a device rung that fails
    (after its shrink retry) raises DeviceFailed instead of settling a
    CPU verdict, while "auto" keeps demoting to the CPU rung."""
    from jepsen_tpu.checker.ladder import DeviceFailed
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops.jitlin import JitLinKernel

    msg = ("RESOURCE_EXHAUSTED: out of memory" if failure == "oom"
           else "injected device failure")

    def boom(self, stream, capacity=256):
        raise RuntimeError(msg)

    monkeypatch.setattr(JitLinKernel, "check", boom)
    with pytest.raises(DeviceFailed):
        LinearizableChecker(accelerator="tpu", watchdog_s=0).check(
            {}, _register_history(300), {})
    out = LinearizableChecker(accelerator="auto", watchdog_s=0).check(
        {}, _register_history(300), {})
    assert out["algorithm"] == "jitlin-cpu(fallback)"


def test_ladder_strict_device_demotes_between_device_rungs():
    """Under strict_device a failed device rung may still hand over to
    the next DEVICE rung; only the step onto a host rung raises."""
    from jepsen_tpu.checker.ladder import Backend, BackendLadder, DeviceFailed

    def bad(ctx):
        raise RuntimeError("sharded collective failed")

    ladder = BackendLadder([
        Backend("mesh", bad, device=True),
        Backend("single", lambda ctx: "dev", device=True),
        Backend("cpu", lambda ctx: "host"),
    ], watchdog_s=0)
    assert ladder.run({"strict_device": True}) == ("dev", "single")
    ladder = BackendLadder([
        Backend("mesh", bad, device=True),
        Backend("cpu", lambda ctx: "host"),
    ], watchdog_s=0)
    assert ladder.run({}) == ("host", "cpu")
    with pytest.raises(DeviceFailed):
        ladder.run({"strict_device": True})


def test_linearizable_ladder_bit_identical_host_path():
    """The ladder refactor must not change host-regime dispatch: the
    native/python rungs produce the same verdicts and labels as the
    direct calls."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    h = _register_history(20)
    out = LinearizableChecker(accelerator="cpu").check({}, h, {})
    assert out["valid?"] is True
    assert out["algorithm"] in ("jitlin-native", "jitlin-cpu")


# ---------------------------------------------------------------------------
# Fault registry
# ---------------------------------------------------------------------------

def test_fault_classify():
    from jepsen_tpu.nemesis.faults import classify

    assert classify("start-partition") == ("begin", "net")
    assert classify("stop-partition") == ("end", "net")
    assert classify("start_partition") == ("begin", "net")
    assert classify("kill") == ("begin", "process")
    # bare start/stop are ambiguous (kill-heal vs raw-partitioner
    # open/close) and deliberately unclassified
    assert classify("start") == (None, None)
    assert classify("stop") == (None, None)
    assert classify("pause") == ("begin", "pause")
    assert classify("resume") == ("end", "pause")
    assert classify("bump") == ("begin", "clock")
    assert classify("reset") == ("end", "clock")
    assert classify("truncate-file") == ("begin", "file")
    # prefix fallback maps only to kinds we can actually heal: a
    # partition-flavored suffix is net; an unknown suffix (yugabyte's
    # stop-master is an INJECTION, not a heal) stays unclassified
    assert classify("start-partition-replica") == ("begin", "net")
    assert classify("stop-partition-replica") == ("end", "net")
    assert classify("stop-master") == (None, None)
    assert classify("read") == (None, None)
    assert classify(None) == (None, None)
    # membership reconfigurations: one-shot "begin" transitions, healed
    # by State resolution (nemesis/membership.py), never by a close op
    for f in ("grow", "shrink", "join", "leave", "add-node",
              "remove-node", "rolling-restart", "reconfigure"):
        assert classify(f) == ("begin", "membership"), f
    assert classify("rolling_restart") == ("begin", "membership")
    # libfaketime clock-rate windows are a proper begin/end pair
    assert classify("start-clock-rate") == ("begin", "clock-rate")
    assert classify("stop-clock-rate") == ("end", "clock-rate")


def test_teardown_heals_and_unhealable_table_rows():
    """The PR-9 table extensions: clock-rate is restored by a clean
    nemesis teardown (unwrap); membership is NOT — State.teardown does
    not restore the member set, so unresolved reconfigs must stay on
    the books for replay — and neither is unhealable evidence."""
    from jepsen_tpu.nemesis.faults import (
        KINDS, ROW_HEALERS, TEARDOWN_HEALS, UNHEALABLE_KINDS,
    )
    assert "membership" in KINDS and "clock-rate" in KINDS
    assert "clock-rate" in TEARDOWN_HEALS
    assert "membership" not in TEARDOWN_HEALS
    assert "membership" not in UNHEALABLE_KINDS
    assert "clock-rate" not in UNHEALABLE_KINDS
    # both heal from WHAT was recorded (pre-op set / binary path), not
    # from a kind-wide cluster action
    assert set(ROW_HEALERS) == {"membership", "clock-rate"}


def test_teardown_marker_skips_membership(tmp_path):
    """core's teardown heal marker must leave membership entries
    unhealed: the fake State teardown can't re-join a removed node."""
    from jepsen_tpu.nemesis.faults import TEARDOWN_HEALS, FaultRegistry

    reg = FaultRegistry(tmp_path / "faults.jsonl")
    a = reg.record("net", f="start-partition")
    b = reg.record("membership", f="shrink",
                   value={"pre_members": ["n1", "n2"]})
    assert reg.mark_healed(kinds=TEARDOWN_HEALS, via="teardown") == [a]
    assert [r["id"] for r in reg.unhealed()] == [b]
    reg.close()


def test_fault_registry_roundtrip_and_reopen(tmp_path):
    from jepsen_tpu.nemesis.faults import FaultRegistry

    p = tmp_path / "faults.jsonl"
    reg = FaultRegistry(p)
    a = reg.record("net", f="start-partition", value="majority")
    b = reg.record("clock", f="bump", value={"n1": 100})
    assert [r["id"] for r in reg.unhealed()] == [a, b]
    assert reg.mark_healed(kind="net", via="nemesis") == [a]
    assert [r["id"] for r in reg.unhealed()] == [b]
    reg.close()
    # reopen: the durable log reconstructs the same state
    reg2 = FaultRegistry(p)
    assert [r["id"] for r in reg2.unhealed()] == [b]
    # ids keep monotonically increasing after reopen
    c = reg2.record("net", f="start-partition")
    assert c > b
    # healing twice marks once
    assert reg2.mark_healed(fault_id=b) == [b]
    assert reg2.mark_healed(fault_id=b) == []
    # the teardown marker never claims file damage healed
    d = reg2.record("file", f="truncate-file")
    from jepsen_tpu.nemesis.faults import TEARDOWN_HEALS
    assert reg2.mark_healed(kinds=TEARDOWN_HEALS, via="teardown") == [c]
    assert [r["id"] for r in reg2.unhealed()] == [d]
    reg2.close()


def test_replay_unhealed_heals_exactly_once(tmp_path):
    from jepsen_tpu.net import NoopNet
    from jepsen_tpu.nemesis.faults import FaultRegistry, replay_unhealed

    p = tmp_path / "faults.jsonl"
    reg = FaultRegistry(p)
    reg.record("net", f="start-partition")
    reg.record("net", f="start-partition")
    reg.record("file", f="truncate-file")
    test = {"nodes": ["n1", "n2"], "ssh": {"dummy": True},
            "net": NoopNet()}
    out = replay_unhealed(test, reg)
    assert len(out["healed"]) == 2      # both net faults, one heal action
    assert len(out["unhealable"]) == 1  # file damage has no inverse
    assert test["_net_log"] == [("heal",)]  # exactly one net.heal
    # second replay: net entries are marked healed; nothing re-applied
    out2 = replay_unhealed(test, reg)
    assert out2["healed"] == []
    assert test["_net_log"] == [("heal",)]
    reg.close()


def test_heal_clock_raises_when_no_mechanism_works(monkeypatch):
    """A clock heal that can't verify any reset mechanism worked must
    raise — the registry marks healed only on clean return, and a false
    success would durably destroy the only record that the clocks are
    still scrambled."""
    from jepsen_tpu import control
    from jepsen_tpu.control.core import RemoteError
    from jepsen_tpu.nemesis import faults as fm

    monkeypatch.setattr(control, "on", lambda node, test, fn: fn())

    def bad_exec(*a, **k):
        raise RemoteError("command not found")

    monkeypatch.setattr(control, "exec_", bad_exec)
    with pytest.raises(RuntimeError, match="clock-reset"):
        fm._heal_clock({"nodes": ["n1"]})


def test_recover_prefers_longer_wal_over_torn_history(tmp_path):
    """A crash DURING save_1 leaves a torn history.jsonl next to the
    complete journal; --recover must use the journal, not silently
    analyze the truncated history as if the run were complete."""
    from jepsen_tpu import store
    from jepsen_tpu.journal import Journal

    run_dir = tmp_path / "noop" / "20260101T000000.000"
    run_dir.mkdir(parents=True)
    ops = []
    for i in range(6):
        ops.append({"type": "invoke", "f": "write", "value": i,
                    "process": 0, "time": 2 * i, "index": 2 * i})
        ops.append({"type": "ok", "f": "write", "value": i,
                    "process": 0, "time": 2 * i + 1, "index": 2 * i + 1})
    j = Journal(run_dir / "history.wal.jsonl", fsync_interval_s=0)
    for op in ops:
        j.append(op)
    j.close()
    # torn mid-save: only the first 3 ops landed, last one torn
    with open(run_dir / "history.jsonl", "w") as f:
        for op in ops[:3]:
            f.write(json.dumps(op) + "\n")
        f.write('{"type": "inv')
    (run_dir / "test.json").write_text(json.dumps(
        {"name": "noop", "start_time": "20260101T000000.000",
         "nodes": ["n1"], "ssh": {"dummy": True}}))
    main = _cli_main()
    rc = main(["analyze", "--recover", "--store-dir", str(tmp_path),
               "--test-name", "noop", "--no-ssh", "--accelerator", "cpu"])
    assert rc == 0
    recovered = store.load_history("noop", "20260101T000000.000",
                                   str(tmp_path))
    assert len(recovered) == len(ops)  # journal won over the torn file
    results = json.loads((run_dir / "results.json").read_text())
    assert results["incomplete"] is True


def test_heal_refuses_to_heal_blind(tmp_path):
    """cli heal with faults on the books but no readable node list must
    NOT mark them healed — that would destroy the only record that
    healing is still needed."""
    import argparse

    from jepsen_tpu import cli
    from jepsen_tpu.nemesis.faults import FaultRegistry

    run_dir = tmp_path / "t" / "ts"
    run_dir.mkdir(parents=True)
    reg = FaultRegistry(run_dir / "faults.jsonl")
    reg.record("net", f="start-partition")
    reg.close()
    # no test.json at all
    opts = argparse.Namespace(dir=str(run_dir), test_name=None,
                              timestamp=None, store_dir=str(tmp_path))
    assert cli.heal_cmd(opts) == cli.EXIT_UNKNOWN
    reg = FaultRegistry(run_dir / "faults.jsonl")
    assert len(reg.unhealed()) == 1  # registry untouched
    reg.close()


# ---------------------------------------------------------------------------
# Chaos: SIGKILL mid-run -> analyze --recover -> cli heal
# ---------------------------------------------------------------------------

def _cli_main():
    from jepsen_tpu import cli
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.fakes import noop_test

    def build(opts):
        return cli.test_opts_to_test(
            opts, noop_test(checker=linearizable(accelerator="cpu")))

    return cli.single_test_cmd(build)


@pytest.mark.chaos
def test_sigkill_midrun_recover_and_heal(tmp_path):
    """The acceptance scenario end to end: a fake-mode run SIGKILLed
    mid-case leaves a replayable WAL and an unhealed-fault registry;
    ``analyze --recover`` produces a valid-but-incomplete verdict over
    the partial history; ``cli heal`` restores net state and a second
    heal is a no-op."""
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "crashsafe_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, worker, str(tmp_path)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    # wait for the WAL to accumulate ops, then kill mid-case
    wal = None
    deadline = time.monotonic() + 120
    try:
        while time.monotonic() < deadline:
            wals = list(tmp_path.glob("noop/*/history.wal.jsonl"))
            if wals and wals[0].read_text().count("\n") >= 40:
                wal = wals[0]
                break
            if proc.poll() is not None:
                out = proc.stdout.read()
                pytest.fail(f"worker exited early ({proc.returncode}):\n"
                            f"{out[-4000:]}")
            time.sleep(0.05)
        assert wal is not None, "WAL never appeared"
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)

    run_dir = wal.parent
    # the crash left: a journal, an early test.json, an unhealed fault —
    # and NO saved history/results
    assert not (run_dir / "history.jsonl").exists()
    assert not (run_dir / "results.json").exists()
    assert (run_dir / "test.json").exists()
    from jepsen_tpu.nemesis.faults import FaultRegistry
    freg = FaultRegistry(run_dir / "faults.jsonl")
    unhealed = freg.unhealed()
    freg.close()
    assert [r["kind"] for r in unhealed] == ["net"]

    # analyze --recover: a valid verdict over the partial history,
    # badged incomplete; the run becomes re-analyzable normally
    main = _cli_main()
    rc = main(["analyze", "--recover", "--store-dir", str(tmp_path),
               "--no-ssh", "--accelerator", "cpu"])
    assert rc == 0
    results = json.loads((run_dir / "results.json").read_text())
    assert results["valid?"] is True
    assert results["incomplete"] is True
    assert (run_dir / "history.jsonl").exists()
    ops = [json.loads(line) for line in
           (run_dir / "history.jsonl").read_text().splitlines()]
    assert len(ops) >= 40
    test_json = json.loads((run_dir / "test.json").read_text())
    assert test_json.get("wal_recovered") is True

    # cli heal: replays the unhealed partition heal (dummy transport ->
    # NoopNet), marks it healed; the second heal is a no-op
    rc = main(["heal", str(tmp_path)])
    assert rc == 0
    freg = FaultRegistry(run_dir / "faults.jsonl")
    assert freg.unhealed() == []
    freg.close()
    rc = main(["heal", str(tmp_path)])
    assert rc == 0


@pytest.mark.chaos
def test_failed_teardown_triggers_crash_path_replay(tmp_path):
    """A nemesis whose teardown keeps dying (after the backoff retries)
    leaves its partition unmarked — core.run's crash-path finally
    replays the heal, so the run still ends with a clean cluster and a
    fully-healed registry."""
    from jepsen_tpu import core
    from jepsen_tpu import generator as gen
    from jepsen_tpu import nemesis as nem
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test
    from jepsen_tpu.nemesis.faults import FaultRegistry

    class TeardownDies(nem.Nemesis):
        def __init__(self, inner):
            self.inner = inner

        def setup(self, test):
            return TeardownDies(self.inner.setup(test))

        def fs(self):
            return self.inner.fs()

        def invoke(self, test, op):
            return self.inner.invoke(test, op)

        def teardown(self, test):
            raise RuntimeError("teardown dies every time")

    db = AtomDB()
    # a partition that the generator never stops: only teardown (which
    # dies) or the crash-path replay can heal it
    g = gen.Seq([
        gen.nemesis_gen(gen.Seq([
            {"type": "info", "f": "start-partition", "value": None}])),
        gen.clients(gen.limit(4, gen.cycle(gen.Seq(
            [{"type": "invoke", "f": "write", "value": 1}])))),
    ])
    t = noop_test(db=db, client=AtomClient(db),
                  nemesis=TeardownDies(nem.partitioner()),
                  generator=g, store_dir=str(tmp_path), time_limit=30.0)
    result = core.run(t)
    runs = list(tmp_path.glob("noop/*/faults.jsonl"))
    assert runs, "fault registry missing"
    freg = FaultRegistry(runs[0])
    assert freg.unhealed() == []  # crash-path replay healed the partition
    freg.close()
    rows = [json.loads(line) for line in runs[0].read_text().splitlines()]
    heals = [r for r in rows if r["op"] == "heal"]
    assert heals and heals[-1]["via"] == "replay"
    # the replay really drove the net layer: the last action on the
    # (NoopNet) log is the heal
    assert result["_net_log"][-1] == ("heal",)
