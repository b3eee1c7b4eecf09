"""Membership + clock-rate nemesis tier (doc/robustness.md "Membership
and clock-rate faults"): the modeled reconfiguration state machine, its
durable fault records and exactly-once rejoin heal, deadline interplay,
preflight NEM diagnostics, and the faketime clock-rate package.

The SIGKILL chaos scenario rides the slow lane (``-m 'membership and
slow'``); everything else is quick."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error

import pytest

from jepsen_tpu import telemetry
from jepsen_tpu.fakes import FakeClusterState
from jepsen_tpu.nemesis import membership
from jepsen_tpu.nemesis.faults import FaultRegistry, replay_unhealed
from jepsen_tpu.utils import with_relative_time

pytestmark = pytest.mark.membership

NODES = ["n1", "n2", "n3", "n4", "n5"]


@pytest.fixture
def metrics_registry():
    reg = telemetry.Registry()
    prev = telemetry.install(reg)
    try:
        yield reg
    finally:
        telemetry.install(prev)


def _run(test):
    from jepsen_tpu.generator import interpreter
    with with_relative_time():
        return interpreter.run(test)


# ---------------------------------------------------------------------------
# FakeClusterState: the durable fake cluster
# ---------------------------------------------------------------------------

def test_fake_cluster_state_durable_roundtrip(tmp_path):
    p = tmp_path / "members.json"
    st = FakeClusterState(p, nodes=NODES)
    assert st.members() == set(NODES)
    assert json.loads(p.read_text()) == sorted(NODES)
    out = st.invoke({}, {"f": "shrink", "value": "n5"})
    assert out["action"] == "shrink"
    assert json.loads(p.read_text()) == ["n1", "n2", "n3", "n4"]
    # a NEW state over the same file sees the shrunken set: the file IS
    # the cluster, so reconfigurations survive a control-process crash
    st2 = FakeClusterState(p, nodes=NODES)
    assert st2.members() == {"n1", "n2", "n3", "n4"}
    # op() proposes growing the missing node back
    op = st2.op({"nodes": NODES})
    assert (op["f"], op["value"]) == ("grow", "n5")


def test_fake_cluster_state_settle_window(tmp_path):
    st = FakeClusterState(tmp_path / "m.json", nodes=NODES, settle_s=30.0)
    val = st.invoke({}, {"f": "shrink", "value": "n5"})
    # in flight: unresolved, and no second op proposed
    assert st.resolve_op({}, ({"f": "shrink"}, val)) is None
    assert st.op({"nodes": NODES}) == "pending"
    fast = FakeClusterState(tmp_path / "m2.json", nodes=NODES, settle_s=0.0)
    val = fast.invoke({}, {"f": "shrink", "value": "n5"})
    assert fast.resolve_op({}, ({"f": "shrink"}, val)) is fast


def test_restore_members_file_idempotent(tmp_path):
    p = tmp_path / "members.json"
    st = FakeClusterState(p, nodes=NODES)
    st.invoke({}, {"f": "shrink", "value": "n5"})
    row = {"id": 0, "kind": "membership",
           "value": {"pre_members": sorted(NODES),
                     "heal": st.heal_spec({})}}
    membership.heal_record({}, row)
    assert json.loads(p.read_text()) == sorted(NODES)
    membership.heal_record({}, row)  # idempotent
    assert json.loads(p.read_text()) == sorted(NODES)


def test_heal_record_rejects_missing_spec():
    from jepsen_tpu.nemesis.faults import Unhealable
    with pytest.raises(Unhealable, match="no heal spec"):
        membership.heal_record({}, {"id": 1, "value": {"pre_members": []}})
    with pytest.raises(Unhealable, match="unknown membership heal"):
        membership.heal_record({}, {"id": 1, "value": {
            "pre_members": [], "heal": {"mechanism": "telepathy"}}})
    with pytest.raises(Unhealable, match="not importable"):
        membership.heal_record({}, {"id": 1, "value": {
            "pre_members": [], "heal": {"mechanism": "import",
                                        "module": "no.such.module",
                                        "fn": "nope"}}})


# ---------------------------------------------------------------------------
# MembershipNemesis: records, resolution heal, thread safety, bounds
# ---------------------------------------------------------------------------

def test_invoke_records_pre_op_set_and_heals_on_resolve(tmp_path,
                                                        metrics_registry):
    st = FakeClusterState(tmp_path / "m.json", nodes=NODES, settle_s=0.0)
    n = membership.MembershipNemesis(st, poll_interval=0.05)
    registry = FaultRegistry(tmp_path / "faults.jsonl")
    test = {"nodes": NODES, "_faults": registry}
    out = n.invoke(test, {"type": "info", "f": "shrink", "value": "n5"})
    assert out["type"] == "info"
    rows = [json.loads(line)
            for line in (tmp_path / "faults.jsonl").read_text().splitlines()]
    injects = [r for r in rows if r["op"] == "inject"]
    heals = [r for r in rows if r["op"] == "heal"]
    assert len(injects) == 1 and injects[0]["kind"] == "membership"
    assert injects[0]["value"]["pre_members"] == sorted(NODES)
    assert injects[0]["value"]["heal"]["mechanism"] == "file"
    # settle_s=0: the trailing resolve pass already marked it healed
    assert heals and heals[0]["via"] == "resolve"
    assert registry.unhealed() == []
    registry.close()
    reg = metrics_registry
    assert reg.counter("nemesis_membership_ops_total",
                       labels=("f",)).value(f="shrink") == 1
    assert reg.counter("nemesis_membership_resolves_total",
                       labels=("f",)).value(f="shrink") == 1


def test_reconfig_cycles_leave_nothing_pending_or_unhealed(tmp_path):
    """Alternating shrink/grow through record, invoke and resolve: once
    settled, no op is pending and every durable record is healed."""
    st = FakeClusterState(tmp_path / "m.json", nodes=NODES, settle_s=0.0)
    n = membership.MembershipNemesis(st, poll_interval=3600)
    registry = FaultRegistry(tmp_path / "faults.jsonl")
    test = {"nodes": NODES, "_faults": registry}
    for i in range(20):
        f = "shrink" if i % 2 == 0 else "grow"
        n.invoke(test, {"type": "info", "f": f, "value": "n5"})
    assert n.pending_count() == 0
    assert registry.unhealed() == []
    registry.close()
    assert json.loads((tmp_path / "m.json").read_text()) == sorted(NODES)


def test_unresolved_op_stays_unhealed_and_replays(tmp_path):
    """A reconfig that never resolves (settle window) leaves its entry
    on the books; replay_unhealed restores the recorded pre-op set
    exactly once."""
    p = tmp_path / "m.json"
    st = FakeClusterState(p, nodes=NODES, settle_s=600.0)
    n = membership.MembershipNemesis(st, poll_interval=0.05)
    registry = FaultRegistry(tmp_path / "faults.jsonl")
    test = {"nodes": NODES, "_faults": registry}
    n.invoke(test, {"type": "info", "f": "shrink", "value": "n5"})
    assert json.loads(p.read_text()) == ["n1", "n2", "n3", "n4"]
    assert [r["kind"] for r in registry.unhealed()] == ["membership"]
    out = replay_unhealed({"nodes": NODES}, registry)
    assert len(out["healed"]) == 1
    assert json.loads(p.read_text()) == sorted(NODES)  # pre-op set back
    # exactly once: a second replay is a no-op even if the file moved on
    p.write_text(json.dumps(["sentinel"]))
    out2 = replay_unhealed({"nodes": NODES}, registry)
    assert out2 == {"healed": [], "unhealable": [], "failed": []}
    assert json.loads(p.read_text()) == ["sentinel"]
    registry.close()


def test_newest_first_replay_restores_oldest_pre_op_set(tmp_path):
    """Two stranded reconfigs: the replay must end on the OLDEST
    record's pre-op set — the cluster as it was before the first
    stranded op."""
    p = tmp_path / "m.json"
    st = FakeClusterState(p, nodes=NODES, settle_s=600.0)
    n = membership.MembershipNemesis(st, poll_interval=0.05)
    registry = FaultRegistry(tmp_path / "faults.jsonl")
    test = {"nodes": NODES, "_faults": registry}
    n.invoke(test, {"type": "info", "f": "shrink", "value": "n5"})
    n.invoke(test, {"type": "info", "f": "shrink", "value": "n4"})
    assert json.loads(p.read_text()) == ["n1", "n2", "n3"]
    replay_unhealed({"nodes": NODES}, registry)
    assert json.loads(p.read_text()) == sorted(NODES)
    registry.close()


def test_resolve_fixed_point_bounded(metrics_registry):
    """A State that resolves at most one op per pass cannot spin the
    fixed point past max_resolve_iters; the cap is counted."""

    class OnePerPass(membership.State):
        _budget = 0

        def fs(self):
            return {"tick"}

        def merge_views(self, test, views):
            return self

        def resolve(self, test):
            self._budget = 1  # one resolution per fixed-point pass
            return self

        def resolve_op(self, test, pair):
            if self._budget > 0:
                self._budget -= 1
                return self
            return None

    st = OnePerPass()
    n = membership.MembershipNemesis(st, max_resolve_iters=2)
    with n._lock:
        n._pending = [membership._Pending({"f": "tick", "value": i},
                                          {}, None, False)
                      for i in range(5)]
    n._resolve({})
    # 2 iterations resolved ops 0 and 1; the bound stopped the rest
    assert n.pending_count() == 3
    reg = metrics_registry
    assert reg.counter(
        "nemesis_membership_resolve_capped_total").value() == 1


def test_concurrent_invoke_and_generator_resolve(tmp_path):
    """The PR-9 race fix: membership_gen's next_op (interpreter thread)
    and invoke (nemesis worker) hammer _resolve/state/_pending
    concurrently without corruption — every applied op leaves the
    members file parseable and the pending list empty once settled."""
    st = FakeClusterState(tmp_path / "m.json", nodes=NODES, settle_s=0.0)
    n = membership.MembershipNemesis(st, poll_interval=0.01)
    test = {"nodes": NODES}
    gen_fn = membership.membership_gen(n)
    errors: list = []
    stop = threading.Event()

    def churn_gen():
        from jepsen_tpu.generator.simulate import default_context
        ctx = default_context({"concurrency": 2})
        while not stop.is_set():
            try:
                gen_fn(test, ctx)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    def churn_invoke(f, node):
        for _ in range(100):
            try:
                n.invoke(test, {"type": "info", "f": f, "value": node})
            except Exception as e:  # noqa: BLE001
                errors.append(e)

    threads = [threading.Thread(target=churn_gen, daemon=True)
               for _ in range(2)]
    threads += [threading.Thread(target=churn_invoke,
                                 args=("shrink", "n5"), daemon=True),
                threading.Thread(target=churn_invoke,
                                 args=("grow", "n5"), daemon=True)]
    for t in threads:
        t.start()
    for t in threads[2:]:
        t.join(timeout=30)
    stop.set()
    for t in threads[:2]:
        t.join(timeout=5)
    assert not errors
    n._resolve(test)
    assert n.pending_count() == 0
    members = json.loads((tmp_path / "m.json").read_text())
    assert set(members) <= set(NODES) and "n1" in members


def test_teardown_abandons_stuck_poll_thread(metrics_registry):
    """A node_view hung in remote I/O must not wedge teardown: the join
    is bounded, the thread abandoned, the abandonment counted."""
    release = threading.Event()

    class StuckView(membership.State):
        def fs(self):
            return {"noop"}

        def node_view(self, test, node):
            release.wait()
            return []

        def merge_views(self, test, views):
            return self

    n = membership.MembershipNemesis(StuckView(), poll_interval=0.01,
                                     teardown_join_s=0.3)
    n.setup({"nodes": ["n1"]})
    time.sleep(0.1)  # the poll thread is now stuck inside node_view
    t0 = time.monotonic()
    n.teardown({"nodes": ["n1"]})
    assert time.monotonic() - t0 < 3.0
    reg = metrics_registry
    assert reg.counter(
        "nemesis_membership_poll_abandoned_total").value() == 1
    release.set()


# ---------------------------------------------------------------------------
# Deadline interplay (the PR-4 late-heal rule for reconfigurations)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_hung_invoke_zombifies_and_entry_stays_unhealed(tmp_path,
                                                        metrics_registry):
    """The acceptance pin: a hung membership invoke cannot wedge a run —
    the op times out, the worker zombifies, and the registry entry
    remains unhealed for replay EVEN IF the hung invoke later returns
    and the op resolves."""
    import jepsen_tpu.generator as gen

    release = threading.Event()

    class HangingState(membership.State):
        def fs(self):
            return {"shrink"}

        def merge_views(self, test, views):
            return self

        def members(self):
            return set(NODES)

        def heal_spec(self, test):
            return {"mechanism": "file", "path": "/dev/null"}

        def invoke(self, test, op):
            release.wait()  # stuck mid-reconfig (SSH to a dead node)
            return {"applied": op.get("f")}

        def resolve_op(self, test, pair):
            return self  # resolves instantly once invoked

    n = membership.MembershipNemesis(HangingState(), poll_interval=0.05)
    registry = FaultRegistry(tmp_path / "faults.jsonl")
    test = {"concurrency": 1, "nodes": ["n1"], "client": None,
            "nemesis": n, "_faults": registry,
            "generator": gen.nemesis_gen(gen.Seq([
                {"type": "info", "f": "shrink", "value": "n1"}])),
            "op_timeout_s": 0.4, "drain_timeout_s": 2.0, "stall_s": 0}
    t0 = time.monotonic()
    history = _run(test)
    assert time.monotonic() - t0 < 10.0  # reaped, not wedged
    timeouts = [op for op in history
                if (op.get("error") or [None])[0] == "op-timeout"]
    assert [op["f"] for op in timeouts] == ["shrink"]
    # recorded before firing; unresolved at reap time
    assert [r["kind"] for r in registry.unhealed()] == ["membership"]
    reg = metrics_registry
    assert reg.counter("interpreter_op_timeouts_total",
                       labels=("f",)).value(f="shrink") == 1

    # the hung invoke returns LATE on the zombie thread and the op then
    # resolves — the entry must STILL stay on the books (the run already
    # published an indeterminate :info for it; only the replay may heal)
    release.set()
    time.sleep(0.5)
    n._resolve(test)
    assert [r["kind"] for r in registry.unhealed()] == ["membership"]
    registry.close()


# ---------------------------------------------------------------------------
# Generator integration + preflight
# ---------------------------------------------------------------------------

def test_polling_gen_pending_not_exhausted():
    from jepsen_tpu.generator.simulate import default_context
    box = {"ops": [None, None, {"type": "info", "f": "shrink",
                                "value": "n5"}]}

    def fn(test, ctx):
        return box["ops"].pop(0) if box["ops"] else None

    g = membership.PollingGen(fn)
    ctx = default_context({"concurrency": 1})
    from jepsen_tpu import generator as gen_mod
    op, g2 = g.op({}, ctx)
    assert op is gen_mod.PENDING and g2 is g  # None = pending, NOT done
    op, g2 = g.op({}, ctx)
    assert op is gen_mod.PENDING
    op, g2 = g.op({}, ctx)
    assert op["f"] == "shrink" and g2 is g


def test_membership_package_skipped_with_gen005(tmp_path):
    """Preflight must SKIP the membership package's generator (GEN005) —
    enumerating it would consume live nemesis state — and the skip must
    leave the State untouched."""
    from jepsen_tpu import core
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test
    from jepsen_tpu.nemesis import combined

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES, settle_s=0.0)
    pkg = combined.nemesis_package({
        "db": None, "faults": {"membership"}, "membership_state": st,
        "interval": 0.1})
    db = AtomDB()
    t = core.prepare_test(noop_test(
        db=db, client=AtomClient(db), nemesis=pkg["nemesis"],
        generator=pkg["generator"]))
    diags = pf.preflight(t)
    assert [d.code for d in diags] == ["GEN005"]
    assert st.members() == set(NODES)  # nothing consumed
    assert (tmp_path / "m.json").exists()


def test_preflight_rejects_f_outside_state_surface(tmp_path):
    """Acceptance pin: a membership package whose (data) generator emits
    an :f outside State.fs() fails preflight with NEM003."""
    import jepsen_tpu.generator as gen
    from jepsen_tpu import core
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES)
    n = membership.MembershipNemesis(st)
    db = AtomDB()
    t = core.prepare_test(noop_test(
        db=db, client=AtomClient(db), nemesis=n,
        generator=gen.nemesis_gen(gen.limit(
            2, {"type": "info", "f": "frobnicate", "value": None}))))
    diags = pf.preflight(t)
    errors = {d.code for d in diags if d.severity == "error"}
    assert "NEM003" in errors
    with pytest.raises(pf.PreflightFailed):
        pf.check(t)


def test_preflight_rejects_unhealable_membership_state():
    """Acceptance pin: a membership package whose kind is unhealable (no
    heal spec) fails preflight with NEM005 — downgradeable via
    preflight_allow."""
    from jepsen_tpu import core
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test

    class NoHeal(membership.State):
        def fs(self):
            return {"shrink"}

    n = membership.MembershipNemesis(NoHeal())
    db = AtomDB()
    t = core.prepare_test(noop_test(db=db, client=AtomClient(db),
                                    nemesis=n, generator=None))
    diags = pf.preflight(t)
    assert [(d.code, d.severity) for d in diags] == [("NEM005", "error")]
    t["preflight_allow"] = ["NEM005"]
    diags = pf.preflight(t)
    assert [(d.code, d.severity) for d in diags] == [("NEM005", "warning")]
    pf.check(t)  # downgraded: the run may proceed


def test_preflight_validates_package_knobs(tmp_path):
    from jepsen_tpu import core
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES)
    n = membership.MembershipNemesis(st, poll_interval="soon")
    db = AtomDB()
    t = core.prepare_test(noop_test(db=db, client=AtomClient(db),
                                    nemesis=n, generator=None))
    codes = {d.code for d in pf.preflight(t) if d.severity == "error"}
    assert "NEM004" in codes


def test_preflight_faketime_missing_lib(monkeypatch, tmp_path):
    """The faketime.install failure path surfaces as a structured NEM006
    diagnostic at preflight (downgradeable), not a RemoteError
    mid-run."""
    from jepsen_tpu import core, faketime
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test
    from jepsen_tpu.nemesis.time import ClockRateNemesis

    monkeypatch.setattr(faketime, "LIB_PATHS", ("/nonexistent/libfake.so",))
    db = AtomDB()
    t = core.prepare_test(noop_test(db=db, client=AtomClient(db),
                                    nemesis=ClockRateNemesis("/opt/db/db"),
                                    generator=None))
    diags = pf.preflight(t)
    assert [(d.code, d.severity) for d in diags] == [("NEM006", "error")]
    with pytest.raises(pf.PreflightFailed):
        pf.check(t)
    t["preflight_allow"] = ["NEM006"]
    pf.check(t)  # deliberate: the run may try an on-node install
    # a present library (or an explicit lib=) passes clean
    monkeypatch.setattr(faketime, "LIB_PATHS", (sys.executable,))
    assert pf.preflight(core.prepare_test(noop_test(
        db=db, client=AtomClient(db),
        nemesis=ClockRateNemesis("/opt/db/db"), generator=None))) == []


# ---------------------------------------------------------------------------
# Clock-rate: records + offline heal
# ---------------------------------------------------------------------------

@pytest.fixture()
def dummy():
    from jepsen_tpu import control
    t = {"nodes": list(NODES), "ssh": {"dummy": True}, "concurrency": 2}
    remote = control.default_remote(t)
    yield t, remote
    control.disconnect_all(t)


def test_clock_rate_classify_and_teardown_heals():
    from jepsen_tpu.nemesis.faults import (
        KINDS, TEARDOWN_HEALS, UNHEALABLE_KINDS, classify,
    )
    assert classify("start-clock-rate") == ("begin", "clock-rate")
    assert classify("stop-clock-rate") == ("end", "clock-rate")
    assert "clock-rate" in KINDS and "membership" in KINDS
    assert "clock-rate" in TEARDOWN_HEALS
    # membership is NOT teardown-healed: State.teardown does not restore
    # the member set, so unresolved reconfigs must survive to replay
    assert "membership" not in TEARDOWN_HEALS
    assert "membership" not in UNHEALABLE_KINDS


def test_clock_rate_nemesis_wraps_and_heals_offline(dummy, tmp_path):
    from jepsen_tpu.nemesis.time import ClockRateNemesis

    t, remote = dummy
    n = ClockRateNemesis("/opt/db/bin/db", restart=False)
    out = n.invoke(t, {"type": "info", "f": "start-clock-rate",
                       "value": {"binary": "/opt/db/bin/db",
                                 "rates": {"n1": 1.01, "n2": 0.99}}})
    assert out["value"]["rates"] == {"n1": 1.01, "n2": 0.99}
    joined = " ".join(str(x) for x in remote.log)
    assert "/opt/db/bin/db.real" in joined  # wrapper installed
    # offline heal: a stranded clock-rate record unwraps via the
    # binary path serialized in the record value
    registry = FaultRegistry(tmp_path / "faults.jsonl")
    registry.record("clock-rate", f="start-clock-rate",
                    value={"binary": "/opt/db/bin/db",
                           "rates": {"n1": 1.01}})
    out = replay_unhealed(t, registry)
    assert len(out["healed"]) == 1
    joined = " ".join(str(x) for x in remote.log)
    assert "mv /opt/db/bin/db.real /opt/db/bin/db" in joined
    registry.close()


def test_clock_rate_package_generator_enumerable(tmp_path):
    """The clock-rate package is data+pure-fn: preflight enumerates it
    (no GEN005) and sees the begin/end window fs."""
    from jepsen_tpu import core
    from jepsen_tpu.analysis import preflight as pf
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test
    from jepsen_tpu.nemesis import combined

    pkg = combined.nemesis_package({
        "db": None, "faults": {"clock-rate"},
        "clock_rate_binary": "/opt/db/db",
        "clock_rate_lib": "/usr/lib/faketime/libfaketime.so.1",
        "interval": 0.1})
    db = AtomDB()
    t = core.prepare_test(noop_test(
        db=db, client=AtomClient(db), nemesis=pkg["nemesis"],
        generator=pkg["generator"], preflight_ops=16))
    diags = pf.preflight(t)
    assert not [d for d in diags if d.severity == "error"], diags
    assert "GEN005" not in {d.code for d in diags}
    # the enumerated schedule alternates begin/end windows (a bare Fn
    # in the cycle would pin it on start ops forever)
    from jepsen_tpu.analysis.preflight import _enumerate
    invocations, _ = _enumerate(t)
    fs = [op.get("f") for op in invocations]
    assert "start-clock-rate" in fs and "stop-clock-rate" in fs
    first_stop = fs.index("stop-clock-rate")
    assert fs[first_stop - 1] == "start-clock-rate"


# ---------------------------------------------------------------------------
# Combined compositions: model-aware fault windows during reconfig
# ---------------------------------------------------------------------------

def test_partition_during_reconfig_window_follows_pending(tmp_path):
    from jepsen_tpu.generator.simulate import default_context
    from jepsen_tpu.nemesis import combined

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES, settle_s=600.0)
    pkg = combined.partition_during_reconfig_package({
        "db": None, "faults": {"partition-during-reconfig"},
        "membership_state": st, "interval": 0.05})
    assert pkg is not None
    fs = pkg["nemesis"].fs()
    assert {"grow", "shrink", "start-partition", "stop-partition"} <= fs
    # find the membership nemesis inside the composition
    from jepsen_tpu.analysis.preflight import _walk_nemeses
    nems: list = []
    _walk_nemeses(pkg["nemesis"], nems)
    mn = next(x for x in nems
              if isinstance(x, membership.MembershipNemesis))
    # the window generator is the second composed generator; drive the
    # package generator and watch the partition edges track pending
    # both composed children are PollingGens now; the window generator
    # is the unpaced one (the membership gen carries the interval)
    window_gen = [g for g in pkg["generator"].gens
                  if isinstance(g, membership.PollingGen)
                  and not g.interval_nanos]
    assert window_gen, "combo lost its window generator"
    wg = window_gen[0]
    ctx = default_context({"concurrency": 1})
    t = {"nodes": NODES}
    from jepsen_tpu import generator as gen_mod
    op, _ = wg.op(t, ctx)
    assert op is gen_mod.PENDING  # nothing pending: window stays shut
    mn.invoke(t, {"type": "info", "f": "shrink", "value": "n5"})
    assert mn.pending_count() == 1
    op, _ = wg.op(t, ctx)
    assert op["f"] == "start-partition"  # reconfig in flight: open
    # an OFFERED edge is not a DISPATCHED edge: until the interpreter's
    # update confirms the dispatch, the edge must keep being offered —
    # a busy nemesis thread / lost scheduling tie must not drop it
    op2, _ = wg.op(t, ctx)
    assert op2["f"] == "start-partition"
    wg.update(t, ctx, dict(op))  # the edge dispatched
    op3, _ = wg.op(t, ctx)
    assert op3 is gen_mod.PENDING  # window open now
    with mn._lock:
        mn._pending.clear()  # the reconfig resolves
    op4, _ = wg.op(t, ctx)
    assert op4["f"] == "stop-partition"  # converged: close
    op5, _ = wg.op(t, ctx)
    assert op5["f"] == "stop-partition"  # still offered until dispatched
    wg.update(t, ctx, dict(op4))
    op6, _ = wg.op(t, ctx)
    assert op6 is gen_mod.PENDING  # closed and idle


def test_polling_gen_paces_after_dispatch_even_on_fast_resolve():
    """A State that resolves before the next scheduler poll must not
    bypass the interval: pacing is armed by the dispatch UPDATE, not by
    guessing from the next fn answer."""
    from jepsen_tpu.generator.simulate import default_context

    def always_propose(test, ctx):
        return {"type": "info", "f": "shrink", "value": "n5"}

    g = membership.PollingGen(always_propose, interval=10.0)
    ctx = default_context({"concurrency": 1})
    from jepsen_tpu import generator as gen_mod
    op, _ = g.op({}, ctx)
    assert op["f"] == "shrink"
    g.update({}, ctx, dict(op))  # dispatched; op resolved instantly
    op2, _ = g.op({}, ctx)  # fn STILL proposes, but the pacing gates it
    assert op2 is gen_mod.PENDING
    assert g._not_before is not None and g._not_before > ctx.time


def test_plain_nemesis_membership_fs_not_generically_recorded(tmp_path):
    """Pre-existing suites (faunadb topology, rethinkdb reconfigure) use
    membership-flavored :f names with PLAIN nemeses that keep no model:
    the interpreter's generic snapshot must not book permanently-
    unhealed membership rows for them (SELF_RECORDED_ONLY)."""
    import jepsen_tpu.generator as gen

    class PlainReconfigurer:
        def fs(self):
            return {"reconfigure", "add-node"}

        def invoke(self, test, op):
            return {**op, "type": "info", "value": "done"}

    registry = FaultRegistry(tmp_path / "faults.jsonl")
    test = {"concurrency": 1, "nodes": ["n1"], "client": None,
            "nemesis": PlainReconfigurer(), "_faults": registry,
            "generator": gen.nemesis_gen(gen.Seq([
                {"type": "info", "f": "reconfigure", "value": None},
                {"type": "info", "f": "add-node", "value": "n9"}])),
            "stall_s": 0}
    _run(test)
    assert registry.unhealed() == []
    assert (tmp_path / "faults.jsonl").read_text() == ""
    registry.close()


def test_etcd_remove_node_resolves_despite_stale_dead_view(monkeypatch):
    """The removed node's poll only fails after its process is killed,
    so the nemesis keeps its last good view — which still lists the
    node. Resolution must count only the survivors' views."""
    from jepsen_tpu.suites import etcd

    api = FakeMembersAPI(["n1", "n2", "n3"])
    monkeypatch.setattr(etcd, "_members_request", api)
    st = etcd.EtcdMembershipState()
    t = {"nodes": ["n1", "n2", "n3"]}
    full = st.node_view(t, "n1")
    st.merge_views(t, {n: full for n in ["n1", "n2", "n3"]})
    op = {"type": "info", "f": "remove-node", "value": "n3"}
    val = st.invoke(t, op)
    # survivors converge; n3's view is STALE (still the full set)
    survivor_view = sorted(api.members)
    st.merge_views(t, {"n1": survivor_view, "n2": survivor_view,
                       "n3": full})
    assert st.resolve_op(t, (op, val)) is st


def test_polling_gen_ignores_prior_completion():
    """Nemesis events arrive twice per op (dispatch with the op's value,
    completion with a rewritten one): a previous dispatch's completion
    must not pass for a dispatch of the CURRENT offer and burn a
    pacing window."""
    from jepsen_tpu.generator.simulate import default_context

    def always_propose(test, ctx):
        return {"type": "info", "f": "shrink", "value": "n5"}

    g = membership.PollingGen(always_propose, interval=10.0)
    ctx = default_context({"concurrency": 1})
    op, _ = g.op({}, ctx)
    assert op["f"] == "shrink"
    # the PREVIOUS op's completion: same f, rewritten value
    g.update({}, ctx, {**op, "value": {"action": "shrink", "at": 1.0}})
    assert g._offered is not None  # still awaiting OUR dispatch
    assert g._not_before is None   # no pacing burned
    g.update({}, ctx, dict(op))    # the real dispatch event
    assert g._offered is None and g._not_before is not None


def test_both_during_reconfig_combos_rejected(tmp_path):
    from jepsen_tpu.nemesis import combined

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES)
    with pytest.raises(ValueError, match="cannot be combined"):
        combined.nemesis_package({
            "db": None, "membership_state": st,
            "clock_rate_binary": "/opt/db/db",
            "faults": {"partition-during-reconfig",
                       "clock-rate-during-reconfig"}})


def test_preflight_inert_closure_types_still_enumerable(tmp_path):
    """Closures over immutable value objects (Path, datetime, ...) must
    keep full enumeration coverage — only live-state instances trigger
    the GEN005 skip."""
    import datetime
    from pathlib import Path

    from jepsen_tpu.analysis.preflight import _stateful_reason

    p, d = Path("/tmp/x"), datetime.date(2026, 1, 1)

    def data_gen(test, ctx):
        return {"f": "write", "value": f"{p}-{d}"}

    import jepsen_tpu.generator as gen
    assert _stateful_reason(gen.Fn(data_gen)) is None


def test_partition_combo_subsumes_standalone_partition(tmp_path):
    """faults={'partition','partition-during-reconfig'} must build ONE
    PartitionNemesis: a second one's staggered stop-partition would
    heal mid-reconfig and its start events would flip the combo's
    window state."""
    from jepsen_tpu.analysis.preflight import _walk_nemeses
    from jepsen_tpu.nemesis import combined
    from jepsen_tpu.nemesis.combined import PartitionNemesis

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES)
    pkg = combined.nemesis_package({
        "db": None, "membership_state": st,
        "faults": {"partition", "partition-during-reconfig"}})
    nems: list = []
    _walk_nemeses(pkg["nemesis"], nems)
    partitions = [n for n in nems if isinstance(n, PartitionNemesis)]
    assert len(partitions) == 1


def test_current_op_reaped_propagates_through_timeout_helper():
    """A Timeout nemesis wrapper runs the inner invoke on a helper
    thread; current_op_reaped() must answer for the logical op, not
    the physical thread."""
    from jepsen_tpu.generator import interpreter
    from jepsen_tpu.utils import timeout as timeout_fn

    ev = threading.Event()
    interpreter._worker_tls.zombied = ev
    try:
        assert timeout_fn(1000, None,
                          interpreter.current_op_reaped) is False
        ev.set()
        assert timeout_fn(1000, None,
                          interpreter.current_op_reaped) is True
    finally:
        del interpreter._worker_tls.zombied


def test_preflight_nested_and_builtin_closures_enumerable():
    """Nested immutable containers, module builtins, and partials over
    pure fns stay enumerable; instance-bound builtins (random.random is
    a bound method of the hidden Random) stay stateful."""
    import functools
    import math
    import random

    import jepsen_tpu.generator as gen
    from jepsen_tpu.analysis.preflight import _stateful_reason

    pairs = (("w", 1), ("r", None))
    sqrt = math.sqrt
    half = functools.partial(round, ndigits=2)

    def data_gen(test, ctx):
        return {"f": pairs[0][0], "value": half(sqrt(4.0))}

    assert _stateful_reason(gen.Fn(data_gen)) is None

    rand = random.random

    def rng_gen(test, ctx):
        return {"f": "write", "value": rand()}

    assert "bound to a Random" in _stateful_reason(gen.Fn(rng_gen))


def test_requested_but_unwired_fault_raises():
    """A fault the user NAMED must never silently no-op: membership /
    clock-rate / combo names without their wiring fail loudly at
    package-build time (cli maps ValueError to bad-args)."""
    from jepsen_tpu.nemesis import combined

    for faults in ({"membership"}, {"clock-rate"},
                   {"partition-during-reconfig"},
                   {"clock-rate-during-reconfig"}):
        with pytest.raises(ValueError, match="requested"):
            combined.nemesis_package({"db": None, "faults": faults})


def test_clock_rate_during_reconfig_package_builds(tmp_path):
    from jepsen_tpu.nemesis import combined

    st = FakeClusterState(tmp_path / "m.json", nodes=NODES)
    pkg = combined.nemesis_package({
        "db": None, "faults": {"clock-rate-during-reconfig"},
        "membership_state": st, "clock_rate_binary": "/opt/db/db",
        "interval": 0.05})
    fs = pkg["nemesis"].fs()
    assert {"grow", "shrink", "start-clock-rate", "stop-clock-rate"} <= fs


# ---------------------------------------------------------------------------
# Etcd membership state (stubbed members API)
# ---------------------------------------------------------------------------

class FakeMembersAPI:
    """A v2 /members transport double over a dict cluster."""

    def __init__(self, names):
        self.members = {n: f"id-{n}" for n in names}
        self.calls: list = []

    def __call__(self, node, method="GET", body=None, member_id=None,
                 timeout_s=5.0):
        self.calls.append((node, method, body, member_id))
        if method == "GET":
            return {"members": [{"id": i, "name": n,
                                 "peerURLs": [f"http://{n}:2380"]}
                                for n, i in sorted(self.members.items())]}
        if method == "POST":
            name = (body or {}).get("name")
            if name in self.members:
                raise urllib.error.HTTPError("u", 409, "conflict", {}, None)
            self.members[name] = f"id-{name}"
            return {}
        if method == "DELETE":
            name = next((n for n, i in self.members.items()
                         if i == member_id), None)
            if name is None:
                raise urllib.error.HTTPError("u", 404, "gone", {}, None)
            del self.members[name]
            return {}
        raise AssertionError(method)


def test_etcd_membership_state_cycle(monkeypatch):
    from jepsen_tpu.suites import etcd

    api = FakeMembersAPI(["n1", "n2", "n3", "n4", "n5"])
    monkeypatch.setattr(etcd, "_members_request", api)
    st = etcd.EtcdMembershipState()
    t = {"nodes": NODES}
    view = st.node_view(t, "n1")
    assert view == sorted(NODES)
    st.merge_views(t, {n: view for n in NODES})
    assert st.members() == set(NODES)
    op = st.op(t)
    assert op["f"] == "remove-node" and op["value"] == "n5"
    val = st.invoke(t, op)
    assert val["expect_present"] is False
    assert "n5" not in api.members
    # unresolved until the polled views agree the member is gone
    assert st.resolve_op(t, (op, val)) is None
    new_view = sorted(api.members)
    st.merge_views(t, {n: new_view for n in new_view})
    assert st.resolve_op(t, (op, val)) is st
    # with a member missing, the model proposes re-adding it
    op2 = st.op(t)
    assert (op2["f"], op2["value"]) == ("add-node", "n5")
    st.invoke(t, op2)
    assert "n5" in api.members


def test_etcd_restore_members_diffs_both_ways(monkeypatch):
    from jepsen_tpu.suites import etcd

    # n3 was removed (stranded shrink) and n9 half-added
    api = FakeMembersAPI(["n1", "n2", "n9"])
    monkeypatch.setattr(etcd, "_members_request", api)
    row = {"id": 7, "kind": "membership",
           "value": {"pre_members": ["n1", "n2", "n3"],
                     "heal": {"mechanism": "import",
                              "module": "jepsen_tpu.suites.etcd",
                              "fn": "restore_members"}}}
    membership.heal_record({"nodes": NODES}, row)
    assert sorted(api.members) == ["n1", "n2", "n3"]
    membership.heal_record({"nodes": NODES}, row)  # idempotent
    assert sorted(api.members) == ["n1", "n2", "n3"]


@pytest.mark.slow
def test_etcd_fake_mode_membership_end_to_end():
    """--fault membership runs the full fake suite lifecycle: the
    durable fake cluster reconfigures, every op lands (and heals) in
    the registry, and the run ends clean."""
    from jepsen_tpu.suites.etcd import etcd_test
    from tests.conftest import run_fake

    res = run_fake(etcd_test, faults={"membership"}, nemesis_interval=0.2,
                   membership_settle_s=0.0, time_limit=2.0)
    hist = res.get("history") or []
    fs = {op.get("f") for op in hist if op.get("process") == "nemesis"}
    assert fs & {"shrink", "grow"}, "membership nemesis never fired"
    assert (res.get("results") or {}).get("valid?") is True


# ---------------------------------------------------------------------------
# join_noisy bounded mode
# ---------------------------------------------------------------------------

def test_join_noisy_bounded_abandons():
    from jepsen_tpu.utils import join_noisy

    release = threading.Event()
    t = threading.Thread(target=release.wait, daemon=True)
    t.start()
    t0 = time.monotonic()
    assert join_noisy(t, "stuck thread", heartbeat_s=0.1,
                      max_wait_s=0.3) is False
    assert time.monotonic() - t0 < 2.0
    release.set()
    assert join_noisy(t, "released thread", heartbeat_s=0.1,
                      max_wait_s=5.0) is True


# ---------------------------------------------------------------------------
# Chaos: SIGKILL mid-shrink -> analyze --recover -> cli heal (slow lane)
# ---------------------------------------------------------------------------

def _cli_main():
    from jepsen_tpu import cli
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.fakes import noop_test

    def build(opts):
        return cli.test_opts_to_test(
            opts, noop_test(checker=linearizable(accelerator="cpu")))

    return cli.single_test_cmd(build)


@pytest.mark.slow
@pytest.mark.chaos
def test_sigkill_mid_shrink_recover_and_heal(tmp_path):
    """The tentpole acceptance scenario end to end: SIGKILL lands while
    a shrink is unresolved; the durable record holds the pre-op member
    set; ``analyze --recover`` yields a valid-incomplete verdict with
    the membership fault window visible in the registry-derived fault
    bands; ``cli heal`` restores the recorded member set exactly once
    (a second heal is a no-op)."""
    members_path = tmp_path / "cluster-members.json"
    store = tmp_path / "store"
    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "membership_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, worker, str(store), str(members_path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    deadline = time.monotonic() + 120
    run_dir = None
    try:
        while time.monotonic() < deadline:
            regs = list(store.glob("noop/*/faults.jsonl"))
            wals = list(store.glob("noop/*/history.wal.jsonl"))
            if regs and wals and "shrink" in regs[0].read_text() \
                    and wals[0].read_text().count("\n") >= 20:
                run_dir = regs[0].parent
                break
            if proc.poll() is not None:
                out = proc.stdout.read()
                pytest.fail(f"worker exited early ({proc.returncode}):\n"
                            f"{out[-4000:]}")
            time.sleep(0.05)
        assert run_dir is not None, "shrink never recorded"
        os.kill(proc.pid, signal.SIGKILL)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(timeout=30)

    # the shrink applied (members file shrunk) but never resolved: the
    # registry holds the unhealed membership record with the pre-op set
    assert json.loads(members_path.read_text()) == ["n1", "n2", "n3", "n4"]
    freg = FaultRegistry(run_dir / "faults.jsonl")
    unhealed = freg.unhealed()
    freg.close()
    assert [r["kind"] for r in unhealed] == ["membership"]
    assert unhealed[0]["value"]["pre_members"] == sorted(NODES)

    # analyze --recover: valid-but-incomplete verdict over the WAL
    main = _cli_main()
    rc = main(["analyze", "--recover", "--store-dir", str(store),
               "--no-ssh", "--accelerator", "cpu"])
    assert rc == 0
    results = json.loads((run_dir / "results.json").read_text())
    assert results["valid?"] is True and results["incomplete"] is True

    # the unhealed membership row is visible in the registry-derived
    # fault bands (the source the explain timeline + perf-plot shading
    # draw from): an open window, in-registry, not yet healed
    from jepsen_tpu import store as store_mod
    from jepsen_tpu.checker.perf_plots import registry_fault_windows
    name, ts = "noop", run_dir.name
    stored = store_mod.load_test(name, ts, str(store))
    stored["store_dir"] = str(store)
    history = store_mod.load_history(name, ts, str(store))
    windows = [w for w in registry_fault_windows(stored, history)
               if w["kind"] == "membership"]
    assert windows and windows[0]["in_registry"] is True
    assert windows[0]["healed"] is False
    assert windows[0]["end_time"] is None  # never closed in-history

    # cli heal: restores the recorded pre-op member set, exactly once
    rc = main(["heal", str(run_dir)])
    assert rc == 0
    assert json.loads(members_path.read_text()) == sorted(NODES)
    freg = FaultRegistry(run_dir / "faults.jsonl")
    assert freg.unhealed() == []
    freg.close()
    # after the heal, the fault band flips to healed-via-replay
    windows = [w for w in registry_fault_windows(stored, history)
               if w["kind"] == "membership"]
    assert windows and windows[0]["healed"] is True
    assert windows[0]["via"] == "replay"
    # exactly once: a second heal is a no-op even if the cluster moved on
    members_path.write_text(json.dumps(["sentinel"]))
    rc = main(["heal", str(run_dir)])
    assert rc == 0
    assert json.loads(members_path.read_text()) == ["sentinel"]
