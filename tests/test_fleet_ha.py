"""Fleet-HA tier: leased checking with fencing, receiver failover +
honest backpressure, ENOSPC park-and-retry, and the self-chaos harness
(doc/robustness.md "Fleet HA").

Covers the ISSUE-19 acceptance surface:

* lease protocol: claim / renew / TTL expiry / takeover, the read-back
  race electing exactly one claimant, and the stale-epoch regression
  pins (a fenced `RunTracker` status write and a fenced
  `CheckpointStore.save` both drop, never land);
* two live daemons over one store: one holder, one waiter, a takeover
  past the TTL, the deposed host fencing itself out;
* Journal / FaultRegistry / ingest ENOSPC: bounded in-memory park, a
  truncate rollback of partially-landed bytes, drain on the next
  append — ENOSPC is transient weather, any other OSError still
  permanently self-disables the journal;
* receiver shedding: 429 + Retry-After on disk headroom, the pool's
  aggregate-lag pressure hook, and an injected ENOSPC park;
* shipper HA: endpoint failover with resync counters, a 429's
  Retry-After obeyed with the un-absorbed bytes re-polled, the sealed
  path when the receiver already holds the final;
* finals race, both orders: exactly one digest-valid history.jsonl,
  the loser told with 409, the seal surviving a receiver restart;
* preflight KNB rows + env twins for the HA knobs, and the
  `fleet_receivers` URL-list validation;
* the fleet-chaos harness end to end (slow lane, `-m fleet_chaos`).
"""
from __future__ import annotations

import errno
import hashlib
import json
import random
import time
import urllib.error
import urllib.request

import pytest

pytestmark = pytest.mark.fleet


def _register_history(n, seed=7, n_procs=3):
    from __graft_entry__ import _register_history as gen
    return gen(n, n_procs=n_procs, seed=seed, n_values=5)


def _write_wal(run_dir, ops, complete=False):
    from jepsen_tpu.journal import Journal
    run_dir.mkdir(parents=True, exist_ok=True)
    j = Journal(run_dir / "history.wal.jsonl", fsync_interval_s=-1)
    for op in ops:
        j.append(op)
    j.close()
    if complete:
        with open(run_dir / "history.jsonl", "w") as f:
            for op in ops:
                f.write(json.dumps(op) + "\n")


def _ctr(reg, name, **labels):
    total = 0
    for row in reg.snapshot():
        if row.get("name") != name:
            continue
        got = row.get("labels", {})
        if any(got.get(k) != v for k, v in labels.items()):
            continue
        total += row.get("value", 0)
    return total


def _lease_store(root, host, clock, ttl=10.0):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.lease import LeaseStore
    return LeaseStore(root, host_id=host, ttl_s=ttl,
                      registry=telemetry.Registry(),
                      time_fn=lambda: clock[0])


# ---------------------------------------------------------------------------
# lease protocol: claim / renew / expiry / takeover / fencing
# ---------------------------------------------------------------------------

def test_lease_acquire_renew_release(tmp_path):
    clock = [1000.0]
    a = _lease_store(tmp_path, "a", clock)
    rd = tmp_path / "demo" / "t0"
    rd.mkdir(parents=True)
    epoch = a.acquire(rd)
    assert epoch == 1
    assert a.held == {str(rd): 1}
    doc = a.read(rd)
    assert doc["host"] == "a" and doc["epoch"] == 1
    assert _ctr(a.registry, "fleet_lease_acquired_total") == 1

    clock[0] += 5.0
    assert a.renew(rd, epoch)
    assert a.read(rd)["renewed_at"] == clock[0]
    # renewal is a heartbeat, never a takeover: the epoch is stable
    assert a.read(rd)["epoch"] == 1
    assert _ctr(a.registry, "fleet_lease_renewals_total") == 1
    assert a.guard(rd, epoch)
    assert _ctr(a.registry, "fleet_lease_fenced_writes_total") == 0

    a.release(rd, epoch)
    assert a.read(rd) is None
    assert a.held == {}


def test_lease_foreign_holder_blocks_until_ttl(tmp_path):
    """A live foreign lease blocks adoption; past the TTL the waiter
    takes over at epoch+1, and the deposed host's renew/guard both say
    no (fencing) with the loss counted."""
    clock = [1000.0]
    a = _lease_store(tmp_path, "a", clock, ttl=10.0)
    b = _lease_store(tmp_path, "b", clock, ttl=10.0)
    rd = tmp_path / "demo" / "t0"
    rd.mkdir(parents=True)
    assert a.acquire(rd) == 1
    assert b.acquire(rd) is None  # a is live: no takeover

    clock[0] += 10.1  # a's lease expires un-renewed
    assert b.acquire(rd) == 2  # takeover bumps the fencing epoch
    assert b.read(rd)["host"] == "b"

    assert not a.renew(rd, 1)
    assert _ctr(a.registry, "fleet_lease_lost_total") == 1
    assert not a.guard(rd, 1)
    assert _ctr(a.registry, "fleet_lease_fenced_writes_total") == 1
    # the deposed host must not unlink its successor's lease
    a.release(rd, 1)
    assert b.read(rd)["host"] == "b"
    assert b.guard(rd, 2)


def test_lease_read_back_race_elects_one_claimant(tmp_path):
    """Two hosts racing an expired lease both write; last-writer-wins
    plus the read-back verify elects exactly one, and the loser reports
    the claim failed (it never believes it holds the run)."""
    clock = [1000.0]
    a = _lease_store(tmp_path, "a", clock)
    b = _lease_store(tmp_path, "b", clock)
    rd = tmp_path / "demo" / "t0"
    rd.mkdir(parents=True)

    real_write = a._write

    def write_then_lose(run_dir, epoch, acquired_at):
        # a's write lands, then b — which read "free" at the same
        # instant — overwrites it before a's read-back; the on-disk
        # file is the only truth
        out = real_write(run_dir, epoch, acquired_at)
        b._write(run_dir, epoch, acquired_at)
        return out

    a._write = write_then_lose
    assert a.acquire(rd) is None
    assert str(rd) not in a.held
    assert b.read(rd)["host"] == "b"


def test_lease_garbled_file_is_adoptable(tmp_path):
    clock = [1000.0]
    a = _lease_store(tmp_path, "a", clock)
    rd = tmp_path / "demo" / "t0"
    rd.mkdir(parents=True)
    (rd / "check.lease").write_text("{torn garbage")
    assert a.acquire(rd) == 1  # a torn lease never wedges the run


# ---------------------------------------------------------------------------
# stale-epoch regression pins: fenced writes DROP
# ---------------------------------------------------------------------------

def test_tracker_status_write_fenced(tmp_path):
    """The regression pin for the double-publish bug leasing exists to
    prevent: a RunTracker whose fence says no must drop the status
    write entirely, not land a stale document."""
    from jepsen_tpu.live.daemon import RunTracker
    rd = tmp_path / "demo" / "t0"
    _write_wal(rd, _register_history(12))
    tr = RunTracker(rd, accelerator="cpu", fence=lambda: False,
                    lease={"host": "a", "epoch": 1})
    tr.write_status(tr.status(lag_budget_ops=1000.0))
    assert not (rd / "live-status.json").exists()
    assert tr.fenced


def test_tracker_snapshot_fenced(tmp_path):
    from jepsen_tpu.live.daemon import RunTracker
    rd = tmp_path / "demo" / "t0"
    _write_wal(rd, _register_history(12))
    tr = RunTracker(rd, accelerator="cpu", fence=lambda: False)
    tr.unsupported = True  # snapshotable without a session
    tr.ops_absorbed = 5
    tr._last_snapshot = -1e9
    assert not tr.maybe_snapshot()
    assert tr.fenced
    assert not tr._ckpt_path.exists()


def test_checkpoint_store_guard_fences(tmp_path):
    from jepsen_tpu.checker.checkpoint import CheckpointStore
    p = tmp_path / "check.ckpt"
    fenced = CheckpointStore(p, interval_s=0.0, guard=lambda: False)
    assert not fenced.save({"carry": 1})
    assert fenced.fenced and not p.exists()

    held = CheckpointStore(p, interval_s=0.0, guard=lambda: True)
    assert held.save({"carry": 1})
    assert p.exists() and not held.fenced


def test_two_daemons_one_store_takeover(tmp_path):
    """The leased-checking e2e: daemon A admits and leases a run;
    daemon B over the same store stays out while A's lease is live,
    adopts at epoch 2 past the TTL, and A's next poll fences itself
    out (lease lost, tracker dropped, no stale write)."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.live.daemon import LiveDaemon
    clock = [1000.0]
    ls_a = _lease_store(tmp_path, "a", clock, ttl=30.0)
    ls_b = _lease_store(tmp_path, "b", clock, ttl=30.0)
    rd = tmp_path / "demo" / "t0"
    _write_wal(rd, _register_history(24))  # no final: stays tracked

    da = LiveDaemon(store_root=tmp_path, accelerator="cpu",
                    registry=telemetry.Registry(), lease_store=ls_a)
    db = LiveDaemon(store_root=tmp_path, accelerator="cpu",
                    registry=telemetry.Registry(), lease_store=ls_b)
    try:
        da.poll_once()
        assert ls_a.read(rd)["host"] == "a"
        db.poll_once()
        assert not db.trackers  # leased elsewhere: not admitted
        assert ls_a.read(rd)["epoch"] == 1

        clock[0] += 31.0  # a stalls past its TTL (SIGSTOP, GC, NFS...)
        db.poll_once()
        doc = ls_b.read(rd)
        assert doc["host"] == "b" and doc["epoch"] == 2
        status = json.loads((rd / "live-status.json").read_text())
        assert status["lease"] == {"host": "b", "epoch": 2}

        da.poll_once()  # the deposed host discovers it was deposed
        assert not da.trackers
        assert _ctr(ls_a.registry, "fleet_lease_lost_total") == 1
        # b's status survived a's fenced poll untouched
        status = json.loads((rd / "live-status.json").read_text())
        assert status["lease"]["host"] == "b"
    finally:
        da.stop()
        db.stop()


def test_daemon_releases_lease_and_fires_on_final(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.live.daemon import LiveDaemon
    clock = [1000.0]
    ls = _lease_store(tmp_path, "a", clock, ttl=30.0)
    rd = tmp_path / "demo" / "t0"
    ops = _register_history(24)
    _write_wal(rd, ops)
    finals = []
    d = LiveDaemon(store_root=tmp_path, accelerator="cpu",
                   registry=telemetry.Registry(), lease_store=ls,
                   on_final=lambda tr, res: finals.append(
                       (tr.label, tr.lease, res.get("valid?"))))
    try:
        d.poll_once()  # admit + lease while the run is still live
        assert ls.read(rd)["host"] == "a"
        with open(rd / "history.jsonl", "w") as f:
            for op in ops:
                f.write(json.dumps(op) + "\n")
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and not finals:
            d.poll_once()
    finally:
        d.stop()
    assert finals == [("demo/t0", {"host": "a", "epoch": 1}, True)]
    assert ls.read(rd) is None  # released at finalize
    assert ls.held == {}


def test_standby_settles_adopted_runs_and_skips_settled_ones(tmp_path):
    """Failover: daemon A settles one run and leases a live one, then
    stalls past its TTL. Standby B adopts the live run and settles it,
    and never finalizes the run A had already settled."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.live.daemon import LiveDaemon
    clock = [1000.0]
    ls_a = _lease_store(tmp_path, "a", clock, ttl=30.0)
    ls_b = _lease_store(tmp_path, "b", clock, ttl=30.0)
    settled, live = tmp_path / "demo" / "p0", tmp_path / "demo" / "l0"
    ops_settled, ops_live = _register_history(24), _register_history(24, 8)
    _write_wal(settled, ops_settled)
    _write_wal(live, ops_live)
    finals = []

    def record(tr, res):
        finals.append((tr.label, dict(tr.lease), res.get("valid?")))

    da = LiveDaemon(store_root=tmp_path, accelerator="cpu",
                    registry=telemetry.Registry(), lease_store=ls_a,
                    on_final=record)
    db = LiveDaemon(store_root=tmp_path, accelerator="cpu",
                    registry=telemetry.Registry(), lease_store=ls_b,
                    on_final=record)

    def settle(daemon, run_dir, ops):
        with open(run_dir / "history.jsonl", "w") as f:
            for op in ops:
                f.write(json.dumps(op) + "\n")
        deadline = time.monotonic() + 30
        n = len(finals)
        while time.monotonic() < deadline and len(finals) == n:
            daemon.poll_once()

    try:
        da.poll_once()  # a leases both runs while they are live
        assert ls_a.read(settled)["host"] == ls_a.read(live)["host"] == "a"
        settle(da, settled, ops_settled)
        assert finals == [("demo/p0", {"host": "a", "epoch": 1}, True)]

        clock[0] += 31.0  # a stalls past its TTL
        db.poll_once()
        assert ls_b.read(live)["host"] == "b"
        assert [tr.label for tr in db.trackers.values()] == ["demo/l0"]
        settle(db, live, ops_live)
        for _ in range(3):
            db.poll_once()
    finally:
        da.stop()
        db.stop()
    assert finals == [("demo/p0", {"host": "a", "epoch": 1}, True),
                      ("demo/l0", {"host": "b", "epoch": 2}, True)]


# ---------------------------------------------------------------------------
# ENOSPC: Journal park/drain + truncate rollback, FaultRegistry park
# ---------------------------------------------------------------------------

class _FailingFile:
    """A write handle that fails every write with ``err`` — optionally
    leaking ``partial`` bytes into the real file first, the way a real
    disk-full write can land a prefix before dying."""

    def __init__(self, err=errno.ENOSPC, leak_path=None):
        self.err = err
        self.leak_path = leak_path
        self.closed = False

    def write(self, data):
        if self.leak_path is not None:
            with open(self.leak_path, "ab") as f:
                f.write(data[: max(1, len(data) // 2)])
        raise OSError(self.err, "injected write failure")

    def flush(self):
        pass

    def close(self):
        self.closed = True


def test_journal_enospc_parks_then_drains(tmp_path):
    from jepsen_tpu.journal import Journal, read_jsonl_tolerant
    p = tmp_path / "history.wal.jsonl"
    j = Journal(p, fsync_interval_s=-1)
    j.append({"i": 0})
    good = p.read_bytes()
    real = j._f
    j._f = _FailingFile()  # the disk fills
    j.append({"i": 1})
    real.close()
    assert j.appended == 1  # parked, not counted as landed
    assert len(j.parked) == 1
    assert p.read_bytes() == good  # nothing half-landed
    j.append({"i": 2})  # next append re-probes: reopen + drain backlog
    assert j.appended == 3 and j.parked == []
    j.close()
    rows, truncated = read_jsonl_tolerant(p)
    assert [r["i"] for r in rows] == [0, 1, 2]
    assert not truncated


def test_journal_enospc_rolls_back_partial_bytes(tmp_path):
    """A failed write that LANDED a prefix is truncated back to the
    last known-good offset — a torn half-line must never sit in the
    WAL waiting to corrupt a resume token."""
    from jepsen_tpu.journal import Journal, read_jsonl_tolerant
    p = tmp_path / "history.wal.jsonl"
    j = Journal(p, fsync_interval_s=-1)
    j.append({"i": 0})
    good = p.read_bytes()
    real = j._f
    j._f = _FailingFile(leak_path=p)
    j.append({"i": 1})
    real.close()
    assert p.read_bytes() == good  # the leaked prefix was truncated
    j.append({"i": 2})
    j.close()
    rows, _ = read_jsonl_tolerant(p)
    assert [r["i"] for r in rows] == [0, 1, 2]
    assert p.read_bytes().startswith(good)


def test_journal_enospc_park_is_bounded(tmp_path, monkeypatch):
    from jepsen_tpu import journal as journal_mod
    monkeypatch.setattr(journal_mod, "ENOSPC_PARK_MAX_LINES", 3)
    j = journal_mod.Journal(tmp_path / "w.jsonl", fsync_interval_s=-1)
    for i in range(5):
        j._park([json.dumps({"i": i}).encode() + b"\n"])
    assert len(j.parked) == 3
    assert j.parked_dropped == 2
    # oldest dropped first: the tail of the run is the valuable part
    assert [json.loads(line)["i"] for line in j.parked] == [2, 3, 4]
    j.close()


def test_journal_non_enospc_still_self_disables(tmp_path):
    from jepsen_tpu.journal import Journal
    p = tmp_path / "w.jsonl"
    j = Journal(p, fsync_interval_s=-1)
    j.append({"i": 0})
    real = j._f
    j._f = _FailingFile(err=errno.EIO)
    j.append({"i": 1})  # unknown I/O fault: permanent self-disable
    real.close()
    assert j._f.closed and not j._parked_closed
    before = p.read_bytes()
    j.append({"i": 2})  # no-op: the journal is done
    assert j.appended == 1
    assert p.read_bytes() == before
    j.close()


def test_fault_registry_enospc_parks_then_drains(tmp_path):
    from jepsen_tpu.nemesis.faults import FaultRegistry, load_rows
    p = tmp_path / "faults.jsonl"
    reg = FaultRegistry(p)
    fid0 = reg.record("net", f="start-partition")
    real = reg._f
    reg._f = _FailingFile()
    fid1 = reg.record("clock", f="clock-skew")  # parked, id still minted
    assert len(reg._parked) == 1 and reg._dirty_tail
    reg._f = real
    fid2 = reg.record("net", f="start-partition")  # drains the backlog
    assert reg._parked == [] and not reg._dirty_tail
    reg.close()
    rows = load_rows(p)
    recorded = {r["id"] for r in rows if r.get("op") == "inject"}
    assert recorded == {fid0, fid1, fid2}


# ---------------------------------------------------------------------------
# receiver backpressure: 429 + Retry-After, ENOSPC park + rollback
# ---------------------------------------------------------------------------

def _post_chunk(port, key, body, offset=0, prefix_sha=None,
                chunk_sha=None):
    if prefix_sha is None:
        prefix_sha = hashlib.sha256().hexdigest()
    if chunk_sha is None:
        chunk_sha = hashlib.sha256(body).hexdigest()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/wal/{key}", data=body,
        headers={"X-Jepsen-Offset": str(offset),
                 "X-Jepsen-Prefix-Sha": prefix_sha,
                 "X-Jepsen-Chunk-Sha": chunk_sha}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.status, resp.read(), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers or {})


def test_receiver_sheds_on_disk_headroom(tmp_path, monkeypatch):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet import ingest as ingest_mod
    monkeypatch.setattr(ingest_mod, "disk_free_mb", lambda path: 1.0)
    reg = telemetry.Registry()
    srv = ingest_mod.IngestServer(tmp_path, port=0, registry=reg,
                                  disk_headroom_mb=64.0)
    srv.start()
    try:
        body = b'{"i": 0}\n'
        status, resp, headers = _post_chunk(srv.port, "demo/t0", body)
        assert status == 429
        assert float(headers["Retry-After"]) > 0
        verdict = json.loads(resp)
        assert verdict["shed"] == "headroom"
        assert not (tmp_path / "demo" / "t0"
                    / "history.wal.jsonl").exists()
        assert _ctr(reg, "fleet_ingest_shed_total",
                    reason="headroom") == 1
    finally:
        srv.stop()


def test_receiver_sheds_on_pressure_hook(tmp_path):
    """The pool's aggregate-lag hook: non-None = shed, and the wait it
    returns is the Retry-After the shipper is told verbatim."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ingest import IngestServer
    wait = {"s": 2.5}
    srv = IngestServer(tmp_path, port=0,
                       registry=telemetry.Registry(),
                       pressure=lambda: wait["s"])
    srv.start()
    try:
        status, resp, headers = _post_chunk(srv.port, "demo/t0",
                                            b'{"i": 0}\n')
        assert status == 429
        assert json.loads(resp) == {"shed": "lag", "retry_after": 2.5}
        assert abs(float(headers["Retry-After"]) - 2.5) < 1e-6

        wait["s"] = None  # pool caught up: chunks land again
        status, _, _ = _post_chunk(srv.port, "demo/t0", b'{"i": 0}\n')
        assert status == 204
    finally:
        srv.stop()


def test_receiver_enospc_parks_and_rolls_back(tmp_path, monkeypatch):
    """An append dying on ENOSPC sheds the chunk, truncates any
    partially-landed bytes back to the advertised cursor, and parks
    the run; the park lapses and the SAME bytes then land whole."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet import ingest as ingest_mod
    monkeypatch.setattr(ingest_mod, "ENOSPC_PARK_S", 0.05)
    reg = telemetry.Registry()
    fail = {"on": False}
    wal = tmp_path / "demo" / "t0" / "history.wal.jsonl"

    def fault_hook(key, body):
        if fail["on"]:
            # leak a partial prefix the way a real disk-full can
            wal.parent.mkdir(parents=True, exist_ok=True)
            with open(wal, "ab") as f:
                f.write(body[: len(body) // 2])
            raise OSError(errno.ENOSPC, "injected disk full")

    srv = ingest_mod.IngestServer(tmp_path, port=0, registry=reg,
                                  fault_hook=fault_hook)
    srv.start()
    try:
        first = b'{"i": 0}\n'
        assert _post_chunk(srv.port, "demo/t0", first)[0] == 204

        fail["on"] = True
        sha0 = hashlib.sha256(first).hexdigest()
        second = b'{"i": 1}\n'
        sha1 = hashlib.sha256(first + second).hexdigest()
        status, resp, _ = _post_chunk(srv.port, "demo/t0", second,
                                      offset=len(first),
                                      prefix_sha=sha0, chunk_sha=sha1)
        assert status == 429
        assert json.loads(resp)["shed"] == "enospc"
        assert wal.read_bytes() == first  # partial bytes rolled back
        # parked: an immediate retry bounces without touching the disk
        status, resp, _ = _post_chunk(srv.port, "demo/t0", second,
                                      offset=len(first),
                                      prefix_sha=sha0, chunk_sha=sha1)
        assert status == 429

        fail["on"] = False
        time.sleep(0.08)  # the park lapses; the next append re-probes
        status, _, _ = _post_chunk(srv.port, "demo/t0", second,
                                   offset=len(first),
                                   prefix_sha=sha0, chunk_sha=sha1)
        assert status == 204
        assert wal.read_bytes() == first + second
        assert _ctr(reg, "fleet_ingest_shed_total", reason="enospc") == 2
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# shipper HA: failover, Retry-After, sealed runs
# ---------------------------------------------------------------------------

def _dead_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_shipper_fails_over_and_ships_everything(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ingest import IngestServer
    from jepsen_tpu.fleet.ship import Shipper
    ops = _register_history(30)
    rd = tmp_path / "src" / "demo" / "t0"
    _write_wal(rd, ops, complete=True)
    store = tmp_path / "fleet"
    srv = IngestServer(store, port=0,
                       registry=telemetry.Registry())
    srv.start()
    try:
        reg = telemetry.Registry()
        sh = Shipper(rd, [f"http://127.0.0.1:{_dead_port()}",
                          f"http://127.0.0.1:{srv.port}"],
                     poll_s=0.02, registry=reg,
                     rng=random.Random(0))
        assert sh.run(timeout_s=60)
        assert sh.failovers >= 1
        assert _ctr(reg, "fleet_ship_resyncs_total",
                    reason="failover") >= 1
        assert ((store / "demo" / "t0" / "history.wal.jsonl")
                .read_bytes()
                == (rd / "history.wal.jsonl").read_bytes())
        assert ((store / "demo" / "t0" / "history.jsonl").read_bytes()
                == (rd / "history.jsonl").read_bytes())
    finally:
        srv.stop()


def test_shipper_obeys_retry_after_and_repolls(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ingest import IngestServer
    from jepsen_tpu.fleet.ship import Shipper
    rd = tmp_path / "src" / "demo" / "t0"
    _write_wal(rd, [{"i": 0}, {"i": 1}])
    wait = {"s": 0.08}
    store = tmp_path / "fleet"
    srv = IngestServer(store, port=0, registry=telemetry.Registry(),
                       pressure=lambda: wait["s"])
    srv.start()
    try:
        reg = telemetry.Registry()
        sh = Shipper(rd, f"http://127.0.0.1:{srv.port}", poll_s=0.01,
                     registry=reg, rng=random.Random(0))
        assert sh.sync()
        assert sh.step() == 0  # shed: nothing absorbed
        assert sh._retry_at > time.monotonic()
        assert sh.tailer.offset == 0  # the bytes were rewound
        assert _ctr(reg, "fleet_ship_resyncs_total", reason="shed") == 1
        assert sh.step() == 0  # still parked: not even a request

        wait["s"] = None
        time.sleep(0.1)
        assert sh.step() > 0  # the SAME bytes land after the park
        assert ((store / "demo" / "t0" / "history.wal.jsonl")
                .read_bytes()
                == (rd / "history.wal.jsonl").read_bytes())
    finally:
        srv.stop()


def test_shipper_seals_when_receiver_holds_final(tmp_path):
    """A shipper (re)starting against a run the receiver already
    finalized stops shipping instead of fighting the seal."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ingest import IngestServer
    from jepsen_tpu.fleet.ship import Shipper
    ops = _register_history(12)
    rd = tmp_path / "src" / "demo" / "t0"
    _write_wal(rd, ops, complete=True)
    final = (rd / "history.jsonl").read_bytes()
    store = tmp_path / "fleet"
    srv = IngestServer(store, port=0, registry=telemetry.Registry())
    srv.start()
    try:
        assert srv.finalize_run(
            "demo/t0", hashlib.sha256(final).hexdigest(), final) == "ok"
        sh = Shipper(rd, f"http://127.0.0.1:{srv.port}", poll_s=0.01,
                     registry=telemetry.Registry())
        assert sh.run(timeout_s=30)
        assert sh.sealed
        assert sh.bytes_sent == 0  # nothing shipped against the seal
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# finals race: one digest-valid history, 409 loser, both orders
# ---------------------------------------------------------------------------

def test_finals_race_final_then_late_chunk(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ingest import IngestServer
    reg = telemetry.Registry()
    srv = IngestServer(tmp_path, port=0, registry=reg)
    srv.start()
    try:
        chunk = b'{"i": 0}\n'
        assert _post_chunk(srv.port, "demo/t0", chunk)[0] == 204
        final = b'{"i": 0}\n{"i": 1}\n'
        sha = hashlib.sha256(final).hexdigest()
        assert srv.finalize_run("demo/t0", sha, final) == "ok"

        # the losing half of the race: a late WAL chunk after the seal
        late = b'{"i": 9}\n'
        status, resp, _ = _post_chunk(
            srv.port, "demo/t0", late, offset=len(chunk),
            prefix_sha=hashlib.sha256(chunk).hexdigest(),
            chunk_sha=hashlib.sha256(chunk + late).hexdigest())
        assert status == 409
        assert json.loads(resp)["reason"] == "finalized"
        wal = tmp_path / "demo" / "t0" / "history.wal.jsonl"
        assert wal.read_bytes() == chunk  # the WAL is sealed
        hist = (tmp_path / "demo" / "t0" / "history.jsonl").read_bytes()
        assert hashlib.sha256(hist).hexdigest() == sha
        assert _ctr(reg, "fleet_ingest_rejected_total",
                    reason="finalized") == 1

        # a DIFFERENT final is the race's other loser: 409, not a swap
        other = b'{"i": 7}\n'
        assert srv.finalize_run(
            "demo/t0", hashlib.sha256(other).hexdigest(),
            other) == "conflict"
        # the byte-identical final is an idempotent re-send
        assert srv.finalize_run("demo/t0", sha, final) == "ok"
        assert (tmp_path / "demo" / "t0"
                / "history.jsonl").read_bytes() == final
    finally:
        srv.stop()


def test_finals_race_chunk_then_final_and_restart_seal(tmp_path):
    """The other order: the chunk lands first, the final seals after —
    and the seal survives a receiver restart (the on-disk history IS
    the final), so a replaying shipper still gets its 409."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ingest import IngestServer
    chunk = b'{"i": 0}\n'
    final = b'{"i": 0}\n{"i": 1}\n'
    sha = hashlib.sha256(final).hexdigest()
    srv = IngestServer(tmp_path, port=0,
                       registry=telemetry.Registry())
    srv.start()
    try:
        assert _post_chunk(srv.port, "demo/t0", chunk)[0] == 204
        assert srv.finalize_run("demo/t0", sha, final) == "ok"
    finally:
        srv.stop()

    srv2 = IngestServer(tmp_path, port=0,
                        registry=telemetry.Registry())
    srv2.start()
    try:
        late = b'{"i": 9}\n'
        status, resp, _ = _post_chunk(
            srv2.port, "demo/t0", late, offset=len(chunk),
            prefix_sha=hashlib.sha256(chunk).hexdigest(),
            chunk_sha=hashlib.sha256(chunk + late).hexdigest())
        assert status == 409
        assert json.loads(resp)["reason"] == "finalized"
        assert srv2.finalize_run(
            "demo/t0", hashlib.sha256(late).hexdigest(),
            late) == "conflict"
        assert (tmp_path / "demo" / "t0"
                / "history.jsonl").read_bytes() == final
    finally:
        srv2.stop()


# ---------------------------------------------------------------------------
# pool scheduler: HA status block, degraded mode, pressure wiring
# ---------------------------------------------------------------------------

def test_fleet_daemon_publishes_ha_block(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.scheduler import FleetDaemon
    fd = FleetDaemon(tmp_path, port=0, poll_s=0.05,
                     accelerator="cpu", host_id="pool-a",
                     lease_ttl_s=5.0,
                     registry=telemetry.Registry())
    fd.ingest.start()
    try:
        payload = fd.poll_once()
        ha = payload["ha"]
        assert ha["host"] == "pool-a"
        assert ha["leasing"] and ha["lease_ttl_s"] == 5.0
        assert ha["leases_held"] == 0 and not ha["shedding"]
        for k in ("lease_acquired", "lease_lost", "fenced_writes",
                  "degraded_total"):
            assert ha[k] == 0
        assert payload["ingest"]["shed_total"] == 0
    finally:
        fd.stop()


def test_fleet_daemon_lease_ttl_zero_disables_leasing(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.scheduler import FleetDaemon
    fd = FleetDaemon(tmp_path, port=0, lease_ttl_s=0,
                     accelerator="cpu",
                     registry=telemetry.Registry())
    fd.ingest.start()
    try:
        assert fd.lease_store is None
        assert not fd.poll_once()["ha"]["leasing"]
    finally:
        fd.stop()


def test_fleet_daemon_degrades_on_status_write_failure(tmp_path,
                                                       monkeypatch):
    """Degraded mode: a failing status write is counted and survived —
    poll_once still returns, because verdicts outrank dashboards."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.scheduler import FleetDaemon

    def broken_write(path, text):
        raise OSError(errno.EIO, "injected status-plane failure")

    monkeypatch.setattr(telemetry, "_atomic_write", broken_write)
    reg = telemetry.Registry()
    fd = FleetDaemon(tmp_path, port=0, lease_ttl_s=0,
                     accelerator="cpu", registry=reg)
    fd.ingest.start()
    try:
        payload = fd.poll_once()
        assert payload.get("degraded_write")
        assert _ctr(reg, "fleet_degraded_total", surface="status") == 1
    finally:
        fd.stop()


def test_fleet_daemon_lag_pressure_sheds(tmp_path):
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.scheduler import LAG_SHED_BUDGETS, FleetDaemon
    fd = FleetDaemon(tmp_path, port=0, lease_ttl_s=0,
                     accelerator="cpu",
                     registry=telemetry.Registry())
    fd.ingest.start()
    try:
        over = fd.daemon.lag_budget_ops * LAG_SHED_BUDGETS + 1
        fd._update_pressure({"demo/t0": {"lag_ops": over}})
        assert fd._shed_wait is not None
        verdict = fd.ingest.overload()
        assert verdict and verdict["shed"] == "lag"
        fd._update_pressure({"demo/t0": {"lag_ops": 0}})
        assert fd._shed_wait is None and fd.ingest.overload() is None
    finally:
        fd.stop()


def test_web_ha_line_renders(tmp_path):
    from jepsen_tpu.web import Handler
    line = Handler._ha_line({
        "host": "pool-a", "leasing": True, "lease_ttl_s": 5.0,
        "leases_held": 3, "lease_acquired": 4, "lease_lost": 1,
        "fenced_writes": 2, "degraded_total": 1, "shedding": True})
    assert "pool-a" in line and "3 held" in line
    assert "4 takeovers" in line and "2 fenced writes" in line
    assert "shedding" in line and "degraded" in line
    assert Handler._ha_line({}) == ""


# ---------------------------------------------------------------------------
# knobs: preflight KNB rows, env twins, fleet_receivers validation
# ---------------------------------------------------------------------------

def test_preflight_validates_ha_knobs():
    from jepsen_tpu.analysis.preflight import preflight

    diags = preflight({"nodes": ["n1"], "fleet_lease_ttl_s": "junk"})
    assert any(d.code == "KNB001" and d.path == "fleet_lease_ttl_s"
               for d in diags)
    diags = preflight({"nodes": ["n1"], "fleet_lease_ttl_s": -1})
    assert any(d.code == "KNB002" for d in diags)
    diags = preflight({"nodes": ["n1"],
                       "fleet_disk_headroom_mb": "junk"})
    assert any(d.code == "KNB001"
               and d.path == "fleet_disk_headroom_mb" for d in diags)
    diags = preflight({"nodes": ["n1"], "fleet_lease_ttl_s": 2.0,
                       "fleet_disk_headroom_mb": 64})
    assert not [d for d in diags if d.path.startswith("fleet_")]


def test_preflight_validates_ha_env_twins(monkeypatch):
    from jepsen_tpu.analysis.preflight import preflight
    monkeypatch.setenv("JEPSEN_TPU_FLEET_LEASE_TTL_S", "junk")
    monkeypatch.setenv("JEPSEN_TPU_FLEET_DISK_HEADROOM_MB", "nope")
    diags = preflight({"nodes": ["n1"]})
    assert any(d.code == "KNB001"
               and d.path == "JEPSEN_TPU_FLEET_LEASE_TTL_S"
               for d in diags)
    assert any(d.code == "KNB001"
               and d.path == "JEPSEN_TPU_FLEET_DISK_HEADROOM_MB"
               for d in diags)


def test_preflight_validates_fleet_receivers():
    from jepsen_tpu.analysis.preflight import preflight

    diags = preflight({"nodes": ["n1"], "fleet_receivers": 42})
    assert any(d.code == "KNB001" and d.path == "fleet_receivers"
               for d in diags)
    diags = preflight({"nodes": ["n1"],
                       "fleet_receivers": ["ftp://pool:1"]})
    assert any(d.code == "KNB007" and d.path == "fleet_receivers"
               for d in diags)
    diags = preflight({"nodes": ["n1"],
                       "fleet_receivers": ["http://a:8091",
                                           "https://b:8091"]})
    assert not [d for d in diags if d.path == "fleet_receivers"]
    # the comma-separated string form validates entry by entry
    diags = preflight({"nodes": ["n1"],
                       "fleet_receivers": "http://a:8091, gopher://b"})
    assert any(d.code == "KNB007" for d in diags)


def test_preflight_validates_fleet_receivers_env_twin(monkeypatch):
    from jepsen_tpu.analysis.preflight import preflight
    monkeypatch.setenv("JEPSEN_TPU_FLEET_RECEIVERS", "not-a-url")
    diags = preflight({"nodes": ["n1"]})
    assert any(d.code == "KNB007"
               and d.path == "JEPSEN_TPU_FLEET_RECEIVERS"
               for d in diags)


def test_fleet_knob_env_twins(monkeypatch):
    from jepsen_tpu.fleet import fleet_knob, fleet_receivers
    monkeypatch.setenv("JEPSEN_TPU_FLEET_LEASE_TTL_S", "2.5")
    assert fleet_knob("fleet_lease_ttl_s", None, 10.0, 0.0) == 2.5
    monkeypatch.setenv("JEPSEN_TPU_FLEET_RECEIVERS",
                       "http://a:8091, http://b:8091/")
    assert fleet_receivers() == ["http://a:8091", "http://b:8091"]
    # explicit values win over the env; garbage tolerantly reads empty
    assert fleet_receivers(["http://c:1/"]) == ["http://c:1"]
    assert fleet_receivers("http://d:2,,") == ["http://d:2"]
    assert fleet_receivers(42) == []


# ---------------------------------------------------------------------------
# the self-chaos harness (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.fleet_chaos
def test_fleet_chaos_invariants_hold(tmp_path):
    """The whole HA story under its own nemesis: SIGKILL the receiver,
    SIGSTOP a pool host past its TTL, SIGKILL the other, torn TCP,
    injected ENOSPC — zero double-checked runs, zero lost/duplicated
    WAL bytes, verdicts bit-identical to local analyze."""
    from jepsen_tpu.fleet.chaos import REPORT_NAME, run_fleet_chaos
    report = run_fleet_chaos(tmp_path, runs=3, n_ops=100, seed=2,
                             lease_ttl_s=0.8, timeout_s=150.0)
    assert report["ok"], report
    assert report["double_checked"] == []
    assert report["wal_mismatch"] == []
    assert report["verdict_mismatch"] == []
    assert report["settled"] == report["runs"] == 3
    assert report["chaos"]["receiver_kills"] == 1
    assert report["chaos"]["pool_kills"] == 1
    on_disk = json.loads((tmp_path / REPORT_NAME).read_text())
    assert on_disk["ok"]
