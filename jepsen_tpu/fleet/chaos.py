"""Self-chaos harness: the fleet plane under its own faults.

``jepsen-tpu fleet-chaos`` turns the nemesis discipline on the fleet
plane itself (doc/robustness.md "Fleet HA"): N producers write + ship
runs against a receiver and a two-host checker pool — all real OS
processes — while the conductor

* SIGKILLs the receiver mid-stream and restarts it on the same port
  (shippers fail over / back off, the resume token re-syncs);
* SIGSTOPs the active pool host past its lease TTL (its peer adopts
  the runs from the restart snapshots; the un-paused host must fence)
  and later SIGKILLs a pool host outright;
* tears TCP shipments mid-chunk (a short body the receiver must
  reject, never absorb);
* injects ENOSPC into the receiver's WAL appends via a flag file
  (chunks bounce with 429, the WAL stays uncorrupted).

Then it asserts the invariants the HA design promises:

1. **zero double-checked runs** — across every pool host's finals log,
   each run was finalized exactly once;
2. **zero lost or duplicated WAL bytes** — the receiver's per-run WAL
   is byte-identical to the producer's local WAL;
3. **verdict parity** — every surviving run's fleet verdict equals a
   local post-hoc ``analyze`` of the producer's own history, bit for
   bit.

The harness reuses the schedule-fuzzer's trial discipline (seeded
histories, planted anomalies) and writes a ``fleet-chaos.json`` report
into the store root. Child processes re-enter this module via
``python -m jepsen_tpu.fleet.chaos <role>``.
"""
from __future__ import annotations

import argparse
import errno
import json
import logging
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

logger = logging.getLogger(__name__)

ENOSPC_FLAG = ".chaos-enospc"
REPORT_NAME = "fleet-chaos.json"
READY_TIMEOUT_S = 60.0
# harness-speed override for the receiver's ENOSPC park window: the
# production 5s default would serialize the whole chaos budget behind
# one injected fault
CHILD_ENOSPC_PARK_S = 0.3


def _planted_history(n_ops: int, seed: int, plant: bool
                     ) -> tuple[list[dict], int | None]:
    """A deterministic register history via the fuzz trial machinery;
    ``plant`` corrupts one acked read so the run's only correct verdict
    is invalid — verdict-parity checks need both polarities."""
    from jepsen_tpu.fuzz.schedule import Schedule
    from jepsen_tpu.fuzz.trial import run_trial
    history = run_trial(Schedule(seed=seed, n_ops=n_ops, concurrency=3))
    planted = None
    if plant:
        for i, op in enumerate(history):
            if i > n_ops // 2 and op.get("type") == "ok" \
                    and op.get("f") == "read" \
                    and op.get("value") is not None:
                op["value"] = op["value"] + 10_000
                planted = i
                break
    return history, planted


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- child roles (python -m jepsen_tpu.fleet.chaos <role> ...) ----------

def _receiver_child(opts) -> None:
    """The ingest receiver as a killable process. ENOSPC injection is a
    flag file so it survives receiver restarts: while
    ``<store>/.chaos-enospc`` exists, every WAL append raises ENOSPC
    and the receiver must shed instead of corrupting."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet import ingest as ingest_mod
    ingest_mod.ENOSPC_PARK_S = CHILD_ENOSPC_PARK_S
    store = Path(opts.store)
    flag = store / ENOSPC_FLAG

    def fault_hook(key, body):
        if flag.exists():
            raise OSError(errno.ENOSPC, "chaos: injected disk full")

    srv = ingest_mod.IngestServer(store, port=opts.port,
                                  registry=telemetry.Registry(),
                                  fault_hook=fault_hook)
    srv.start()
    print(f"READY {srv.port}", flush=True)
    while True:  # killed by the conductor, never exits on its own
        time.sleep(0.5)


def _pool_child(opts) -> None:
    """One leased pool host as a stoppable/killable process. Every
    finalize is appended (fsynced) to ``finals-<host>.jsonl`` — the
    double-check invariant's evidence — stamped with the lease epoch
    the verdict was published under."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.lease import LeaseStore
    from jepsen_tpu.live.daemon import LiveDaemon
    store = Path(opts.store)
    finals = store / f"finals-{opts.host_id}.jsonl"

    def on_final(tr, results):
        row = {"key": tr.label, "host": opts.host_id,
               "epoch": (tr.lease or {}).get("epoch"),
               "valid": tr.last_verdict.get("valid_so_far"),
               "first_anomaly_op":
                   tr.last_verdict.get("first_anomaly_op"),
               "time": time.time()}
        with open(finals, "a", encoding="utf-8") as f:  # durability: fsync
            f.write(json.dumps(row) + "\n")
            f.flush()
            os.fsync(f.fileno())

    lease_store = LeaseStore(store, host_id=opts.host_id,
                             ttl_s=opts.ttl,
                             registry=telemetry.Registry())
    daemon = LiveDaemon(store_root=store, poll_s=opts.poll,
                        check_budget_s=30.0, accelerator="cpu",
                        registry=telemetry.Registry(),
                        on_final=on_final, lease_store=lease_store)
    print("READY 0", flush=True)
    while True:  # killed/stopped by the conductor
        daemon.poll_once()
        time.sleep(opts.poll)


def _child_main(argv) -> int:
    ap = argparse.ArgumentParser(prog="jepsen_tpu.fleet.chaos")
    sub = ap.add_subparsers(dest="role", required=True)
    pr = sub.add_parser("receiver")
    pr.add_argument("--store", required=True)
    pr.add_argument("--port", type=int, default=0)
    pp = sub.add_parser("pool")
    pp.add_argument("--store", required=True)
    pp.add_argument("--host-id", required=True)
    pp.add_argument("--ttl", type=float, default=1.0)
    pp.add_argument("--poll", type=float, default=0.05)
    opts = ap.parse_args(argv)
    if opts.role == "receiver":
        _receiver_child(opts)
    else:
        _pool_child(opts)
    return 0


# -- the conductor ------------------------------------------------------

class _Child:
    """One spawned role process + its READY handshake."""

    def __init__(self, store: Path, role: str, args: list[str],
                 log_name: str):
        self.store = store
        self.role = role
        self.args = args
        self.log_path = store / log_name
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self.stopped = False

    def spawn(self) -> "_Child":
        env = dict(os.environ)
        # the roles check on the CPU by design: the parent may hold the
        # chip, and a child that reached for it would fail or hang
        env["JAX_PLATFORMS"] = "cpu"
        log = open(self.log_path, "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "jepsen_tpu.fleet.chaos",
             self.role] + self.args,
            stdout=subprocess.PIPE, stderr=log, env=env, text=True)
        log.close()
        line: list[str] = []

        def read():  # blocking: rpc — child stdout, bounded by join below
            line.append(self.proc.stdout.readline())

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(READY_TIMEOUT_S)
        if not line or not line[0].startswith("READY"):
            self.proc.kill()
            raise RuntimeError(
                f"chaos {self.role} child never came up "
                f"(see {self.log_path})")
        self.port = int(line[0].split()[1])
        self.stopped = False
        return self

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)

    def pause(self) -> None:
        os.kill(self.proc.pid, signal.SIGSTOP)
        self.stopped = True

    def resume(self) -> None:
        if self.stopped and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGCONT)
        self.stopped = False


def _torn_tcp(port: int, key: str) -> None:
    """Half a POST body, then a hard close: the receiver's short read
    must reject the chunk, never absorb the fragment."""
    body = b'{"torn": true}\n' * 16
    zero = "0" * 64
    head = (f"POST /wal/{key} HTTP/1.1\r\nHost: chaos\r\n"
            f"X-Jepsen-Offset: 0\r\nX-Jepsen-Prefix-Sha: {zero}\r\n"
            f"X-Jepsen-Chunk-Sha: {zero}\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    try:
        s = socket.create_connection(("127.0.0.1", port), timeout=2.0)
        s.sendall(head + body[: len(body) // 2])
        s.close()
    except OSError:
        pass  # receiver mid-restart: the tear landed even harder


def run_fleet_chaos(store_root, runs: int = 4, n_ops: int = 160,
                    seed: int = 0, lease_ttl_s: float = 1.0,
                    timeout_s: float = 180.0) -> dict:
    """The full harness; returns (and persists) the invariant report.
    ``ok`` is True only when every invariant held."""
    from jepsen_tpu import telemetry
    from jepsen_tpu.fleet.ship import Shipper
    from jepsen_tpu.journal import WAL_NAME, Journal

    rng = random.Random(seed)
    root = Path(store_root)
    fleet = root / "fleet-store"
    src = root / "src"
    fleet.mkdir(parents=True, exist_ok=True)
    src.mkdir(parents=True, exist_ok=True)
    port = _free_port()

    receiver = _Child(fleet, "receiver",
                      ["--store", str(fleet), "--port", str(port)],
                      "chaos-receiver.log").spawn()
    pools = [
        _Child(fleet, "pool",
               ["--store", str(fleet), "--host-id", f"pool{i}",
                "--ttl", str(lease_ttl_s)],
               f"chaos-pool{i}.log").spawn()
        for i in (0, 1)
    ]

    cases: dict[str, tuple[list[dict], int | None]] = {}
    threads: list[threading.Thread] = []
    shippers: list[Shipper] = []
    dead_base = f"http://127.0.0.1:{_free_port()}"
    stats = {"receiver_kills": 0, "pool_kills": 0, "pool_stops": 0,
             "torn_tcp": 0, "enospc_windows": 0}

    def producer(run_dir: Path, history: list[dict]) -> None:
        j = Journal(run_dir / WAL_NAME, fsync_interval_s=-1)
        for op in history:
            j.append(op)
            time.sleep(0.002)
        j.close()
        with open(run_dir / "history.jsonl", "w",
                  encoding="utf-8") as f:
            for op in history:
                f.write(json.dumps(op) + "\n")

    try:
        for i in range(runs):
            key = f"c{i:02d}/0"
            rd = src / key
            rd.mkdir(parents=True, exist_ok=True)
            history, planted = _planted_history(
                n_ops, seed=seed * 1000 + i, plant=(i % 2 == 1))
            cases[key] = (history, planted)
            tp = threading.Thread(target=producer, args=(rd, history),
                                  daemon=True)
            # odd runs lead with a dead endpoint: every exchange
            # exercises the failover rotation before reaching the real
            # receiver
            bases = ([dead_base, f"http://127.0.0.1:{port}"]
                     if i % 2 else [f"http://127.0.0.1:{port}"])
            sh = Shipper(rd, bases, poll_s=0.02,
                         registry=telemetry.Registry(),
                         rng=random.Random(rng.getrandbits(32)))
            ts = threading.Thread(
                target=lambda sh=sh: sh.run(timeout_s=timeout_s),
                daemon=True)
            tp.start()
            ts.start()
            threads.extend([tp, ts])
            shippers.append(sh)

        # -- the chaos schedule, while producers ship -------------------
        time.sleep(0.4)
        _torn_tcp(port, "c00/0")
        stats["torn_tcp"] += 1

        (fleet / ENOSPC_FLAG).touch()  # receiver WAL appends now ENOSPC
        stats["enospc_windows"] += 1
        time.sleep(0.5)
        (fleet / ENOSPC_FLAG).unlink(missing_ok=True)

        receiver.kill()  # SIGKILL mid-stream
        stats["receiver_kills"] += 1
        time.sleep(0.3)
        _torn_tcp(port, "c01/0")  # tear against the dead port too
        stats["torn_tcp"] += 1
        receiver.spawn()  # same port + store: cursors rebuild from disk

        # pause one pool host past its TTL: the peer adopts from the
        # restart snapshots; the un-paused host must fence, not
        # double-publish
        pools[0].pause()
        stats["pool_stops"] += 1
        time.sleep(max(2.5 * lease_ttl_s, 1.0))
        pools[0].resume()

        time.sleep(0.5)
        pools[1].kill()  # hard kill: its leases expire, pool0 adopts
        stats["pool_kills"] += 1

        for t in threads:
            t.join(timeout_s)

        # every run settled: a final live-status on the fleet side
        from jepsen_tpu.live.daemon import load_live_status
        deadline = time.monotonic() + timeout_s
        pending = set(cases)
        while pending and time.monotonic() < deadline:
            for key in sorted(pending):
                st = load_live_status(fleet / key)
                if st is not None and st.get("state") == "final":
                    pending.discard(key)
            time.sleep(0.2)
    finally:
        receiver.kill()
        for p in pools:
            p.resume()
            p.kill()

    # -- invariants -----------------------------------------------------
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.journal import read_jsonl_tolerant
    from jepsen_tpu.live.daemon import load_live_status

    finals: dict[str, list[dict]] = {}
    for f in sorted(fleet.glob("finals-*.jsonl")):
        rows, _ = read_jsonl_tolerant(f)
        for row in rows:
            finals.setdefault(str(row.get("key")), []).append(row)

    double_checked = sorted(k for k, rows in finals.items()
                            if len(rows) > 1)
    unsettled = sorted(pending)
    wal_mismatch: list[str] = []
    verdict_mismatch: list[str] = []
    for key, (history, planted) in cases.items():
        if key in unsettled:
            continue
        local_wal = (src / key / "history.wal.jsonl").read_bytes()
        fleet_wal_p = fleet / key / "history.wal.jsonl"
        fleet_wal = (fleet_wal_p.read_bytes()
                     if fleet_wal_p.exists() else b"")
        if fleet_wal != local_wal:
            wal_mismatch.append(key)
        st = load_live_status(fleet / key) or {}
        local = LinearizableChecker(accelerator="cpu").check(
            {}, history, {})
        if st.get("valid_so_far") is not local["valid?"] or (
                planted is not None
                and st.get("first_anomaly_op") != planted):
            verdict_mismatch.append(key)

    report = {
        "version": 1,
        "runs": len(cases),
        "settled": len(cases) - len(unsettled),
        "unsettled": unsettled,
        "double_checked": double_checked,
        "wal_mismatch": wal_mismatch,
        "verdict_mismatch": verdict_mismatch,
        "finals_hosts": {k: [r.get("host") for r in rows]
                         for k, rows in sorted(finals.items())},
        "chaos": stats,
        "ok": not (double_checked or wal_mismatch
                   or verdict_mismatch or unsettled),
    }
    telemetry._atomic_write(root / REPORT_NAME,
                            json.dumps(report, indent=1))
    return report


if __name__ == "__main__":
    sys.exit(_child_main(sys.argv[1:]))
