"""The pool scheduler: one daemon over every shipped run.

Composition, not reinvention: the ingest receiver lands shipped WALs
in the exact store layout core.run writes locally, so the pool's
checker IS a :class:`jepsen_tpu.live.daemon.LiveDaemon` over the
ingest store — discovery, admission (``CostModel.admission_budget_ops``
spent most-lagged-first), per-run circuit breakers, restart snapshots
and the capped metric export all apply to fleet runs unchanged. What
this module adds on top, per poll:

* the **mesh heal path** — when devices previously shrunk away
  (``parallel.shrink_mesh``) may have recovered, re-probe and regrow
  (``parallel.regrow_mesh``, ``mesh_regrow_total{from,to}``), on a
  backoff so a flapping device can't turn every poll into a probe
  storm;
* the **status plane** — one aggregated, atomic ``fleet-status.json``
  plus the fleet-level Prometheus export (``fleet-metrics.prom``);
* the **HA plane** (doc/robustness.md "Fleet HA") — a
  :class:`jepsen_tpu.fleet.lease.LeaseStore` handed to the live daemon
  so two pool hosts over one shared ingest store check each run
  exactly once (fencing keeps a deposed host's stale writes out);
  receiver backpressure (free-disk floor + an aggregate-lag pressure
  hook feeding 429s); and **degraded mode** — a failing status write
  or metrics export is counted (``fleet_degraded_total{surface}``) and
  survived, never allowed to stall the verdict path.
"""
from __future__ import annotations

import logging
import threading
import time

from jepsen_tpu import telemetry
from jepsen_tpu.fleet import (
    DEFAULT_FLEET_DISK_HEADROOM_MB, DEFAULT_FLEET_INGEST_BUDGET_S,
    DEFAULT_FLEET_LEASE_TTL_S, DEFAULT_FLEET_MAX_RUNS,
    DEFAULT_FLEET_PORT, fleet_knob,
)
from jepsen_tpu.fleet.ingest import RETRY_AFTER_S, IngestServer
from jepsen_tpu.fleet.lease import LeaseStore, default_host_id
from jepsen_tpu.fleet.status import FleetStatus
from jepsen_tpu.live.daemon import DEFAULT_POLL_S, LiveDaemon
from jepsen_tpu.utils import join_noisy

logger = logging.getLogger(__name__)

REGROW_BACKOFF_S = 5.0
# aggregate-lag pressure: shed new chunks once total checker lag
# exceeds this many per-run lag budgets — the pool is drowning and
# absorbing more WAL only digs the hole (doc/robustness.md "Fleet HA")
LAG_SHED_BUDGETS = 4.0


class FleetDaemon:
    """Ingest receiver + live checker pool + status plane, one knob
    set (``fleet_port``, ``fleet_ingest_budget_s``, ``fleet_max_runs``,
    ``fleet_lease_ttl_s``, ``fleet_disk_headroom_mb`` — each with a
    ``JEPSEN_TPU_FLEET_*`` env twin)."""

    def __init__(self, store_root, host: str = "127.0.0.1",
                 port=None, ingest_budget_s=None, max_runs=None,
                 lease_ttl_s=None, disk_headroom_mb=None,
                 host_id: str | None = None,
                 poll_s=DEFAULT_POLL_S, accelerator: str = "auto",
                 registry: telemetry.Registry | None = None,
                 regrow_backoff_s: float = REGROW_BACKOFF_S,
                 on_final=None, fault_hook=None):
        self.registry = registry if registry is not None \
            else telemetry.Registry()
        self.store_root = store_root
        port = int(fleet_knob("fleet_port", port,
                              DEFAULT_FLEET_PORT, 0.0))
        budget = fleet_knob("fleet_ingest_budget_s", ingest_budget_s,
                            DEFAULT_FLEET_INGEST_BUDGET_S, 0.0)
        max_runs = int(fleet_knob("fleet_max_runs", max_runs,
                                  DEFAULT_FLEET_MAX_RUNS, 1.0))
        ttl = fleet_knob("fleet_lease_ttl_s", lease_ttl_s,
                         DEFAULT_FLEET_LEASE_TTL_S, 0.0)
        headroom = fleet_knob("fleet_disk_headroom_mb",
                              disk_headroom_mb,
                              DEFAULT_FLEET_DISK_HEADROOM_MB, 0.0)
        self.host_id = host_id or default_host_id()
        # ttl 0 disables leasing: the single-pool-host mode, where
        # fencing would only cost fsyncs
        self.lease_store = None if ttl <= 0 else LeaseStore(
            store_root, host_id=self.host_id, ttl_s=ttl,
            registry=self.registry)
        # aggregate-lag pressure for the receiver: poll_once updates
        # the wait; the ingest thread only reads it (atomic attr read)
        self._shed_wait: float | None = None
        self.ingest = IngestServer(store_root, host=host, port=port,
                                   registry=self.registry,
                                   disk_headroom_mb=headroom,
                                   pressure=lambda: self._shed_wait,
                                   fault_hook=fault_hook)
        self.daemon = LiveDaemon(store_root=store_root,
                                 poll_s=poll_s, max_runs=max_runs,
                                 check_budget_s=budget,
                                 accelerator=accelerator,
                                 registry=self.registry,
                                 on_final=on_final,
                                 lease_store=self.lease_store)
        self.status = FleetStatus(store_root, self.registry)
        self.regrow_backoff_s = regrow_backoff_s
        self._regrow_last = 0.0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self.ingest.port

    def _maybe_regrow(self) -> None:
        """Re-probes shrunk-away devices on a backoff; a heal regrows
        the mesh for every session the pool checks."""
        from jepsen_tpu import parallel
        if not parallel.failed_device_ids():
            return
        now = time.monotonic()
        if now - self._regrow_last < self.regrow_backoff_s:
            return
        self._regrow_last = now
        parallel.regrow_mesh()

    def _degraded(self, surface: str) -> None:
        """Counts a non-verdict surface failing — the fleet keeps
        checking; the dashboard shows it's flying on instruments."""
        self.registry.counter(
            "fleet_degraded_total",
            "non-verdict surfaces (status write, metrics export) that "
            "failed a poll; verdicts kept flowing",
            labels=("surface",)).inc(surface=surface)

    def _update_pressure(self, statuses: dict) -> None:
        """Refreshes the receiver's aggregate-lag shed signal from this
        poll's statuses: once total lag across tracked runs exceeds
        LAG_SHED_BUDGETS per-run budgets, new chunks get a 429 until
        the pool catches up."""
        budget = self.daemon.lag_budget_ops * LAG_SHED_BUDGETS
        if budget <= 0:
            self._shed_wait = None
            return
        agg = sum(st.get("lag_ops", 0) or 0
                  for st in statuses.values())
        self._shed_wait = RETRY_AFTER_S if agg > budget else None

    def poll_once(self) -> dict:  # owner: scheduler
        """One fleet poll: check every tracked run (the live daemon's
        own poll), then heal, then publish the aggregate. Publication
        failures degrade, they don't stall verdicts."""
        statuses = self.daemon.poll_once()
        self._update_pressure(statuses)
        self._maybe_regrow()
        ha = {
            "host": self.host_id,
            "leasing": self.lease_store is not None,
            "lease_ttl_s": (self.lease_store.ttl_s
                            if self.lease_store else 0.0),
            "leases_held": (len(self.lease_store.held)
                            if self.lease_store else 0),
            "shedding": self._shed_wait is not None,
        }
        payload = self.status.write(statuses,
                                    self.ingest.ingest_stats(), ha=ha)
        if payload.get("degraded_write"):
            self._degraded("status")
        try:
            self.registry.export(self.status.store_root,
                                 prefix="fleet-metrics")
        except OSError:
            logger.exception("fleet metrics export failed")
            self._degraded("metrics-export")
        return payload

    # -- lifecycle ------------------------------------------------------

    def _loop(self) -> None:  # owner: scheduler
        while not self._stop.is_set():
            t0 = time.monotonic()
            try:
                self.poll_once()
            except Exception:  # noqa: BLE001 — the pool must survive anything
                logger.exception("fleet poll failed")
            rest = self.daemon.poll_s - (time.monotonic() - t0)
            if rest > 0:
                self._stop.wait(rest)

    def start(self) -> "FleetDaemon":
        self.ingest.start()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="jepsen-fleet-poller")
            self._thread.start()
        logger.info("fleet daemon up: ingest on :%d, polling every "
                    "%.3gs", self.port, self.daemon.poll_s)
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            join_noisy(t, "fleet daemon poller", heartbeat_s=5.0)
            self._thread = None
        self.ingest.stop()

    def run_until_idle(self, timeout_s: float = 60.0) -> dict:
        """Foreground helper (tests, ``--once``): the ingest plane
        stays up while the pool polls until every tracked run
        finalized (or the deadline passes); returns the last
        fleet-status payload."""
        self.ingest.start()
        deadline = time.monotonic() + timeout_s
        payload: dict = {}
        try:
            while time.monotonic() < deadline:
                payload = self.poll_once()
                if self.status.polls > 1 and not self.daemon.trackers:
                    break
                time.sleep(min(self.daemon.poll_s,
                               max(0.0,
                                   deadline - time.monotonic())))
        finally:
            self.ingest.stop()
        return payload


def serve(store_root, **kw) -> None:
    """``jepsen-tpu fleet``: runs the fleet daemon in the foreground
    until interrupted."""
    from jepsen_tpu import compile_cache
    compile_cache.enable()
    fd = FleetDaemon(store_root, **kw)
    fd.start()
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        fd.stop()
