"""Pipelined device dispatch: overlap host staging with device compute.

Every dispatch/readback pair costs a fixed round trip, and the
checker's batch paths (jitlin's
transfer-matrix sub-dispatches, the segmented scale chain) are sequences
of bounded dispatches whose HOST side — prepass, grid build, interning,
H2D staging — can run entirely under the previous dispatch's device
compute. JAX dispatch is already asynchronous; what this module adds is
the discipline and the evidence:

* :class:`DispatchPipeline` — a bounded-depth dispatch queue. Each
  ``submit(prep_fn, dispatch_fn)`` runs the host staging, issues the
  async dispatch, and tracks the unsynced device handles; when more than
  ``depth`` dispatches are outstanding the OLDEST is blocked on first
  (delayed blocking), so ≥ 2 sub-batches stay in flight while device
  memory stays bounded. ``results()`` performs ONE batched host
  transfer at the very end — never a readback per sub-batch.
* Occupancy accounting — how much host staging time was hidden under
  in-flight device work, stall time spent at the depth limit, and the
  in-flight high-water — wired into the telemetry registry
  (``dispatch_*`` instruments) and mirrored into the thread-local
  :func:`last_stats` so
  bench.py can fold the numbers into its summary line.
* A round-trip cost model (:class:`CostModel`) for ``accelerator=auto``
  routing: when the CPU lane can finish a batch before the device's
  round-trip floor, the batch routes to the C++/CPU lane instead of
  paying the dispatch latency (VERDICT r4 #4 / r5 weak #2 — sub-128-key
  ``independent`` batches were latency-bound, not compute-bound).

The pipeline is deliberately host-synchronous: ``submit`` runs prep on
the calling thread (numpy prep work is GIL-bound anyway) and relies on
the device runtime for the actual overlap. That keeps results
DETERMINISTIC — submission order is result order, and a pipelined run
is bit-identical to a serial one (tests/test_pipeline.py pins this
against the un-pipelined path).
"""
from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque

logger = logging.getLogger("jepsen.pipeline")

# Stats of the calling thread's most recently completed pipeline
# (results() updates it): bench.py reads this after a timed stage the
# way elle's bench reads its phase dict. Thread-local — concurrent
# checkers under bounded_pmap must not clobber each other's stats.
_LAST_STATS = threading.local()


def last_stats() -> dict:
    """The calling thread's most recent pipeline stats ({} if none)."""
    return dict(getattr(_LAST_STATS, "value", {}))

# Default CPU-lane throughput estimate (events/sec) for the cost model
# before any measured sample lands: the r5 bench's directly-measured
# sequential CPU anchor checked ~95k ops/s = ~190k events/s on this
# host; half that is a conservative floor so auto-routing never sends
# device-sized work to a slower-than-expected CPU.
DEFAULT_CPU_EVENTS_PER_SEC = 100_000.0

_RTT_CACHE: dict = {}
_CPU_RATE: dict = {}
# measured checker throughput per mesh width: {n_devices: events/s EWMA}
# (n_devices=1 is the single-device lane). Feeds CostModel.mesh_route so
# a small batch is not sent to the mesh on faith.
_DEVICE_RATE: dict = {}

# Below this many events, a batch with no measured rates skips the mesh:
# the fixed mesh costs (per-device staging, divisibility padding, the
# verdict collective) can't amortize on tiny dispatches. Env-tunable for
# on-chip sweeps.
MESH_MIN_EVENTS = int(os.environ.get("JEPSEN_TPU_MESH_MIN_EVENTS",
                                     str(1 << 16)))
# with no measured single-device rate, every Nth mesh-eligible batch
# runs single-device instead — the probe that lets mesh_route's
# measured comparison activate (and demote a losing mesh) in workloads
# that would otherwise only ever sample the mesh width
MESH_PROBE_EVERY = 16
_MESH_PROBE_COUNT = 0


def measured_roundtrip_s() -> float:
    """One tiny H2D+D2H round trip (median of 3 after a warm-up, cached
    per process) — the fixed latency floor every device dispatch chain
    pays at least twice (first dispatch + final readback). The
    ``JEPSEN_TPU_RTT_S`` env var overrides (tests, known deployments);
    an unreachable backend reads as 0.0 so routing degrades to
    device-always rather than guessing."""
    env = os.environ.get("JEPSEN_TPU_RTT_S")
    if env:
        try:
            return float(env)
        except ValueError:
            logger.warning("ignoring malformed JEPSEN_TPU_RTT_S=%r", env)
    if "rtt" not in _RTT_CACHE:
        try:
            import jax
            import numpy as np
            x = np.zeros(8, np.float32)
            jax.device_get(jax.device_put(x))  # warm backend/compile
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.device_get(jax.device_put(x))
                ts.append(time.perf_counter() - t0)
            _RTT_CACHE["rtt"] = sorted(ts)[1]
        except Exception:  # noqa: BLE001 — no backend: never route on it
            _RTT_CACHE["rtt"] = 0.0
    return _RTT_CACHE["rtt"]


def observe_cpu_rate(n_events: int, seconds: float) -> None:
    """Feeds a measured CPU-lane sample into the cost model (EWMA) so
    routing tracks the actual host instead of the built-in default."""
    if seconds <= 0 or n_events <= 0:
        return
    rate = n_events / seconds
    prev = _CPU_RATE.get("events_per_sec")
    _CPU_RATE["events_per_sec"] = (rate if prev is None
                                   else 0.7 * prev + 0.3 * rate)


def cpu_events_per_sec() -> float:
    return _CPU_RATE.get("events_per_sec", DEFAULT_CPU_EVENTS_PER_SEC)


def observe_device_rate(n_devices: int, n_events: int,
                        seconds: float) -> None:
    """Feeds one measured device-lane sample into the per-device-count
    rate model (EWMA per mesh width). The first sample per width
    includes JIT compile — the EWMA washes it out within a few
    dispatches, and an under-estimate only means routing a batch to one
    device, the old behavior. Samples below a quarter of
    MESH_MIN_EVENTS are dropped: a tiny dispatch measures fixed
    overhead (compile, staging, the round trip), not throughput, and
    would mislead the route comparison at the large sizes where routing
    matters."""
    if (seconds <= 0 or n_events < max(1, MESH_MIN_EVENTS // 4)
            or n_devices < 1):
        return
    rate = n_events / seconds
    prev = _DEVICE_RATE.get(n_devices)
    _DEVICE_RATE[n_devices] = (rate if prev is None
                               else 0.7 * prev + 0.3 * rate)


def device_events_per_sec(n_devices: int) -> float | None:
    """The measured EWMA rate at a mesh width, or None (no sample)."""
    return _DEVICE_RATE.get(n_devices)


class CostModel:
    """Round-trip-vs-CPU routing for ``accelerator=auto``.

    The device floor for a pipelined batch is ~2 round trips (the first
    dispatch's H2D and the single batched readback; intermediate
    dispatches overlap). When the CPU lane's predicted time beats that
    floor, the device can only lose — route to CPU. Compute time on
    device is NOT modeled (it would need a per-kernel throughput model);
    the floor alone is what kills small batches, and
    an under-estimate only means taking the device path, the old
    behavior."""

    def __init__(self, roundtrip_s: float | None = None,
                 cpu_events_per_sec_: float | None = None):
        self._rtt = roundtrip_s
        self._cpu_rate = cpu_events_per_sec_

    def rtt(self) -> float:
        return self._rtt if self._rtt is not None else measured_roundtrip_s()

    def cpu_rate(self) -> float:
        return (self._cpu_rate if self._cpu_rate is not None
                else cpu_events_per_sec())

    def cpu_seconds(self, total_events: int) -> float:
        return total_events / max(self.cpu_rate(), 1e-9)

    def device_floor_seconds(self) -> float:
        return 2.0 * self.rtt()

    def route(self, total_events: int) -> str:
        """"cpu" when the CPU lane beats the device round-trip floor,
        else "device"."""
        return ("cpu" if self.cpu_seconds(total_events)
                < self.device_floor_seconds() else "device")

    def admission_budget_ops(self, seconds: float) -> float:
        """How many events the CPU lane can verify in ``seconds`` — the
        live daemon's per-poll admission budget (one hot run may spend
        at most its share of this before the rest defer; the measured
        EWMA keeps it honest as the host load shifts)."""
        return max(0.0, seconds) * self.cpu_rate()

    def mesh_route(self, total_events: int, n_devices: int) -> bool:
        """Should a batch of ``total_events`` take the ``n_devices``
        mesh path? With measured rates at both widths, compare predicted
        times (the mesh side also pays ~1 extra round trip for the
        verdict collective + per-device staging); without evidence, gate
        on MESH_MIN_EVENTS so small batches never pay mesh overhead on
        faith. A wrong "no" is the old single-device behavior; a wrong
        "yes" self-corrects once the rates land — and because a
        mesh-dominated workload would otherwise never produce a
        single-device sample, every MESH_PROBE_EVERY-th eligible batch
        with no measured single-device rate runs single-device as a
        probe, so the comparison can activate and demote a losing
        mesh."""
        global _MESH_PROBE_COUNT
        if n_devices < 2:
            return False
        r1 = device_events_per_sec(1)
        rn = device_events_per_sec(n_devices)
        if r1 and rn:
            return (total_events / rn + self.rtt()
                    < total_events / r1)
        if total_events < MESH_MIN_EVENTS:
            return False
        if r1 is None:
            _MESH_PROBE_COUNT += 1
            if _MESH_PROBE_COUNT % MESH_PROBE_EVERY == 0:
                return False
        return True


_DEFAULT_MODEL = CostModel()


def auto_route(total_events: int) -> str:
    """Module-level routing with the process-default cost model."""
    return _DEFAULT_MODEL.route(total_events)


def mesh_route(total_events: int, n_devices: int) -> bool:
    """Module-level mesh gate with the process-default cost model."""
    return _DEFAULT_MODEL.mesh_route(total_events, n_devices)


def donate_ok() -> bool:
    """Should dispatches donate their carry buffers? Donation lets XLA
    reuse the previous segment's [B, MV, MV] operator product in place
    (halving the carry's HBM footprint on chained resume dispatches),
    but the CPU backend can't honor it and warns per call — gate on the
    default backend."""
    try:
        import jax
        return jax.default_backend() != "cpu"
    except Exception:  # noqa: BLE001
        return False


def _pending(handle) -> bool:
    """Is the dispatch still executing? jax arrays expose a non-blocking
    ``is_ready()``; an already-finished dispatch must NOT count as
    overlap (a host-bound pipeline would otherwise report near-perfect
    occupancy it never achieved). Objects without readiness (test
    fakes) count as pending."""
    try:
        import jax
        arrs = [l for l in jax.tree_util.tree_leaves(handle)
                if isinstance(l, jax.Array)]
        if arrs:
            return not all(a.is_ready() for a in arrs)
    except ImportError:
        pass
    is_ready = getattr(handle, "is_ready", None)
    return True if is_ready is None else not is_ready()


def _is_jax_tree(handle) -> bool:
    """Does the handle tree contain jax arrays? Distinguishes real
    dispatches from test fakes WITHOUT a blanket except that would
    also swallow genuine device failures."""
    try:
        import jax
        return any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(handle))
    except ImportError:
        return False


def _block(handle) -> None:
    """Blocks until a dispatch's handles are ready. Works on jax arrays
    (tree), or any object exposing block_until_ready (test fakes).
    Device/runtime failures propagate — they must not read as a
    successful (zero-stall) block."""
    if _is_jax_tree(handle):
        import jax
        jax.block_until_ready(handle)
        return
    bur = getattr(handle, "block_until_ready", None)
    if bur is not None:
        bur()


class DispatchPipeline:
    """Bounded-depth async dispatch queue with occupancy accounting.

    ::

        pipe = DispatchPipeline(depth=2, name="matrix")
        for sub in sub_batches:
            pipe.submit(lambda: build_grids(sub),   # host staging
                        dispatch_kernel)            # async device call
        outs = pipe.results()                       # ONE batched fetch

    ``prep_fn()`` returns the dispatch args (a tuple, or a single value);
    ``dispatch_fn(*args)`` must return device handles WITHOUT reading
    them back. With ``dispatch_fn=None``, ``prep_fn`` does both and
    returns the handles directly. Results come back in submission
    order."""

    def __init__(self, depth: int = 2, name: str = "dispatch"):
        from jepsen_tpu import telemetry

        self.depth = max(1, depth)
        self.name = name
        self._handles: list = []
        self._inflight: deque = deque()
        self._t0 = time.perf_counter()
        self._prep_s = 0.0
        self._overlap_prep_s = 0.0
        self._stall_s = 0.0
        self._inflight_peak = 0
        self._reg = telemetry.get_registry()

    def stage(self, *arrays):
        """Issues async H2D copies for ``arrays`` (double-buffered by the
        runtime) so the transfer overlaps in-flight compute instead of
        serializing inside the jitted call."""
        import jax
        return [jax.device_put(a) for a in arrays]

    def submit(self, prep_fn, dispatch_fn=None):
        """Stages one sub-batch and dispatches it. Returns the unsynced
        handle (also tracked for results())."""
        # overlap is judged BEFORE prep runs and only against dispatches
        # still executing (non-blocking readiness probe): crediting any
        # prep-after-first-submit would report near-perfect occupancy
        # even when the device finished long before staging did
        was_computing = any(_pending(h) for h in self._inflight)
        t0 = time.perf_counter()
        staged = prep_fn()
        dt = time.perf_counter() - t0
        self._prep_s += dt
        if was_computing:
            # host staging that ran while >= 1 dispatch computed on
            # device: the time the pipeline actually hid
            self._overlap_prep_s += dt
        if len(self._inflight) >= self.depth:
            oldest = self._inflight.popleft()
            t1 = time.perf_counter()
            _block(oldest)
            self._stall_s += time.perf_counter() - t1
        if dispatch_fn is None:
            handle = staged
        else:
            args = staged if isinstance(staged, tuple) else (staged,)
            handle = dispatch_fn(*args)
        self._handles.append(handle)
        self._inflight.append(handle)
        self._inflight_peak = max(self._inflight_peak, len(self._inflight))
        if self._reg.enabled:
            self._reg.counter(
                "dispatch_batches_total", "sub-batches dispatched",
                labels=("queue",)).inc(queue=self.name)
            self._reg.gauge(
                "dispatch_inflight", "dispatches currently in flight",
                labels=("queue",)).set(len(self._inflight), queue=self.name)
            self._reg.gauge(
                "dispatch_inflight_peak", "in-flight high-water",
                labels=("queue",)).set_max(self._inflight_peak,
                                           queue=self.name)
        return handle

    def results(self) -> list:
        """ONE batched host transfer of every submitted handle, in
        submission order; finalizes the occupancy stats."""
        t1 = time.perf_counter()
        if _is_jax_tree(self._handles):
            # real dispatches: one batched readback; device failures
            # (worker crash, runtime fault) PROPAGATE — swallowing them
            # here would hand unsynced handles to the caller, whose
            # per-element reads would then pay a round trip each and
            # lose the original error
            import jax
            out = jax.device_get(self._handles)
        else:
            out = list(self._handles)  # test fakes
        sync_s = time.perf_counter() - t1
        wall = time.perf_counter() - self._t0
        overlap_frac = (self._overlap_prep_s / self._prep_s
                        if self._prep_s > 0 else 0.0)
        stats = {
            "queue": self.name,
            "batches": len(self._handles),
            "inflight_peak": self._inflight_peak,
            "host_prep_s": round(self._prep_s, 4),
            "overlapped_prep_s": round(self._overlap_prep_s, 4),
            "overlap_frac": round(overlap_frac, 4),
            "stall_s": round(self._stall_s, 4),
            "sync_s": round(sync_s, 4),
            "wall_s": round(wall, 4),
        }
        _LAST_STATS.value = stats
        if self._reg.enabled:
            self._reg.gauge(
                "dispatch_overlap_frac",
                "fraction of host staging hidden under device compute, "
                "last pipeline", labels=("queue",)
                ).set(overlap_frac, queue=self.name)
            self._reg.gauge(
                "dispatch_inflight", "dispatches currently in flight",
                labels=("queue",)).set(0, queue=self.name)
            self._reg.histogram(
                "dispatch_stall_seconds",
                "time blocked at the depth limit", labels=("queue",)
                ).observe(self._stall_s, queue=self.name)
            self._reg.histogram(
                "dispatch_sync_seconds", "final batched readback wait",
                labels=("queue",)).observe(sync_s, queue=self.name)
        self._inflight.clear()
        return out

    def stats(self) -> dict:
        """The finalized stats (valid after results())."""
        s = last_stats()
        return s if s.get("queue") == self.name else {}
