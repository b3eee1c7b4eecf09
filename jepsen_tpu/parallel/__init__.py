"""Device-mesh parallelism for the checker data plane.

The reference's 'distributed communication backend' is SSH fan-out
(SURVEY.md §5.8); ours is XLA collectives over a `jax.sharding.Mesh`. The
checker workloads are batch-parallel over keys (independent registers) and
graph-parallel over txn partitions, so the sharding story is:

* ``keys`` axis: per-key event tensors sharded over all devices; the
  jitlin kernel runs under vmap with inputs/outputs NamedSharding'd on the
  leading axis, so each device checks its shard of keys with zero
  cross-device traffic until the final verdict gather (ICI all-gather of
  B bools).
* SCC label propagation shards edges over devices and psums the label
  updates (see ops/scc.py) — collectives ride ICI on a pod.

Multi-host: ``parallel.distributed`` initializes ``jax.distributed``,
builds a process-spanning global mesh, places per-process edge shards
with make_array_from_process_local_data for the sharded trim (psum
crossing the process boundary), and splits independent key batches by
process with a verdict allgather. Exercised for real by
tests/test_distributed.py: two OS processes × 4 virtual CPU devices
form one 8-device mesh and run both paths end to end.
"""
from __future__ import annotations

import logging
import threading
from typing import Sequence

import numpy as np

logger = logging.getLogger("jepsen.parallel")


def devices():
    import jax
    return jax.devices()


def get_mesh(n_devices: int | None = None, axis: str = "keys"):
    """A 1-D mesh over available devices (jax.sharding.Mesh)."""
    import jax
    from jax.sharding import Mesh
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


# One Mesh object per (device count, axis): jitlin's compile caches key
# on the mesh's device ids + axis names, but Mesh construction itself is
# cheap-ish yet NOT free, and handing callers the same object makes
# caching behavior obvious in traces.
_MESH_CACHE: dict = {}


def coerce_devices(value, knob: str = "mesh_devices") -> int | None:
    """Tolerant device-count knob coercion: None/'' read as unset,
    numeric strings work, garbage warns and reads as unset (the
    interpreter's knob-layer discipline — a bad sweep variable must
    not fail a run preflight already admitted)."""
    if value is None or value == "":
        return None
    if isinstance(value, bool):
        logger.warning("ignoring bool %s=%r (want a device count)",
                       knob, value)
        return None
    try:
        n = int(float(value))
    except (TypeError, ValueError):
        logger.warning("ignoring malformed %s=%r (want an int)",
                       knob, value)
        return None
    return max(0, n)


def coerce_flag(value, knob: str = "checker_sharded") -> bool | None:
    """Tolerant bool knob coercion: None/'' unset; bools and 0/1 pass;
    yes/no/true/false/on/off strings work; garbage warns and reads as
    unset (the env/ladder default then applies)."""
    if value is None or value == "":
        return None
    if isinstance(value, bool):
        return value
    if isinstance(value, (int, float)) and value in (0, 1):
        return bool(value)
    if isinstance(value, str):
        s = value.strip().lower()
        if s in ("1", "true", "yes", "on"):
            return True
        if s in ("0", "false", "no", "off"):
            return False
    logger.warning("ignoring malformed %s=%r (want a bool)", knob, value)
    return None


def sharding_knobs(test, opts) -> tuple:
    """The per-run sharding knob pair ``(checker_sharded flag,
    mesh_devices cap)`` from a checker's (test, opts), tolerantly
    coerced, opts taking precedence over the test map — the ONE reading
    LinearizableChecker and IndependentChecker share (True forces the
    sharded path, False disables it, None = env default + cost model)."""
    tmap = test if isinstance(test, dict) else {}
    flag = coerce_flag(opts.get("checker_sharded",
                                tmap.get("checker_sharded")))
    devices = coerce_devices(opts.get("mesh_devices",
                                      tmap.get("mesh_devices")))
    return flag, devices


def mesh_devices_limit() -> int | None:
    """The ``JEPSEN_TPU_MESH_DEVICES`` env cap on mesh width, tolerantly
    coerced (garbage warns and reads as unset, like the interpreter's
    knob layer). 0/1 effectively disables sharding; None = no cap."""
    import os
    return coerce_devices(os.environ.get("JEPSEN_TPU_MESH_DEVICES"),
                          knob="JEPSEN_TPU_MESH_DEVICES")


# ---------------------------------------------------------------------------
# Device health + the elastic mesh shrink path
# (doc/robustness.md "Resumable checks and the elastic mesh")
# ---------------------------------------------------------------------------

_HEALTH_LOCK = threading.Lock()
_FAILED_DEVICES: set[int] = set()

# mesh widths below this bottom out the shrink ladder (the checker then
# demotes to the single-device rungs); a 1-wide "mesh" is no mesh at all
DEFAULT_MESH_MIN_DEVICES = 2


def mark_device_failed(device_id: int) -> None:
    """Records a device as unhealthy: ``auto_mesh`` (and therefore
    every future sharded dispatch) builds over the survivors until
    :func:`reset_device_health`."""
    with _HEALTH_LOCK:
        if device_id in _FAILED_DEVICES:
            return
        _FAILED_DEVICES.add(device_id)
    logger.warning("device %d marked unhealthy; future meshes exclude it",
                   device_id)


def failed_device_ids() -> frozenset:
    with _HEALTH_LOCK:
        return frozenset(_FAILED_DEVICES)


def reset_device_health() -> None:
    """Clears the failed-device set — for tests, and for operators who
    fixed the accelerator (mirrors BackendLadder.reset)."""
    with _HEALTH_LOCK:
        _FAILED_DEVICES.clear()


def mesh_min_devices(value=None) -> int:
    """The shrink ladder's floor: the smallest mesh width worth keeping
    sharded (below it the checker demotes to single-device). Test-map
    knob ``mesh_min_devices`` (``value``), env twin
    ``JEPSEN_TPU_MESH_MIN_DEVICES``, default
    :data:`DEFAULT_MESH_MIN_DEVICES`; never below 2."""
    import os
    n = coerce_devices(value, knob="mesh_min_devices")
    if n is None:
        n = coerce_devices(os.environ.get("JEPSEN_TPU_MESH_MIN_DEVICES"),
                           knob="JEPSEN_TPU_MESH_MIN_DEVICES")
    if n is None:
        n = DEFAULT_MESH_MIN_DEVICES
    return max(2, n)


def _failed_ids_from_exc(exc, known_ids) -> list[int]:
    """Best-effort device attribution for a dispatch failure: device
    ids named in the exception text (``device 3``, ``TPU_5``, ...)
    that exist on this backend. Empty when the error names nothing —
    the shrink path then halves conservatively instead of guessing."""
    if exc is None:
        return []
    import re
    s = f"{type(exc).__name__}: {exc}"
    ids = set()
    for m in re.finditer(r"(?:device|TPU|tpu)[ _:#]*(\d+)", s):
        ids.add(int(m.group(1)))
    return sorted(i for i in ids if i in known_ids)


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def shrink_mesh(mesh, exc=None, min_devices: int | None = None,
                axis: str = "keys"):
    """The surviving mesh after a sharded-dispatch failure, or None
    when shrink bottoms out (fewer healthy devices than the
    ``mesh_min_devices`` floor — the caller demotes to single-device).

    Attribution: device ids named in ``exc`` are marked unhealthy; an
    unattributable failure (most collective errors name nothing)
    conservatively halves the width instead — either way the rebuilt
    mesh is strictly narrower than ``mesh``, so repeated shrinks
    terminate. Widths stay powers of two (the compile caches and the
    cost model's per-width EWMA rates both key on width, so a sparse
    width set keeps them warm). Counts ``mesh_shrink_total{from,to}``."""
    import jax
    cur = list(mesh.devices.flat)
    n_from = len(cur)
    try:
        all_devs = jax.devices()
    except Exception:  # noqa: BLE001 — backend gone entirely
        return None
    named = _failed_ids_from_exc(exc, {d.id for d in all_devs})
    for i in named:
        mark_device_failed(i)
    failed = failed_device_ids()
    healthy = [d for d in all_devs if d.id not in failed]
    if named and any(d.id in named for d in cur):
        # the error named the casualty: keep every survivor it allows
        target = _pow2_floor(min(len(healthy), n_from))
    else:
        # unattributable: drop half the lanes rather than guess wrong
        target = _pow2_floor(max(1, n_from // 2))
    if target >= n_from:
        target = _pow2_floor(max(1, n_from // 2))
    floor = mesh_min_devices(min_devices)
    if target < floor or len(healthy) < target:
        logger.warning("mesh shrink bottomed out (%d healthy, floor %d); "
                       "demoting to single-device", len(healthy), floor)
        return None
    new = auto_mesh(target, axis=axis)
    if new is None or int(new.devices.size) >= n_from:
        return None
    from jepsen_tpu import telemetry
    reg = telemetry.get_registry()
    if reg.enabled:
        reg.counter("mesh_shrink_total",
                    "elastic mesh shrinks after sharded-dispatch "
                    "failures, by width transition",
                    labels=("from", "to")).inc(
            **{"from": str(n_from), "to": str(int(new.devices.size))})
    from jepsen_tpu import trace as trace_mod
    trace_mod.get_tracer().instant(
        trace_mod.TRACK_LADDER, "mesh-shrink",
        args={"from": n_from, "to": int(new.devices.size),
              "error": type(exc).__name__ if exc is not None else None})
    logger.warning("mesh shrunk %d -> %d devices after dispatch failure "
                   "(%s)", n_from, int(new.devices.size),
                   f"{type(exc).__name__}" if exc is not None else
                   "unattributed")
    return new


def probe_device(device) -> bool:
    """One tiny H2D+D2H round trip on a single device — the heal
    probe. True means the device answered; False (any failure) means
    it stays on the unhealthy list."""
    try:
        import jax
        jax.device_get(jax.device_put(np.zeros(8, np.float32), device))
        return True
    except Exception:  # noqa: BLE001 — an unhealable device is just unhealed
        return False


def regrow_mesh(axis: str = "keys", probe=probe_device):
    """The elastic mesh's heal path: re-probe every device marked
    unhealthy, clear the ones that answer, and return the regrown mesh
    — or None when nothing healed (or healing didn't widen a
    power-of-two step, so the working width is unchanged).

    The twin of :func:`shrink_mesh`: shrink reacts to a dispatch
    failure, regrow reacts to the fleet scheduler's periodic heal probe
    (doc/robustness.md "The elastic mesh"). Widths stay powers of two
    for the same reason shrink's do — compile caches and the per-width
    rate EWMAs key on width. Counts ``mesh_regrow_total{from,to}``."""
    import jax
    failed = failed_device_ids()
    if not failed:
        return None
    try:
        all_devs = jax.devices()
    except Exception:  # noqa: BLE001 — backend gone entirely
        return None
    n_from = _pow2_floor(max(1, len(all_devs) - len(failed)))
    healed = [d.id for d in all_devs
              if d.id in failed and probe(d)]
    if not healed:
        return None
    with _HEALTH_LOCK:
        for i in healed:
            _FAILED_DEVICES.discard(i)
    still_failed = failed_device_ids()
    n_to = _pow2_floor(max(1, len(all_devs) - len(still_failed)))
    if n_to <= n_from or n_to < 2:
        return None
    new = auto_mesh(n_to, axis=axis)
    if new is None:
        return None
    from jepsen_tpu import telemetry
    reg = telemetry.get_registry()
    if reg.enabled:
        reg.counter("mesh_regrow_total",
                    "elastic mesh regrows after device heal probes, "
                    "by width transition",
                    labels=("from", "to")).inc(
            **{"from": str(n_from), "to": str(int(new.devices.size))})
    from jepsen_tpu import trace as trace_mod
    trace_mod.get_tracer().instant(
        trace_mod.TRACK_LADDER, "mesh-regrow",
        args={"from": n_from, "to": int(new.devices.size),
              "healed": healed})
    logger.info("mesh regrown %d -> %d devices (healed: %s)",
                n_from, int(new.devices.size), healed)
    return new


def auto_mesh(n_devices: int | None = None, axis: str = "keys"):
    """The cached 1-D mesh a sharded checker dispatch should run over,
    or None when fewer than 2 devices would participate. ``n_devices``
    caps the width (a test-map ``mesh_devices`` knob); the
    ``JEPSEN_TPU_MESH_DEVICES`` env var caps it globally; devices
    marked unhealthy (:func:`mark_device_failed` — the elastic shrink
    path) are excluded. Returning the SAME Mesh object per width keeps
    jitlin's mesh-keyed compile caches warm across dispatches."""
    import jax
    try:
        devs = jax.devices()
    except Exception:  # noqa: BLE001 — no backend: no mesh
        return None
    failed = failed_device_ids()
    if failed:
        devs = [d for d in devs if d.id not in failed]
    n = len(devs)
    if n_devices is not None:
        n = min(n, int(n_devices))
    limit = mesh_devices_limit()
    if limit is not None:
        n = min(n, limit)
    if n < 2:
        return None
    key = (n, axis)
    mesh = _MESH_CACHE.get(key)
    if mesh is None or list(mesh.devices.flat) != devs[:n]:
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devs[:n]), (axis,))
        _MESH_CACHE[key] = mesh
    return mesh


def sharded_enabled() -> bool:
    """Is the sharded checker rung enabled? ``JEPSEN_TPU_SHARDED``
    (default on); the test-map ``checker_sharded`` knob overrides per
    run (checker/linearizable.py coerces it tolerantly)."""
    import os
    raw = os.environ.get("JEPSEN_TPU_SHARDED", "1").strip().lower()
    return raw not in ("0", "false", "no", "off", "")


def sharded_mesh_for(total_events: int, n_devices: int | None = None):
    """The mesh a sharded dispatch should use for ``total_events`` of
    work, or None: sharding disabled, <2 devices, or the cost model says
    the batch is too small to amortize mesh overhead (collective setup,
    divisibility padding, per-device dispatch) — small batches must not
    pay it (see pipeline.CostModel.mesh_route)."""
    if not sharded_enabled():
        return None
    mesh = auto_mesh(n_devices)
    if mesh is None:
        return None
    from jepsen_tpu.parallel import pipeline
    if not pipeline.mesh_route(total_events, int(mesh.devices.size)):
        return None
    return mesh


def shard_leading(mesh, *arrays):
    """Places arrays with their leading axis sharded over the mesh."""
    return shard_chunked(mesh, list(arrays), axis=0)


def shard_chunked(mesh, arrays, axis: int = 0):
    """Per-device transfer lanes: splits each array into contiguous
    per-device blocks along ``axis`` and stages each block onto its own
    device — every ``device_put`` issues that lane's H2D copy
    immediately and asynchronously, so the eight lanes' staging overlaps
    each other AND any in-flight compute (the DispatchPipeline overlap
    discipline, per device) — then assembles the global sharded array
    the shard_map kernels consume without a resharding copy. The sharded
    axis must be a device multiple; jitlin's planner guarantees that by
    padding (never by silently dropping the sharding)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    devs = list(mesh.devices.flat)
    nd = len(devs)
    out = []
    for a in arrays:
        a = np.asarray(a)
        if a.shape[axis] % nd:
            raise ValueError(
                f"axis {axis} length {a.shape[axis]} not divisible by "
                f"{nd} mesh devices — pad upstream (jitlin._matrix_plan /"
                f" parallel.pad_to_multiple)")
        spec = [None] * a.ndim
        spec[axis] = mesh.axis_names[0]
        sharding = NamedSharding(mesh, P(*spec))
        blocks = np.split(a, nd, axis=axis)
        parts = [jax.device_put(b, d) for b, d in zip(blocks, devs)]
        out.append(jax.make_array_from_single_device_arrays(
            a.shape, sharding, parts))
    return out


def pad_to_multiple(batch: dict, multiple: int) -> tuple[dict, int]:
    """Pads the leading (batch) axis of every array in the event batch to a
    multiple of `multiple` with EV_NOOP events. Returns (batch, real_B)."""
    from jepsen_tpu.ops.jitlin import EV_NOOP
    B = batch["kind"].shape[0]
    rem = (-B) % multiple
    if rem == 0:
        return batch, B
    out = {}
    for k, v in batch.items():
        if not isinstance(v, np.ndarray):
            out[k] = v
            continue
        pad_shape = (rem,) + v.shape[1:]
        fill = EV_NOOP if k == "kind" else 0
        out[k] = np.concatenate([v, np.full(pad_shape, fill, v.dtype)])
    return out, B


# How the most recent batch_check on THIS thread settled: "device"
# (single-device matrix/scan kernels), "mesh" (the shard_map multi-device
# path), or "cpu" (the auto-routed native/Python lane).
# Thread-local — Compose runs checkers concurrently under bounded_pmap,
# and a module global would let one thread's route mislabel another's
# results.
_ROUTE = threading.local()


def last_route() -> str:
    """The lane the calling thread's most recent batch_check took."""
    return getattr(_ROUTE, "value", "device")


def batch_check(streams: Sequence, capacity: int = 256, mesh=None,
                step_ids=None, init_state: int = 0, kernel=None,
                accelerator: str = "device", mesh_devices: int | None = None):
    """Checks a batch of per-key event streams, sharded across a device
    mesh when one is available. The single batching implementation —
    JitLinKernel.check/check_batch delegate here.

    Dispatch prefers the key-batched transfer-matrix kernel
    (jitlin.matrix_check_batch) when the whole batch fits its regime —
    all keys advance together in MXU matmuls instead of a latency-bound
    vmapped event scan. With a mesh the matrix path is still taken: its
    chunk axis is sharded across devices (matrix_check_batch handles the
    divisibility bump). The scan serves as the fallback for keys the
    matrix pass leaves undecided (not-alive or inexact).

    ``accelerator``: "device" (default — the historical behavior),
    "cpu" (the exact native/Python lane, bounded-thread-parallel over
    keys), or "auto" — consult the round-trip cost model
    (parallel.pipeline.CostModel) and take the CPU lane when it beats
    the device's dispatch-latency floor (small batches). The
    thread-local ``last_route()`` records which lane
    settled for the calling thread ("cpu" / "device" / "mesh").
    ``mesh_devices`` caps auto-detected mesh width (the test-map knob;
    pass ``mesh=False`` to force single-device, as the multi-process
    path does).

    Returns [(alive, died_event, overflow, peak)] per stream (real keys
    only; padding keys are dropped).
    """
    import jax
    from jepsen_tpu.ops.jitlin import (
        EV_RETURN, MATRIX_MAX_ELEMS, MATRIX_MAX_SLOTS, MATRIX_MAX_STATES,
        MATRIX_MIN_RETURNS, MATRIX_SUB_KEYS, _bucket, matrix_check_batch)

    if kernel is None:
        from jepsen_tpu.ops.jitlin import JitLinKernel
        kernel = JitLinKernel(step_ids=step_ids, init_state=init_state)
    streams = list(streams)
    _ROUTE.value = "device"
    # an explicit mesh is an operator force (checker_sharded: True) —
    # the auto CPU route must not silently override it
    explicit_mesh = mesh is not None and mesh is not False
    if accelerator == "cpu" or (accelerator == "auto"
                                and not explicit_mesh):
        cpu = _cpu_batch_maybe(streams, kernel,
                               force=(accelerator == "cpu"))
        if cpu is not None:
            _ROUTE.value = "cpu"
            return cpu
    # interned-state count selects the exact dense-table kernel when the
    # configuration space 2^S x V is small (jitlin._build_dense_step);
    # every stream must carry an intern table, else a stream with
    # un-interned ids would be misencoded by the dense table
    if all(getattr(s, "intern", None) is not None for s in streams):
        n_states = max(len(s.intern) for s in streams)
    else:
        n_states = None

    # mesh=False forces single-device local execution — the multi-process
    # path (distributed.batch_check_distributed) splits keys BY PROCESS
    # and must not let auto-detection grab the process-spanning global
    # mesh (a process can only address its own devices' shards)
    total_events = sum(len(s.kind) for s in streams)
    if mesh is False:
        mesh = None
    elif mesh is None:
        # cost-gated: a small batch must not pay mesh overhead
        # (collective setup, divisibility padding) — the per-device-count
        # rate model routes it to one device (doc/performance.md);
        # ``mesh_devices`` (the test-map knob) caps the width
        mesh = sharded_mesh_for(total_events, mesh_devices)
    if mesh is not None:
        _ROUTE.value = "mesh"

    S_all = max(max(1, s.n_slots) for s in streams)
    if n_states is not None and S_all <= MATRIX_MAX_SLOTS \
            and n_states <= MATRIX_MAX_STATES:
        mv = (1 << S_all) * _bucket(n_states, floor=8)
        total_returns = sum(int((np.asarray(s.kind) == EV_RETURN).sum())
                            for s in streams)
        # single-device batches split into MATRIX_SUB_KEYS dispatches, so
        # the element budget binds per sub-batch, not the whole key set.
        # A mesh pads keys to a device multiple and holds B/nd per device
        sub = (-(-len(streams) // int(mesh.devices.size))
               if mesh is not None
               else min(len(streams), MATRIX_SUB_KEYS))
        if total_returns >= MATRIX_MIN_RETURNS \
                and sub * mv * mv <= MATRIX_MAX_ELEMS:
            # matrix_check_batch feeds the per-device-count rate model
            # itself (every caller benefits, not just this one)
            results = matrix_check_batch(
                streams, step_ids=kernel.step_ids,
                init_state=kernel.init_state, num_states=n_states,
                mesh=mesh)
            undecided = [i for i, r in enumerate(results)
                         if not r[0] or r[2]]
            if undecided:
                redo = _scan_batch([streams[i] for i in undecided],
                                   capacity, mesh, kernel, n_states)
                results = list(results)
                for i, r in zip(undecided, redo):
                    results[i] = r
            return results

    return _scan_batch(streams, capacity, mesh, kernel, n_states)


def _cpu_batch_maybe(streams, kernel, force: bool = False):
    """The C++/CPU lane for ``accelerator=auto``: when the round-trip
    cost model predicts the device's dispatch-latency floor dominates
    (sub-128-key ``independent`` batches), checks the
    keys exactly on host — native C++ first (ctypes releases the GIL, so
    bounded_pmap runs keys genuinely in parallel), Python stream search
    as the fallback. Returns None when the device lane should run
    (model says so, or the kernel's spec has no Python twin here).
    Measured CPU throughput feeds back into the cost model
    (pipeline.observe_cpu_rate) so routing tracks the actual host."""
    import time

    from jepsen_tpu.parallel import pipeline

    # the host lane runs the CAS-register search (the Python twin
    # honors any init_state; the native C++ lane hardcodes init id 0) —
    # other specs keep the device lane, whose kernels are spec-generic.
    # Every CAS-register spec shares one step function.
    from jepsen_tpu.models import cas_register_step_ids
    if kernel.step_ids is not cas_register_step_ids:
        if force:
            # an EXPLICIT cpu request that can't be honored must not
            # silently become a device dispatch
            logger.warning(
                "accelerator=cpu requested but kernel spec %r has no "
                "host twin in batch_check; using the device lane",
                getattr(kernel.step_ids, "__qualname__", ""))
        return None
    init_state = kernel.init_state
    total_events = sum(len(s.kind) for s in streams)
    if not force and pipeline.auto_route(total_events) != "cpu":
        return None
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.native import check_stream_native
    from jepsen_tpu.utils import bounded_pmap

    def one(stream):
        res = check_stream_native(stream) if init_state == 0 else None
        if res is None or res.valid == "unknown":
            res = check_stream(stream, init_state=init_state)
        return (res.valid is True, res.failed_event, False,
                res.configs_max)

    t0 = time.perf_counter()
    out = bounded_pmap(one, streams)
    pipeline.observe_cpu_rate(total_events, time.perf_counter() - t0)
    return out


def _scan_batch(streams, capacity, mesh, kernel, n_states):
    """The vmapped frontier-scan path: the dense kernel over each key's
    returns, the sparse one over its events (jitlin.scan_inputs)."""
    import jax

    from jepsen_tpu import trace
    from jepsen_tpu.checker.linear_encode import pad_streams
    from jepsen_tpu.ops.jitlin import (EV_RETURN, _bucket, died_events,
                                       scan_inputs)

    lengths = [len(s) for s in streams]
    with trace.phase("dispatch.pad", keys=len(streams),
                     events=sum(lengths)) as span:
        batch = pad_streams(streams, length=_bucket(max(lengths)))
        S = max(1, batch["n_slots"])
        if mesh is not None:
            batch, real_b = pad_to_multiple(batch, mesh.devices.size)
        else:
            real_b = batch["kind"].shape[0]
        arrays, ret_event = scan_inputs(
            *(batch[k] for k in ("kind", "slot", "f", "a", "b")), S,
            n_states)
        if mesh is not None:
            arrays = shard_leading(mesh, *arrays)
        # steps: the scan steps of the whole batch, padding included;
        # returns: the real return steps among them
        span.set(steps=int(arrays[0].size),
                 returns=int((batch["kind"] == EV_RETURN).sum()))

    with trace.phase("dispatch.call") as span:
        fn = kernel._get(S, capacity, batched=True, num_states=n_states)
        compiled = fn._cache_size()
        out = fn(*arrays)
        # a shape key new to the process-wide kernel cache, or a new
        # array shape, traces and lowers the scan again
        span.set(lowered=int(fn._cache_size() > compiled))
    with trace.phase("dispatch.readback"):
        # ONE batched host transfer: each np.asarray is a full device
        # round-trip, so four sequential syncs would quadruple the fixed
        # cost of every batch check
        alive, died, ovf, peak = jax.device_get(out)
    died = died_events(died, ret_event)
    return [(bool(alive[i]), int(died[i]), bool(ovf[i]), int(peak[i]))
            for i in range(real_b)]
