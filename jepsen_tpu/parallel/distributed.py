"""Multi-process device-mesh support: the DCN half of the scaling story
(SURVEY.md §5.8 — the reference scales its checker workers across hosts
with JVM threads + NCCL-style backends; here a multi-host run is N
Python processes under ``jax.distributed``, one global mesh whose
devices span processes, and the SAME shard_map/psum kernels — XLA's
collectives ride ICI within a host and DCN across hosts, no code
change).

One host cannot demonstrate multi-host, so the proof rides CPU: each process forces ``--xla_force_host_platform_device_count=K``
and joins a 2-process coordinator, giving a 2K-device global mesh
(tests/test_distributed.py drives two real OS processes end to end —
the claim "runs under jax.distributed" is executed, not asserted).

Data placement is the only multi-process-specific piece: a process may
only materialize its own devices' shards, so global arrays are built
with ``make_array_from_process_local_data`` from per-process local
shards instead of ``device_put`` of a replicated numpy array.
"""
from __future__ import annotations

import numpy as np


def initialize(coordinator: str, num_processes: int, process_id: int,
               local_devices: int | None = None) -> None:
    """Joins the distributed runtime. Call before any backend use; on
    CPU, set ``local_devices`` to force a virtual device count (the
    XLA_FLAGS knob) for mesh tests without real hardware."""
    import os

    if local_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{local_devices}").strip()
    import jax

    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(axis: str = "edges"):
    """One mesh over every device of every process."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (axis,))


def local_mesh(axis: str = "keys", max_devices: int | None = None):
    """A mesh over THIS process's devices only, or None with fewer than
    two (``max_devices`` caps the width — pass
    parallel.mesh_devices_limit() so the JEPSEN_TPU_MESH_DEVICES global
    disable applies to multi-process runs too). The intra-host half of
    the multi-host decomposition: keys split by process over DCN
    (batch_check_distributed), then each process's slice shards over its
    own devices with the same shard_map kernels — a process can only
    materialize its own devices' shards, so the process-spanning global
    mesh must never be handed to a local batch_check."""
    import jax
    from jax.sharding import Mesh

    devs = jax.local_devices()
    if max_devices is not None:
        devs = devs[:max_devices]
    if len(devs) < 2:
        return None
    return Mesh(np.asarray(devs), (axis,))


def _place_local(mesh, local: np.ndarray):
    """Global sharded array from this process's shard (equal-length
    shards per process; caller pads)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    return jax.make_array_from_process_local_data(sharding, local)


def trim_to_cycles_distributed(n_nodes: int, local_src, local_dst, mesh,
                               max_iters: int = 512) -> np.ndarray:
    """Multi-process twin of ops.scc.trim_to_cycles_sharded: every
    process contributes its LOCAL edge shard (the global edge list is
    their concatenation in process order), the kernel is the shared
    run_sharded_trim — per-device partial degrees, psum-reduced — and
    the replicated activity mask comes back to every process.

    Local shards are padded to a common per-device length with weight-0
    edges; processes must pass equally-sized shards (pad with any node
    id, the weight zeroes it out).
    """
    import jax
    from jepsen_tpu.ops.scc import run_sharded_trim

    local_src = np.asarray(local_src, np.int32)
    local_dst = np.asarray(local_dst, np.int32)
    n_local_dev = len([d for d in mesh.devices.flat
                       if d.process_index == jax.process_index()])
    E = len(local_src)
    pad = (-E) % max(1, n_local_dev)
    sj = _place_local(mesh, np.concatenate(
        [local_src, np.zeros(pad, np.int32)]))
    dj = _place_local(mesh, np.concatenate(
        [local_dst, np.zeros(pad, np.int32)]))
    wj = _place_local(mesh, np.concatenate(
        [np.ones(E, np.int32), np.zeros(pad, np.int32)]))
    out = run_sharded_trim(mesh, n_nodes, sj, dj, wj, max_iters)
    # the mask is replicated (out_specs=P()), so it is fully addressable
    return np.asarray(out)


def localize_keys_distributed(streams, invalid_indices, step_ids=None,
                              step_py=None, init_state: int = 0):
    """Multi-host anomaly localization over an independent key batch
    (the forensics half of :func:`batch_check_distributed`): each
    process localizes the invalid keys of ITS contiguous slice on its
    local devices — ``jitlin.matrix_localize``'s chunk-product bisection
    when the key is in the matrix regime, the exact CPU frontier
    otherwise (``checker.explain.first_failure``) — and the per-key
    first-anomaly positions allgather, so every process returns the full
    ``{key_index: (failed_event, failed_op_index)}`` map. Like the
    verdict gather, the DCN carries only a few ints per key; the
    localization work itself never crosses a process boundary."""
    import jax
    from jax.experimental import multihost_utils

    from jepsen_tpu.checker.explain import first_failure

    streams = list(streams)
    wanted = sorted(int(i) for i in invalid_indices)
    n = len(streams)
    pid, n_proc = jax.process_index(), jax.process_count()
    lo = pid * n // n_proc
    hi = (pid + 1) * n // n_proc
    per = -(-n // n_proc)
    block = np.full((per, 3), -1, np.int64)
    for row, i in enumerate(range(lo, hi)):
        if i not in wanted:
            continue
        try:
            found = first_failure(streams[i], step_ids=step_ids,
                                  step_py=step_py, init_state=init_state)
        except Exception:  # noqa: BLE001 — forensics never fail the batch
            found = None
        if found is not None:
            block[row] = (i, found[0], found[1])
    gathered = np.asarray(
        multihost_utils.process_allgather(block)).reshape(n_proc, per, 3)
    out: dict[int, tuple[int, int]] = {}
    for p in range(n_proc):
        for key, ev, op in gathered[p]:
            if key >= 0:
                out[int(key)] = (int(ev), int(op))
    return out


def batch_check_distributed(streams, capacity: int = 256, kernel=None):
    """Multi-host jepsen.independent: every process checks its contiguous
    slice of the key batch on its LOCAL devices (independent keys are
    embarrassingly parallel, so the DCN carries only verdicts), then the
    per-key results allgather so each process returns the full list —
    the same [(alive, died, overflow, peak)] contract as
    parallel.batch_check.

    This is deliberately not edge-sharded like the trim: per-key
    linearizability has zero cross-key coupling, so the right multi-host
    decomposition is keys-by-process with one tiny collective at the
    end, not a sharded kernel with per-step DCN collectives."""
    import jax
    from jax.experimental import multihost_utils

    from jepsen_tpu.parallel import batch_check

    streams = list(streams)
    n = len(streams)
    pid, n_proc = jax.process_index(), jax.process_count()
    lo = pid * n // n_proc
    hi = (pid + 1) * n // n_proc
    # within the process, the slice may still shard over the LOCAL
    # devices (cost-gated like the single-host path); mesh=False remains
    # the floor so auto-detection can never grab the process-spanning
    # global mesh
    mesh = False
    if hi > lo:
        from jepsen_tpu import parallel
        from jepsen_tpu.parallel import pipeline
        lm = (local_mesh(max_devices=parallel.mesh_devices_limit())
              if parallel.sharded_enabled() else None)
        if lm is not None and pipeline.mesh_route(
                sum(len(s.kind) for s in streams[lo:hi]),
                int(lm.devices.size)):
            mesh = lm
    local = batch_check(streams[lo:hi], capacity=capacity, kernel=kernel,
                        mesh=mesh) if hi > lo else []
    # fixed-size per-process row block (keys aren't perfectly divisible):
    # pad with sentinel rows, mark validity in column 0
    per = -(-n // n_proc)
    block = np.full((per, 5), -1, np.int64)
    for i, (alive, died, ovf, peak) in enumerate(local):
        block[i] = (1, int(bool(alive)), int(died), int(bool(ovf)),
                    int(peak))
    # single-process allgather returns the block unstacked; normalize to
    # the (n_proc, per, 5) layout the unpack below expects
    gathered = np.asarray(
        multihost_utils.process_allgather(block)).reshape(n_proc, per, 5)
    out = []
    for p in range(n_proc):
        for row in gathered[p]:
            if row[0] == 1:
                out.append((bool(row[1]), int(row[2]), bool(row[3]),
                            int(row[4])))
    assert len(out) == n, (len(out), n)
    return out
