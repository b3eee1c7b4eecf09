"""The device-resident columnar history IR: :class:`DeviceHistory`.

One canonical struct-of-arrays encoding of a run's history, produced
once and consumed zero-copy by every checker backend (ROADMAP item 3;
the Arrow-style one-layout-many-consumers stance). It *promotes*
:class:`jepsen_tpu.history.ColumnarHistory` — same packed int columns
(type/process/f/time/index plus the invocation pairing) — and adds:

* a **value-id column** + value :class:`~jepsen_tpu.history.Intern`
  table, so workload values are dense int32 ids the kernels can consume
  without a per-checker re-interning pass;
* **memoized views** (:meth:`DeviceHistory.view`): each checker derives
  its encoding (register event stream, Elle builder columns, set
  membership matrix, per-key sub-histories — see
  :mod:`jepsen_tpu.history_ir.views`) from the IR exactly once per run;
  a second checker over the same history pays ~nothing;
* **device placement** (:meth:`DeviceHistory.device_columns`): the
  canonical columns staged onto the accelerator — single-device or
  padded + sharded over a :func:`jepsen_tpu.parallel.auto_mesh` mesh
  via the per-device transfer lanes — and cached per mesh. The
  checker kernels today consume IR-derived *views* (event streams,
  Elle columns) whose planners stage per device themselves; this is
  the placement surface for consumers that want the raw columns
  device-resident (guarded by the ``no-host-roundtrip`` lint rule).

The builder half (incremental, streamed from the PR-3 WAL) lives in
:mod:`jepsen_tpu.history_ir.builder`; the ``.npz`` sidecar
serialization in :mod:`jepsen_tpu.history_ir.sidecar`.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Callable, Sequence

import numpy as np

from jepsen_tpu.history import ColumnarHistory, Intern

#: canonical packed-int column names, in sidecar order
CANONICAL_COLUMNS = ("types", "processes", "fs", "times", "indices",
                     "completion_of", "invocation_of", "value_ids")


class ValueIntern(Intern):
    """Intern specialized for op *values*: unhashable values (lists —
    the universal op-value shape: cas pairs, txn micro-ops) key by a
    repr freeze like the base class, but the TABLE keeps the original
    value, so ``value(id)`` returns what the op actually carried and
    the sidecar's codec round-trip is faithful (the base class stores
    the marker tuple itself, which is fine for f-name interning but
    lossy for values)."""

    def id(self, v) -> int:
        try:
            i = self._ids.get(v)
            key = v
        except TypeError:  # unhashable: freeze the key, keep the value
            key = ("__unhashable__", repr(v))
            i = self._ids.get(key)
        if i is None:
            i = len(self.table)
            self._ids[key] = i
            self.table.append(v)
        return i


@dataclass
class DeviceHistory(ColumnarHistory):
    """ColumnarHistory promoted to the one shared checker IR.

    All base columns keep their dtypes and semantics; ``value_ids``
    interns every op's ``value`` (id 0 = None) into ``intern``. Views
    and device placements are memoized on the instance — build the IR
    once per run (``history_ir.of``) and every checker shares it.
    """

    value_ids: np.ndarray | None = None  # int32 into intern
    intern: Intern = field(default_factory=ValueIntern)
    _views: dict = field(default_factory=dict, repr=False, compare=False)
    _lock: threading.RLock = field(default_factory=threading.RLock,
                                   repr=False, compare=False)

    @classmethod
    def from_ops(cls, history: Sequence[dict],
                 intern: Intern | None = None) -> "DeviceHistory":
        dh = super().from_ops(history)
        dh.intern = intern or ValueIntern()
        vid = dh.intern.id
        dh.value_ids = np.fromiter((vid(v) for v in dh.values),
                                   np.int32, len(dh.values))
        return dh

    # -- memoized views --------------------------------------------------

    def view(self, key, build: Callable):
        """The memoized derived view for ``key`` (any hashable), built
        by ``build()`` exactly once. Concurrent checkers (Compose's
        bounded_pmap) serialize on the first build and then share the
        product — this is the "encode once, every checker a view"
        contract. A ``build`` that raises caches nothing."""
        with self._lock:
            if key not in self._views:
                self._views[key] = build()
            return self._views[key]

    def view_keys(self) -> tuple:
        with self._lock:
            return tuple(self._views)

    # -- device placement ------------------------------------------------

    def device_columns(self, mesh=None) -> tuple[dict, int]:
        """The canonical int columns resident on device, memoized per
        mesh: ``(arrays, n_real)``. With ``mesh=None`` every column is
        staged whole onto the default device; with a mesh the op axis
        is padded to a device multiple and sharded over the per-device
        transfer lanes (:func:`jepsen_tpu.parallel.shard_chunked`), so
        mesh consumers read their shard without a resharding copy.
        Padding rows are all-zero with process/pairing -1 (no checker
        semantics: consumers slice to ``n_real``)."""
        key = ("__device__", None if mesh is None
               else (int(mesh.devices.size), tuple(mesh.axis_names)))
        return self.view(key, lambda: self._place(mesh))

    def _place(self, mesh) -> tuple[dict, int]:
        import jax

        from jepsen_tpu import parallel
        n = len(self)
        cols = {name: getattr(self, name) for name in CANONICAL_COLUMNS}
        if mesh is None:
            return {k: jax.device_put(v) for k, v in cols.items()}, n
        nd = int(mesh.devices.size)
        rem = (-n) % nd
        if rem:
            pad = {"processes": -1, "completion_of": -1,
                   "invocation_of": -1}
            cols = {k: np.concatenate(
                        [v, np.full(rem, pad.get(k, 0), v.dtype)])
                    for k, v in cols.items()}
        placed = parallel.shard_chunked(mesh, list(cols.values()))
        return dict(zip(cols, placed)), n
