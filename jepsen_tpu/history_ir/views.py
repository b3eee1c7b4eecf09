"""Checker views over the history IR: encode once, consume everywhere.

Every checker backend's encoding is a *view* derived from one
:class:`~jepsen_tpu.history_ir.ir.DeviceHistory`, memoized on the IR
instance (``dh.view``), so a multi-checker run (Compose, the analyze
re-check) pays each encode exactly once:

* :func:`register_stream` / :func:`multi_register_stream` — the
  linearizability :class:`~jepsen_tpu.checker.linear_encode.EventStream`
  (``checker.linear_encode`` delegates its module functions here; the
  encoder bodies now live in ONE place).
* :func:`elle_build` / :func:`elle_columns` — the Elle list-append
  builder product (``elle.columnar``'s graph parts and storable
  columns).
* :func:`txn_nodes` — the ok/fail/info node split every elle-style
  checker (list-append Python path, rw-register) starts from.
* :func:`set_full_columns` — the set-full membership matrix the
  setscan kernel consumes (moved out of ``checker.SetFullChecker``).
* :func:`subhistories` — the per-key split ``independent`` checkers
  fan out over.

Device placement of the canonical columns is
:meth:`DeviceHistory.device_columns` (mesh-aware); view products that
feed kernels (event streams, matrix chunks) are staged by the kernels'
own planners, which already pool/pad per device. Functions here must
not round-trip device arrays back to host — the ``no-host-roundtrip``
lint rule enforces that on checker-path code.
"""
from __future__ import annotations

import numpy as np

from jepsen_tpu.checker.linear_encode import EV_INVOKE, EV_RETURN
from jepsen_tpu.history import Intern
from jepsen_tpu.history_ir.ir import DeviceHistory


def _key_of(v) -> str:
    """A stable hashable memo-key fragment for an arbitrary value."""
    try:
        hash(v)
        return v
    except TypeError:
        return repr(v)


# ---------------------------------------------------------------------------
# register event stream (linearizability)
# ---------------------------------------------------------------------------


def encode_register_ops(history, intern: Intern | None = None,
                        encode_args=None):
    """Encodes a single-register r/w/cas history (the reference
    tutorial's etcd workload; BASELINE configs 1-3) into an
    EventStream. THE implementation — ``checker.linear_encode
    .encode_register_ops`` is a thin delegate, and the memoized
    :func:`register_stream` view wraps it for IR consumers.

    Op encodings (f, a, b):
      read v  -> (CAS_F_READ, id(v), 0); a read of None (id 0) matches any state
      write v -> (CAS_F_WRITE, id(v), 0)
      cas [u,v] -> (CAS_F_CAS, id(u), id(v))

    ``encode_args(op) -> (f, a, b)`` overrides the per-op encoding (the
    invoke/completion pairing, slot assignment, and crashed-read
    handling are model-independent — encode_multi_register_ops reuses
    them)."""
    from jepsen_tpu.checker.linear_encode import EventStream
    from jepsen_tpu.models import CAS_F_CAS, CAS_F_READ, CAS_F_WRITE
    if isinstance(history, DeviceHistory):
        history = history.ops
    intern = intern or Intern()
    kinds, slots, fs, as_, bs, idxs = [], [], [], [], [], []
    open_by_process: dict = {}   # process -> (slot, op)
    free_slots: list[int] = []
    next_slot = 0
    n_ops = 0

    if encode_args is None:
        def encode_args(op):
            f, v = op.get("f"), op.get("value")
            if f == "read":
                return CAS_F_READ, intern.id(v), 0
            if f == "write":
                return CAS_F_WRITE, intern.id(v), 0
            if f == "cas":
                u, w = v
                return CAS_F_CAS, intern.id(u), intern.id(w)
            raise ValueError(f"unknown register op {f!r}")

    # First pass: pair invokes with completions; find fail pairs and crashed
    # reads to drop; *complete* invocation values from their returns
    # (knossos history/complete semantics — a read's definitive value
    # arrives with its :ok, but the search consumes it at the invoke event).
    drop = set()
    open_inv: dict = {}
    completed_value: dict[int, object] = {}  # invoke idx -> definitive value
    for i, op in enumerate(history):
        p, typ = op.get("process"), op.get("type")
        if not isinstance(p, int) or p < 0:
            drop.add(i)
            continue
        if typ == "invoke":
            open_inv[p] = i
        elif typ == "fail":
            j = open_inv.pop(p, None)
            if j is not None:
                drop.add(j)
            drop.add(i)
        elif typ == "ok":
            j = open_inv.pop(p, None)
            if j is not None and op.get("value") is not None:
                completed_value[j] = op.get("value")
        elif typ == "info":
            j = open_inv.pop(p, None)
            drop.add(i)  # info completion itself is not an event
            if j is not None and history[j].get("f") == "read":
                drop.add(j)  # crashed reads have no effect
    # ops still open at the end of history (no completion at all) crash too
    for p, j in open_inv.items():
        if history[j].get("f") == "read":
            drop.add(j)

    for i, op in enumerate(history):
        if i in drop:
            continue
        p, typ = op.get("process"), op.get("type")
        if typ == "invoke":
            if free_slots:
                s = free_slots.pop()
            else:
                s = next_slot
                next_slot += 1
            open_by_process[p] = (s, i)
            inv = dict(op)
            if i in completed_value:
                inv["value"] = completed_value[i]
            fcode, a, b = encode_args(inv)
            kinds.append(EV_INVOKE)
            slots.append(s)
            fs.append(fcode)
            as_.append(a)
            bs.append(b)
            idxs.append(i)
            n_ops += 1
        elif typ == "ok":
            got = open_by_process.pop(p, None)
            if got is None:
                continue
            s, j = got
            kinds.append(EV_RETURN)
            slots.append(s)
            fs.append(0)
            as_.append(0)
            bs.append(0)
            idxs.append(i)
            free_slots.append(s)
        # info: no return event — the crashed op's slot stays occupied
        # forever, so it may be linearized at any later point or never.

    return EventStream(
        kind=np.array(kinds, dtype=np.int8),
        slot=np.array(slots, dtype=np.int32),
        f=np.array(fs, dtype=np.int32),
        a=np.array(as_, dtype=np.int32),
        b=np.array(bs, dtype=np.int32),
        op_index=np.array(idxs, dtype=np.int32),
        n_slots=max(next_slot, 1),
        n_ops=n_ops,
        intern=intern,
    )


class _DenseIntern:
    """Stands in for Intern when states are arithmetic encodings rather
    than interned values: only the state-count surface is needed."""

    def __init__(self, n: int):
        self._n = n

    def __len__(self):
        return self._n


def encode_multi_register_ops(history, n_keys: int = 3, n_values: int = 5):
    """Encodes a multi-register txn history (the multi-key-acid workload,
    yugabyte/multi_key_acid.clj) for models.multi_register_spec: one op
    f="txn" whose value is [[f, k, v], ...] packs into base-(2V+2)
    per-key action digits of ``a`` (see the spec for the layout).

    The packed encoding holds one action per key, which covers the
    workload's generators exactly (they draw random nonempty *subsets*
    of the key range, so a txn never touches a key twice); a history
    with repeated keys in one txn raises ValueError and the checker
    falls back to the object-model search."""
    V, K = n_values, n_keys
    AB = 2 * V + 2

    def encode_args(op):
        if op.get("f") != "txn":
            raise ValueError(f"multi-register op must be txn, got "
                             f"{op.get('f')!r}")
        acts = [0] * K
        for f, k, v in op.get("value") or ():
            if not isinstance(k, int) or not (0 <= k < K):
                raise ValueError(f"key {k!r} outside [0, {K})")
            if acts[k] != 0:
                raise ValueError(f"txn touches key {k} twice")
            if f == "r":
                if v is None:
                    acts[k] = 1
                elif isinstance(v, int) and 0 <= v < V:
                    acts[k] = 2 + v
                else:
                    raise ValueError(f"read value {v!r} outside [0, {V})")
            elif f == "w":
                if not (isinstance(v, int) and 0 <= v < V):
                    raise ValueError(f"write value {v!r} outside [0, {V})")
                acts[k] = 2 + V + v
            else:
                raise ValueError(f"unknown micro-op {f!r}")
        a = 0
        for k in reversed(range(K)):
            a = a * AB + acts[k]
        return 0, a, 0

    stream = encode_register_ops(history, encode_args=encode_args)
    # interned-state count for kernel selection: the whole map space
    stream.intern = _DenseIntern((V + 1) ** K)
    return stream


def register_stream(dh: DeviceHistory, init_value=None):
    """The memoized register EventStream view. ``init_value`` (the
    model's initial register value) interns FIRST so its id is the
    kernel's init state — the memo is keyed on it."""
    def build():
        intern = Intern()
        if init_value is not None:
            intern.id(init_value)
        return encode_register_ops(dh.ops, intern=intern)
    return dh.view(("register-stream", _key_of(init_value)), build)


def multi_register_stream(dh: DeviceHistory, n_keys: int, n_values: int):
    """The memoized multi-register EventStream view, or None when the
    history falls outside the packed encoding (checker wgl-falls-back)."""
    def build():
        try:
            return encode_multi_register_ops(dh.ops, n_keys, n_values)
        except ValueError:
            return None
    return dh.view(("multi-register-stream", n_keys, n_values), build)


# ---------------------------------------------------------------------------
# elle (list-append) views
# ---------------------------------------------------------------------------


def elle_build(dh: DeviceHistory):
    """The memoized Elle dependency-graph build product
    ((graph, txns, extras, n_keys) — ``elle.columnar._build``), or None
    when the history is outside the integer columnar regime."""
    def build():
        from jepsen_tpu.elle import columnar
        try:
            return columnar._build(dh.ops)
        except (TypeError, ValueError, OverflowError):
            return None
    return dh.view(("elle-build",), build)


def elle_columns(dh: DeviceHistory):
    """The memoized storable Elle builder columns
    (``elle.columnar.parse_columns``), or None when not storable."""
    def build():
        from jepsen_tpu.elle import columnar
        return columnar.parse_columns(dh.ops)
    return dh.view(("elle-columns",), build)


def txn_nodes(dh: DeviceHistory) -> tuple[list, list, list]:
    """The memoized (oks, fails, infos) op split every elle-style
    checker starts from (list-append's Python builder, rw-register)."""
    def build():
        oks = [op for op in dh.ops if op.get("type") == "ok"
               and isinstance(op.get("process"), int)]
        fails = [op for op in dh.ops if op.get("type") == "fail"]
        infos = [op for op in dh.ops if op.get("type") == "info"
                 and isinstance(op.get("process"), int)]
        return oks, fails, infos
    return dh.view(("txn-nodes",), build)


# ---------------------------------------------------------------------------
# set-full membership columns (checker.SetFullChecker's device path)
# ---------------------------------------------------------------------------


def set_full_columns(history) -> dict:
    """The set-full checker's device encoding: every element's
    add-invoke/add-ok times plus the reads x elements membership matrix
    the setscan kernel classifies. Moved here from
    ``checker.SetFullChecker._check_device`` so the encode is an IR
    view (memoized per run) instead of a per-checker pass.

    Returns ``{"member", "read_t", "invoke_t", "ok_t", "has_ok",
    "els"}`` — or ``{"error": ...}`` when the set was never read."""
    from jepsen_tpu.history import Intern as _Intern
    if isinstance(history, DeviceHistory):
        history = history.ops

    intern = _Intern()
    invoke_t: list[float] = []
    ok_t: list[float] = []
    has_ok: list[bool] = []
    has_invoke: list[bool] = []

    def el_slot(v):
        i = intern.id(v) - 1  # id 0 is the None sentinel
        while len(invoke_t) <= i:
            invoke_t.append(0.0)
            ok_t.append(0.0)
            has_ok.append(False)
            has_invoke.append(False)
        return i

    reads: list[tuple[float, object]] = []  # (invoke time, raw payload)
    pending_read_invokes: dict = {}

    # -- adds: vectorized first-invoke / last-ok per element --------
    # the per-event Python walk dominated the host side of this
    # checker at bench scale; for the universal all-int regime the
    # same semantics (invoke_t = first add event's time, ok_t =
    # last ok's — el_slot's exact behavior) fall out of masked
    # first/last-occurrence joins. Non-int elements keep the loop.
    nh = len(history)
    # cheap gate first: the columnar path serves only all-int add
    # values, and a non-int history must not pay for mask building
    fast = any(op.get("f") == "add" for op in history) and \
        all(type(op.get("value")) is int for op in history
            if op.get("f") == "add")
    scan = range(nh)
    if fast:
        fs = [op.get("f") for op in history]
        typs = [op.get("type") for op in history]
        add_m = np.fromiter((f == "add" for f in fs), bool, nh)
        inv_m = np.fromiter((t == "invoke" for t in typs), bool, nh)
        ok_m = np.fromiter((t == "ok" for t in typs), bool, nh)
        add_pos = np.nonzero(add_m & (inv_m | ok_m))[0]
        fast = add_pos.size > 0
    if fast:
        add_idx = add_pos.tolist()
        t_add = np.fromiter(
            (float(history[i].get("time", i)) for i in add_idx),
            np.float64, add_pos.size)
        va = np.asarray([history[i].get("value") for i in add_idx],
                        np.int64)
        uniq, first_idx, inverse = np.unique(
            va, return_index=True, return_inverse=True)
        order = np.argsort(first_idx)
        rank = np.empty(order.size, np.int64)
        rank[order] = np.arange(order.size)
        el_ids = rank[inverse]
        for v in uniq[order].tolist():
            intern.id(v)   # same table the read fallback consults
        E_fast = int(uniq.size)
        _, first_per_el = np.unique(el_ids, return_index=True)
        ok_arr = np.zeros(E_fast)
        has_ok_arr = np.zeros(E_fast, bool)
        ok_sel = np.nonzero(ok_m[add_pos])[0]
        if ok_sel.size:
            el_ok = el_ids[ok_sel][::-1]
            t_ok = t_add[ok_sel][::-1]
            u_ok, last_rev = np.unique(el_ok, return_index=True)
            ok_arr[u_ok] = t_ok[last_rev]
            has_ok_arr[u_ok] = True
        invoke_t = t_add[first_per_el].tolist()
        ok_t = ok_arr.tolist()
        has_ok = has_ok_arr.tolist()
        has_invoke = [True] * E_fast
        # only the (few) read events still walk in Python
        read_m = np.fromiter((f == "read" for f in fs), bool, nh)
        scan = np.nonzero(read_m & (inv_m | ok_m))[0].tolist()
    for i in scan:
        op = history[i]
        f, typ, v, p = (op.get("f"), op.get("type"), op.get("value"),
                        op.get("process"))
        if f == "add":
            t = float(op.get("time", i))
            j = el_slot(v)
            if typ == "invoke" and not has_invoke[j]:
                invoke_t[j] = t
                has_invoke[j] = True
            elif typ == "ok":
                ok_t[j] = t
                has_ok[j] = True
                if not has_invoke[j]:  # ok with no invoke (CPU parity)
                    invoke_t[j] = t
                    has_invoke[j] = True
        elif f == "read":
            t = float(op.get("time", i))
            if typ == "invoke":
                pending_read_invokes[p] = t
            elif typ == "ok":
                t0 = pending_read_invokes.pop(p, t)
                reads.append((t0, v))
    if not reads:
        return {"error": "Set was never read"}
    E = len(invoke_t)
    reads.sort(key=lambda rv: rv[0])
    member = np.zeros((len(reads), max(E, 1)), dtype=bool)
    # Columnar fast path for the common set workload (integer
    # elements): map each read payload to element columns with one
    # sorted-array searchsorted instead of a per-element dict walk —
    # the membership matrix build is the device path's host-side cost
    # and must not dominate the kernel it feeds. Elements a read
    # mentions that were never added are ignored on both paths.
    uv_sorted = uv_order = None
    vals = intern.table[1:E + 1]
    if E and all(type(x) is int for x in vals):
        uv = np.asarray(vals, np.int64)
        uv_order = np.argsort(uv)
        uv_sorted = uv[uv_order]
    for r, (_, vs) in enumerate(reads):
        if uv_sorted is not None:
            try:
                arr = np.asarray(vs if type(vs) is list else list(vs))
            except (TypeError, ValueError, OverflowError):
                arr = None
            # signed-int dtype only: asarray would silently coerce
            # floats ('2.5' -> 2) or parse digit strings, making a
            # read "contain" elements it never mentioned
            if arr is not None and arr.ndim == 1 \
                    and arr.dtype.kind == "i":
                arr = arr.astype(np.int64)
                pos = np.clip(np.searchsorted(uv_sorted, arr), 0, E - 1)
                hit = uv_sorted[pos] == arr
                member[r, uv_order[pos[hit]]] = True
                continue
        for v in set(vs):
            j = intern.id(v) - 1
            if 0 <= j < E:
                member[r, j] = True
    return {
        "member": member[:, :max(E, 1)],
        "read_t": np.array([t for t, _ in reads], dtype=np.float32),
        "invoke_t": np.array(invoke_t, dtype=np.float32),
        "ok_t": np.array(ok_t, dtype=np.float32),
        "has_ok": np.array(has_ok, dtype=bool),
        "els": [intern.value(j + 1) for j in range(E)],
    }


def set_membership(dh: DeviceHistory) -> dict:
    """The memoized set-full membership view."""
    return dh.view(("set-full",), lambda: set_full_columns(dh.ops))


# ---------------------------------------------------------------------------
# independent (key-lifted) views
# ---------------------------------------------------------------------------


def subhistories(dh: DeviceHistory) -> tuple[list, dict]:
    """The memoized ``(keys, {frozen_key: sub_history})`` split the
    independent checker fans out over — computed once per run even when
    several composed checkers lift the same history."""
    def build():
        from jepsen_tpu import independent
        return independent.split_by_key(dh.ops)
    return dh.view(("subhistories",), build)
