"""TPU just-in-time-linearization kernel.

Replaces the reference's CPU-bound knossos linear/wgl searches (invoked at
jepsen/src/jepsen/checker.clj:199-203) with a fixed-shape XLA program:

* A *configuration* is (mask, state): ``mask`` = bitset over pending-op
  slots that have already been linearized; ``state`` = interned model state.
* Sparse kernel (``_build_step``): the frontier of live configurations is
  a capacity-K array pair, and events stream through a ``lax.scan``:
  invokes update the per-slot op table; before consuming each return, the
  closure of the frontier under "linearize any pending, unlinearized op"
  is computed by masked batched expansion ([K, S] candidate grid through
  the model's int transition) and sort-based dedup (two lexicographic
  ``lax.sort`` passes), then configs that failed to linearize the
  returning op are killed. The frontier is monotone within a closure, so
  convergence is detected by count; overflow beyond K makes a False
  verdict "unknown" (a surviving subset is still a sound witness for
  True).
* Dense kernel (``_build_dense_step``, small 2^S x V spaces): the
  frontier is an exact boolean table, and the scan steps over *returns*,
  not events: the pending sets and op tables depend on the event stream
  alone, so a host pre-pass (``scan_inputs``) hands each return step its
  pending set and op table, and every step runs one closure and one
  kill, with no per-event branch.

Both kernels vmap over a batch of per-key histories — the
jepsen.independent -> vmap mapping (SURVEY.md §2.6, BASELINE config 3).

Shapes are static in (E or R, S, K|V): pad E via linear_encode.pad_streams
and bucket history lengths (return counts) so XLA caches compilations.
"""
from __future__ import annotations

import logging
import os
import threading
import time
from functools import partial

import numpy as np

logger = logging.getLogger("jepsen.jitlin")

# Host/device phase split of the calling thread's most recent
# matrix_check_batch call (prepass / grids / dispatch / fetch seconds) —
# bench.py folds these into the matrix-kernel attribution fields the way
# elle's bench reads columnar.LAST_PHASE_SECONDS. Thread-local:
# concurrent checkers under bounded_pmap must not read each other's
# split (or trip over a mid-update clear()).
_PHASE = threading.local()


def last_phase_seconds() -> dict:
    """The calling thread's most recent matrix dispatch phase split."""
    return dict(getattr(_PHASE, "value", {}))


def publish_phase_seconds(phases: dict) -> None:
    """Re-publishes a phase split into THIS thread's slot. The checker's
    degradation ladder runs device dispatches on a watchdog worker
    thread; it captures the split there and re-publishes on the
    dispatching thread so ``last_phase_seconds()`` keeps answering for
    the thread that owns the check."""
    _PHASE.value = dict(phases)


# Most recent dispatch routing of the calling thread: which kernel
# representation ran the chunk products ("f32"/"int8"/"packed", or
# "scan" for the XLA path) and which combine ("fused"/"tree") — the
# per-variant labels bench.py attaches to its phase/roofline fields.
_DISPATCH_INFO = threading.local()


def last_dispatch_info() -> dict:
    """{'variant': ..., 'combine': ...} of the calling thread's most
    recent matrix dispatch (empty before the first one)."""
    return dict(getattr(_DISPATCH_INFO, "value", {}))


# Per-thread routing overrides (the test-map/opts knobs `matrix_variant`
# and `combine_fused`, plumbed by checker/linearizable.py): a pinned
# variant demotes down the probe order when it can't run — PR-3
# semantics — and `combine_fused=False` pins the tree combine.
_OVERRIDE = threading.local()


def _dispatch_overrides() -> tuple:
    return (getattr(_OVERRIDE, "variant", None),
            getattr(_OVERRIDE, "fused", None))


class _routing_overrides:
    """Context manager scoping (variant, fused) overrides to one
    matrix_check_batch call on this thread."""

    def __init__(self, variant, fused):
        self._new = (variant, fused)

    def __enter__(self):
        self._old = _dispatch_overrides()
        _OVERRIDE.variant, _OVERRIDE.fused = self._new

    def __exit__(self, *exc):
        _OVERRIDE.variant, _OVERRIDE.fused = self._old


def _env_int(name: str, default: int) -> int:
    """Env-int knob that degrades to its default on malformed values
    (a bad sweep variable must not make the module unimportable)."""
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        logger.warning("ignoring malformed %s=%r", name,
                       os.environ.get(name))
        return default

SENTINEL_MASK = np.uint32(0xFFFFFFFF)
SENTINEL_STATE = np.int32(0x7FFFFFFF)

EV_INVOKE, EV_RETURN, EV_NOOP = 0, 1, 2


def _build_step(num_slots: int, capacity: int, step_ids, init_state: int,
                max_closure_iters: int | None = None):
    import jax
    import jax.numpy as jnp
    from jax import lax

    S, K = num_slots, capacity
    closure_iters = max_closure_iters or S
    slot_bits = (jnp.uint32(1) << jnp.arange(S, dtype=jnp.uint32))

    def count_valid(mask):
        return jnp.sum((mask != SENTINEL_MASK).astype(jnp.int32))

    def dedup_compact(all_mask, all_state):
        """Sort, drop duplicates, move valid entries to the front, keep K."""
        m, st = lax.sort((all_mask, all_state), num_keys=2, is_stable=False)
        dup = jnp.concatenate([
            jnp.zeros((1,), dtype=bool),
            (m[1:] == m[:-1]) & (st[1:] == st[:-1]),
        ])
        m = jnp.where(dup, SENTINEL_MASK, m)
        st = jnp.where(dup, SENTINEL_STATE, st)
        m, st = lax.sort((m, st), num_keys=2, is_stable=False)
        overflow = m[K] != SENTINEL_MASK if m.shape[0] > K else jnp.bool_(False)
        return m[:K], st[:K], overflow

    def closure(mask, state, pend_mask, cur_f, cur_a, cur_b):
        """Expands the frontier to its closure under linearizing any pending,
        unlinearized op. Early-exits when the config count stops growing."""

        def body(carry):
            mask, state, _, count, overflow, it = carry
            valid = mask != SENTINEL_MASK
            can = (
                valid[:, None]
                & ((pend_mask & slot_bits) != 0)[None, :]
                & ((mask[:, None] & slot_bits[None, :]) == 0)
            )
            st2, ok = step_ids(state[:, None], cur_f[None, :], cur_a[None, :], cur_b[None, :])
            good = can & ok
            new_mask = jnp.where(good, mask[:, None] | slot_bits[None, :], SENTINEL_MASK)
            new_state = jnp.where(good, st2, SENTINEL_STATE)
            all_mask = jnp.concatenate([mask, new_mask.reshape(-1)])
            all_state = jnp.concatenate([state, new_state.reshape(-1)])
            m, st, ovf = dedup_compact(all_mask, all_state)
            c2 = count_valid(m)
            return m, st, c2 > count, c2, overflow | ovf, it + 1

        def cond(carry):
            _, _, changed, _, _, it = carry
            return changed & (it < closure_iters)

        init = (mask, state, jnp.bool_(True), count_valid(mask), jnp.bool_(False),
                jnp.int32(0))
        mask, state, _, count, overflow, _ = lax.while_loop(cond, body, init)
        return mask, state, count, overflow

    def step_event(carry, ev):
        (mask, state, cur_f, cur_a, cur_b, pend_mask, alive, died_at,
         overflow, peak, eidx) = carry
        kind, slot, f, a, b = ev
        slot_bit = jnp.uint32(1) << slot.astype(jnp.uint32)

        def on_invoke(_):
            return (mask, state, cur_f.at[slot].set(f), cur_a.at[slot].set(a),
                    cur_b.at[slot].set(b), pend_mask | slot_bit, alive,
                    died_at, overflow, peak, eidx + 1)

        def on_return(_):
            m, st, count, ovf = closure(mask, state, pend_mask, cur_f, cur_a, cur_b)
            # keep configs that linearized the returning op; clear its bit
            # (sentinel entries have all bits set — exclude them explicitly)
            has = (m != SENTINEL_MASK) & ((m & slot_bit) != 0)
            m2 = jnp.where(has, m & ~slot_bit, SENTINEL_MASK)
            st2 = jnp.where(has, st, SENTINEL_STATE)
            m2, st2, _ = dedup_compact(
                jnp.concatenate([m2, jnp.full((S,), SENTINEL_MASK, jnp.uint32)]),
                jnp.concatenate([st2, jnp.full((S,), SENTINEL_STATE, jnp.int32)]),
            )
            now_alive = count_valid(m2) > 0
            new_died = jnp.where(alive & ~now_alive, eidx, died_at)
            return (m2, st2, cur_f, cur_a, cur_b, pend_mask & ~slot_bit,
                    alive & now_alive, new_died, overflow | ovf,
                    jnp.maximum(peak, count), eidx + 1)

        def on_noop(_):
            return (mask, state, cur_f, cur_a, cur_b, pend_mask, alive,
                    died_at, overflow, peak, eidx + 1)

        new_carry = lax.switch(kind, [on_invoke, on_return, on_noop], None)
        return new_carry, None

    def scan_from(mask0, state0, events):
        carry = (
            mask0, state0,
            jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32),
            jnp.uint32(0), jnp.bool_(True), jnp.int32(-1), jnp.bool_(False),
            jnp.int32(1), jnp.int32(0),
        )
        carry, _ = lax.scan(step_event, carry, events)
        (mask, state, _, _, _, _, alive, died_at, overflow, peak, _) = carry
        return mask, state, alive, died_at, overflow, peak

    def run(kind, slot, f, a, b):
        mask0 = jnp.full((K,), SENTINEL_MASK, dtype=jnp.uint32)
        mask0 = mask0.at[0].set(jnp.uint32(0))
        state0 = jnp.full((K,), SENTINEL_STATE, dtype=jnp.int32)
        state0 = state0.at[0].set(jnp.int32(init_state))
        events = (kind.astype(jnp.int32), slot.astype(jnp.int32),
                  f.astype(jnp.int32), a.astype(jnp.int32), b.astype(jnp.int32))
        _, _, alive, died_at, overflow, peak = scan_from(mask0, state0, events)
        return alive, died_at, overflow, peak

    def run_resume(kind, slot, f, a, b, mask0, state0):
        """Segmented-verification variant: starts from a prior segment's
        frontier (masks are all-zero at a quiescent cut, so only states
        carry meaning) and returns the final frontier with the verdict."""
        events = (kind.astype(jnp.int32), slot.astype(jnp.int32),
                  f.astype(jnp.int32), a.astype(jnp.int32), b.astype(jnp.int32))
        mask, state, alive, died_at, overflow, peak = scan_from(
            mask0, state0, events)
        return alive, died_at, overflow, peak, mask, state

    run.resume = run_resume
    run.init_frontier = lambda: (
        np.concatenate([np.zeros(1, np.uint32),
                        np.full(K - 1, SENTINEL_MASK, np.uint32)]),
        np.concatenate([np.asarray([init_state], np.int32),
                        np.full(K - 1, SENTINEL_STATE, np.int32)]))
    return run


def _build_dense_step(num_slots: int, num_states: int, step_ids,
                      init_state: int):
    """Exact dense-table variant of the scan.

    When per-key concurrency S and the interned state count V are small —
    the jepsen.independent regime, where per-key histories are kept short
    and values few — the *entire* configuration space is only
    ``2^S masks x V states``. The frontier then lives in a dense boolean
    table T[2^S, V] instead of a capacity-K list: closure under
    "linearize any pending op" becomes S batched boolean matmuls
    ``T[r ^ bit_t] @ M_t`` (per-slot [V, V] transition matrices, bf16 on
    the MXU with f32 accumulation) OR-reduced into T, iterated to a
    fixpoint. No sorts, no dedup, and — because the table covers the
    whole space — no capacity overflow: the verdict is always exact.

    The scan steps over returns (``scan_inputs``' dense form): each step
    builds its [S, V, V] slot matrices from the return's op table, runs
    the closure and the kill, and a padding step (slot -1) leaves the
    carry as it is. Invokes and no-ops cost no step, so under ``vmap``
    — where a batched ``lax.switch`` would run every branch at every
    step — a key pays one closure per return, not one per padded event.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    S, V = num_slots, num_states
    M = 1 << S
    # row index tables: r ^ bit_t (the donor/receiver row permutation per
    # slot) and whether bit_t is set in r
    xor_idx = jnp.asarray(np.arange(M)[None, :] ^ (1 << np.arange(S))[:, None])
    has_bit = jnp.asarray(
        ((np.arange(M)[None, :] >> np.arange(S)[:, None]) & 1).astype(bool))
    v_range = jnp.arange(V, dtype=jnp.int32)

    def slot_matrix(f, a, b):
        """One slot's [V, V] transition matrix, plus an out-of-range flag:
        a step_ids whose states aren't dense intern ids would otherwise be
        silently misencoded — flag it so the verdict degrades to unknown
        instead of a confidently wrong exact answer."""
        st2, ok = step_ids(v_range, f, a, b)
        oob = (ok & ((st2 < 0) | (st2 >= V))).any()
        mt = ok[:, None] & (st2[:, None] == v_range[None, :])
        return mt.astype(jnp.bfloat16), oob  # [V, V]

    def slot_matrices(ops, pend):
        """The op table's [S, V, V] slot matrices, and whether a pending
        slot's transition leaves the intern range."""
        mt, oob = jax.vmap(slot_matrix)(ops[:, 0], ops[:, 1], ops[:, 2])
        return mt, (oob & pend).any()

    def closure(table, pend, mt, go):
        gate = pend[:, None] & has_bit  # [S, M]: rows that may receive via t

        def body(carry):
            t, _, it = carry
            donors = t[xor_idx]  # [S, M, V]
            contrib = jnp.einsum(
                "smv,svw->smw", donors.astype(jnp.bfloat16), mt,
                preferred_element_type=jnp.float32) > 0
            t2 = t | (contrib & gate[:, :, None]).any(axis=0)
            return t2, (t2 != t).any(), it + 1

        def cond(carry):
            _, changed, it = carry
            return changed & (it < S)

        # ``go`` false (a padding step) runs no iteration at all
        table, _, _ = lax.while_loop(
            cond, body, (table, go, jnp.int32(0)))
        return table

    def step_return(carry, ret):
        table, alive, died_at, peak, inexact, ridx = carry
        slot, pend, ops = ret
        is_ret = slot >= 0
        mt, oob = slot_matrices(ops, pend)
        tc = closure(table, pend, mt, is_ret)
        # keep configs that linearized the returning op, clearing its
        # bit: T'[r] = (s not in r) & Tc[r | bit_s]
        s = jnp.maximum(slot, 0)
        t2 = jnp.where(~has_bit[s][:, None], tc[xor_idx[s]], False)
        t2 = jnp.where(is_ret, t2, table)
        now_alive = t2.any()
        new_died = jnp.where(alive & ~now_alive, ridx, died_at)
        count = jnp.sum(tc.astype(jnp.int32))
        peak = jnp.where(is_ret, jnp.maximum(peak, count), peak)
        return (t2, alive & now_alive, new_died, peak, inexact | oob,
                ridx + 1), None

    def scan_from(table0, slot, pend, ops, tail_pend, tail_ops):
        carry = (table0, jnp.bool_(True), jnp.int32(-1), jnp.int32(1),
                 jnp.bool_(False), jnp.int32(0))
        carry, _ = lax.scan(step_return, carry,
                            (slot.astype(jnp.int32), pend.astype(bool),
                             ops.astype(jnp.int32)))
        table, alive, died_at, peak, inexact, _ = carry
        # ops invoked after the last return enter no closure, but their
        # out-of-range flags count as every invoked op's do
        _, tail_oob = slot_matrices(tail_ops.astype(jnp.int32),
                                    tail_pend.astype(bool))
        return table, alive, died_at, peak, inexact | tail_oob

    def run(slot, pend, ops, tail_pend, tail_ops):
        """One step per return (``scan_inputs``' dense form); the died
        index is the return step."""
        table0 = jnp.zeros((M, V), dtype=bool).at[0, init_state].set(True)
        _, alive, died_at, peak, inexact = scan_from(
            table0, slot, pend, ops, tail_pend, tail_ops)
        # the table covers the whole config space, so the only inexactness
        # is a state id escaping the intern range — surfaced on the
        # overflow channel so verdict() degrades to unknown, not wrong
        return alive, died_at, inexact, peak

    def run_resume(slot, pend, ops, tail_pend, tail_ops, table0):
        """Segmented-verification variant: starts from a caller-supplied
        frontier table (a previous segment's output — the stream must be
        cut at quiescent points, i.e. no ops pending across the cut, so
        each segment's op table starts empty) and returns the final table
        alongside the verdict, staying on device between segments."""
        table, alive, died_at, peak, inexact = scan_from(
            table0, slot, pend, ops, tail_pend, tail_ops)
        return alive, died_at, inexact, peak, table

    def init_table():
        t = np.zeros((M, V), bool)
        t[0, init_state] = True
        return t

    run.resume = run_resume
    run.init_table = init_table
    return run


def _returns_prepass_batch(kind, slot, f, a, b, S: int, r_pad: int):
    """Host pre-pass over a [B, E] event batch: the per-slot op table and
    pending mask evolve deterministically from the event stream alone
    (invokes/returns), independent of the frontier — so each return's
    (pending set, op table, returning slot) is computable up front.

    Fully vectorized (numpy passes over [B, S, E], no per-event or
    per-key Python) so the prepass doesn't dominate the kernel it feeds:
    per slot t, the pending bit at a return i is ``last invoke of t <= i
    > last return of t < i`` (running maxima of t's positions), and the
    current op is t's last invoke.

    Returns, over each key's returns padded to ``r_pad``: the returning
    slot (-1 past the key's last return), the pending set [S], the op
    table [S, 3] and the return's event index (-1 past the last); then
    the pending set and op table after the key's last event (the ops
    still pending at its end)."""
    kind = np.asarray(kind)
    slot = np.asarray(slot)
    B, E = kind.shape
    # [B, 1 + E, 3]: row 0 is op 0, the op of a slot not yet invoked
    fabs = np.stack([np.asarray(f), np.asarray(a), np.asarray(b)], axis=-1)
    fabs = np.concatenate([np.zeros((B, 1, 3), fabs.dtype), fabs], axis=1)
    is_inv = kind == EV_INVOKE
    is_ret = kind == EV_RETURN
    # (key, event) of every return, key-major, and its rank in its key
    kb, ke = np.nonzero(is_ret)
    kr = np.cumsum(is_ret, axis=1)[kb, ke] - 1
    r_slot = np.full((B, r_pad), -1, np.int32)
    r_slot[kb, kr] = slot[kb, ke]
    ret_event = np.full((B, r_pad), -1, np.int64)
    ret_event[kb, kr] = ke
    r_pend = np.zeros((B, r_pad, S), bool)
    r_ops = np.zeros((B, r_pad, S, 3), fabs.dtype)
    if E == 0:
        return (r_slot, r_pend, r_ops, ret_event, np.zeros((B, S), bool),
                np.zeros((B, S, 3), fabs.dtype))
    # every slot at once: [B, S, E] one-hot invokes and returns
    on = slot[:, None, :] == np.arange(S, dtype=slot.dtype)[None, :, None]
    pos = np.arange(1, E + 1, dtype=np.int32)

    def last(mask):
        """1-based position of each row's latest True at or before each
        event (0: none yet)."""
        return np.maximum.accumulate(np.where(mask, pos, 0), axis=2)

    inv = last(on & is_inv[:, None, :])
    ret = last(on & is_ret[:, None, :])
    # an invoke sets its slot pending and a return clears it; a return
    # sees the pending set from before it, its own slot included (the op
    # being linearized-and-killed)
    before = np.concatenate([np.zeros((B, S, 1), np.int32), ret[..., :-1]],
                            axis=2)
    r_pend[kb, kr] = (inv > before)[kb, :, ke]
    tail_pend = inv[..., -1] > ret[..., -1]
    # current op of slot t: its last invoke, as a row of fabs (0 where t
    # was never invoked)
    r_ops[kb, kr] = fabs[kb[:, None], inv[kb, :, ke]]
    tail_ops = fabs[np.arange(B)[:, None], inv[..., -1]]
    return r_slot, r_pend, r_ops, ret_event, tail_pend, tail_ops


def _returns_prepass(kind, slot, f, a, b):
    """The matrix kernel's pre-pass: :func:`_returns_prepass_batch` of
    one stream. Returns numpy arrays over its R return events (returning
    slot, pending set, op table) and the slot count S."""
    kind = np.asarray(kind)
    slot = np.asarray(slot)
    S = int(slot.max(initial=0)) + 1
    R = int((kind == EV_RETURN).sum())
    r_slot, r_pend, r_ops, *_ = _returns_prepass_batch(
        kind[None], slot[None], np.asarray(f)[None], np.asarray(a)[None],
        np.asarray(b)[None], S, R)
    return r_slot[0], r_pend[0], r_ops[0], S


def scan_inputs(kind, slot, f, a, b, S: int, num_states: int | None):
    """The frontier scan's inputs for a padded [B, E] event batch, and
    the map from the scan's step index back to the event index (None:
    they are the same). The sparse kernel steps over the events as they
    are. The dense kernel steps over returns: each key's returns, padded
    to ``_bucket(R_max, floor=16)`` steps with returning slot -1, with
    their pending sets and op tables, plus the ops still pending at the
    key's end (their out-of-range flags count, though no closure sees
    them)."""
    if not _dense_ok(S, num_states):
        return (kind, slot, f, a, b), None
    kind = np.asarray(kind)
    R_max = int((kind == EV_RETURN).sum(axis=1).max(initial=0))
    r_slot, r_pend, r_ops, ret_event, tail_pend, tail_ops = \
        _returns_prepass_batch(kind, slot, f, a, b, S,
                               _bucket(R_max, floor=16))
    return ((r_slot, r_pend, r_ops.astype(np.int32), tail_pend,
             tail_ops.astype(np.int32)), ret_event)


def died_events(died, ret_event):
    """The scan's died step(s) as event indices: ``died`` [B] (or a
    scalar, with ``ret_event`` [R]) through :func:`scan_inputs`' map;
    -1 stays -1."""
    died = np.asarray(died)
    if ret_event is None:
        return died
    idx = np.maximum(died, 0)[..., None]
    ev = np.take_along_axis(ret_event, idx, axis=-1)[..., 0]
    return np.where(died >= 0, ev, -1)


def receiver_kill_tables(S: int, V: int):
    """The transfer-matrix operators' static bit tables — ONE source of
    truth shared by the XLA scan kernel and the pallas kernel
    (ops/pallas_matrix.py expands these into matrix form):

    - receiver [S, M, M] f32: R_t[r | bit_t, r] = 1 for slots t not in
      mask r (the mask-receiver map of linearizing pending op t)
    - kill_idx [S, MV] i32 / kill_mask [S, MV] f32: the
      closure-then-kill row gather+mask for a return on slot s
    """
    M = 1 << S
    MV = M * V
    r = np.arange(M)
    receiver = np.zeros((S, M, M), np.float32)
    for t in range(S):
        src = r[((r >> t) & 1) == 0]
        receiver[t, src | (1 << t), src] = 1.0
    rows = np.arange(MV)
    rr, ww = rows // V, rows % V
    kill_idx = np.zeros((S, MV), np.int32)
    kill_mask = np.zeros((S, MV), np.float32)
    for s in range(S):
        ok = ((rr >> s) & 1) == 0
        kill_idx[s] = np.where(ok, (rr | (1 << s)) * V + ww, 0)
        kill_mask[s] = ok.astype(np.float32)
    return receiver, kill_idx, kill_mask


def _kernel_math(S: int, V: int, step_ids, G: int):
    """Trace-time math shared by the single-device transfer-matrix
    kernel and its shard_map mesh twin: the static receiver/kill
    tables, the boolean-matmul helpers, the per-scan-step operator
    build, and the chunk-product combiners. ``G`` is the chunk count
    one scan step advances — the global count on a single device, a
    per-device block under shard_map. Everything downstream of the
    chunk layout is built HERE exactly once, which is what keeps mesh
    and single-device verdicts bit-identical: both paths compose the
    same 0/1 operators with the same thresholded bf16 products (every
    intermediate is exactly 0/1, so any association of the boolean
    matrix product yields the same matrix)."""
    import types

    import jax
    import jax.numpy as jnp

    M = 1 << S
    MV = M * V

    receiver, kill_idx, kill_mask = receiver_kill_tables(S, V)
    n_sq = 0
    while (1 << n_sq) < S:
        n_sq += 1
    receiver_j = jnp.asarray(receiver, jnp.bfloat16)
    kill_idx_j = jnp.asarray(kill_idx)
    kill_mask_j = jnp.asarray(kill_mask, jnp.bfloat16)
    eye = jnp.eye(MV, dtype=jnp.bfloat16)
    v_range = jnp.arange(V, dtype=jnp.int32)

    def bmm(x, y):
        # bf16 accumulation is sound for the >0 test: every addend is
        # non-negative, so rounding can never produce a spurious zero (a
        # positive sum stays positive) nor a spurious positive — and the
        # bf16 output halves the HBM traffic of these [G, MV, MV]
        # intermediates, which is what bounds the step
        out = jnp.einsum("gij,gjk->gik", x, y,
                         preferred_element_type=jnp.bfloat16)
        return (out > 0).astype(jnp.bfloat16)

    def uop_tables(uops):
        """[U, 3] distinct-op table -> [U, V, V] transition matrices
        (computed once per run, gathered per step) + [U] oob flags."""
        def one(fab):
            st2, ok = step_ids(v_range, fab[0], fab[1], fab[2])
            # INVARIANT: transitions leaving [0, V) are DROPPED (the
            # equality below can't match), under-approximating
            # reachability — so alive=True with oob set proves nothing
            # and callers must treat it as unknown, never as valid. The
            # oob flag is how that escape is surfaced.
            oob = (ok & ((st2 < 0) | (st2 >= V))).any()
            return (ok[:, None] & (st2[:, None] == v_range[None, :])), oob
        mt, oob = jax.vmap(one)(uops)
        return mt.astype(jnp.bfloat16), oob

    def make_step(mt_tab, oob_tab):
        def step(carry, inp):
            P, inexact = carry
            pend_g, ids_g, s_g, val_g = inp
            mt = mt_tab[ids_g]                   # [G, S, V, V] gather
            oob = oob_tab[ids_g]                 # [G, S]
            gated = pend_g.astype(jnp.bfloat16)
            # row = (receiver mask a, NEW state w); col = (source mask b,
            # OLD state v): L[(a,w),(b,v)] = Σ_t pend_t R_t[a,b] M_t[v,w]
            # (bf16 accumulation: ≤ S non-negative addends, see bmm)
            L = jnp.einsum("gt,tab,gtvw->gawbv", gated, receiver_j, mt,
                           preferred_element_type=jnp.bfloat16)
            Bm = ((L.reshape(G, MV, MV) + eye[None]) > 0).astype(jnp.bfloat16)
            for _ in range(n_sq):
                Bm = bmm(Bm, Bm)                 # (I+L)^(2^k) → closure
            A = jax.vmap(lambda m, idx, msk: m[idx] * msk[:, None])(
                Bm, kill_idx_j[s_g], kill_mask_j[s_g])
            A = jnp.where(val_g[:, None, None], A, eye[None])
            return (bmm(A, P),
                    inexact | (oob & pend_g & val_g[:, None]).any(axis=1)), None
        return step

    def chain_time(seq):
        """[n, MV, MV] time-ordered chunk products -> their composed
        product (later chunk on the LEFT), via the same pairing tree as
        make_combine so every intermediate is a thresholded 0/1
        matrix."""
        while seq.shape[0] > 1:        # static n: unrolls at trace time
            odd = seq[-1:] if seq.shape[0] % 2 else None
            pairs = seq[:-1] if odd is not None else seq
            out = jnp.einsum("nij,njk->nik", pairs[1::2], pairs[0::2],
                             preferred_element_type=jnp.bfloat16)
            seq = (out > 0).astype(jnp.bfloat16)
            if odd is not None:
                seq = jnp.concatenate([seq, odd], axis=0)
        return seq[0]

    def make_combine(B: int, C: int, init_state: int):
        def _combine(P, inexact, tot0):
            # chain each key's C chunk products in time order: chunks are
            # chunk-major per key, so total_b = P[b,C-1] @ ... @ P[b,0] @ tot0.
            # Tree-reduced: boolean matrix product is associative, so pairing
            # neighbors per level ((P1@P0), (P3@P2), ...) computes the same
            # 0/1 product in ceil(log2 C) levels of BATCHED matmuls instead
            # of C sequential [B, MV, MV] products — the old fori_loop chain
            # was C dependent tiny matmuls of pure launch latency (256 of
            # them on the single-dispatch bench config).
            def bmm_pairs(hi, lo):
                out = jnp.einsum("bnij,bnjk->bnik", hi, lo,
                                 preferred_element_type=jnp.bfloat16)
                return (out > 0).astype(jnp.bfloat16)

            seq = P.reshape(B, C, MV, MV)
            while seq.shape[1] > 1:        # static C: unrolls at trace time
                odd = seq[:, -1:] if seq.shape[1] % 2 else None
                pairs = seq[:, :-1] if odd is not None else seq
                # later chunk on the LEFT: product order is preserved
                seq = bmm_pairs(pairs[:, 1::2], pairs[:, 0::2])
                if odd is not None:
                    seq = jnp.concatenate([seq, odd], axis=1)
            total = (jnp.einsum("bij,bjk->bik", seq[:, 0],
                                tot0.astype(jnp.bfloat16),
                                preferred_element_type=jnp.bfloat16)
                     > 0).astype(jnp.bfloat16)
            alive = (total[:, :, init_state] > 0).any(axis=1)
            return alive, inexact.reshape(B, C).any(axis=1), total
        return _combine

    return types.SimpleNamespace(
        M=M, MV=MV, n_sq=n_sq, eye=eye, v_range=v_range,
        receiver_j=receiver_j, kill_idx_j=kill_idx_j,
        kill_mask_j=kill_mask_j, bmm=bmm, uop_tables=uop_tables,
        make_step=make_step, chain_time=chain_time,
        make_combine=make_combine)


def _build_matrix_kernel(S: int, V: int, step_ids, init_state: int,
                         g_steps: int, n_chunks: int, n_keys: int = 1):
    """Block-composed transfer-matrix variant of the dense scan.

    For each return event, closure-then-kill is a *linear* boolean
    operator on the flattened [2^S * V] table: closure is (I+L)^S where
    L = sum_t pend_t * (R_t ⊗ M_t) (R_t the static mask-receiver map for
    slot t, M_t the op's [V, V] transition), computable with
    ceil(log2 S) boolean matrix squarings; kill is a row gather+mask.
    Composing the per-return matrices A_i is associative, so chunks of
    the history multiply *in parallel* (one lax.scan whose every step
    advances all chunks by one return — [G, MV, MV] batched matmuls on
    the MXU) and the G chunk products combine at the end. Sequential
    depth falls from one step per event to one per chunk-row, which is
    what makes a single long history fast on TPU; the event-by-event
    dense scan remains the exact-diagnostics path (died-at event, peak).

    With ``n_keys`` = B > 1, the same chunk axis also carries a batch of
    independent per-key histories (the jepsen.independent regime): chunk
    g = b * n_chunks + c holds key b's c-th slice of returns, every scan
    step advances all B x C chunks with one [G, MV, MV] MXU matmul, and
    the final combine chains each key's C chunk products separately.
    This replaces the latency-bound vmapped event scan with dense batched
    matmul work — sequential depth per key falls from E events to
    T = g_steps.

    Host→device traffic is kept minimal:
    the host interns the batch's distinct (f, a, b) ops into a table of
    ``n_uops`` entries, each op's [V, V] transition matrix is built ONCE
    on device, and the per-return op tables arrive as small int32 id
    grids gathered against that table each step.

    Boolean products ride bf16 inputs with f32 accumulation (counts
    <= MV = 2^S * V <= 2^12 are exact in f32) and a >0 threshold.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    B, C, T = n_keys, n_chunks, g_steps
    G = B * C

    # static tables + step/combine math (shared with the mesh twin —
    # see _kernel_math; the pallas kernel shares the bit tables via
    # receiver_kill_tables)
    math = _kernel_math(S, V, step_ids, G)
    MV, eye = math.MV, math.eye
    uop_tables = math.uop_tables
    make_step = math.make_step
    _combine = math.make_combine(B, C, init_state)

    # --- stage 1: per-chunk products ([G, MV, MV] bf16 + inexact) -----
    # The products and the combine are SEPARATE dispatches: the chunk
    # products materialize in HBM between the scan and the combine
    # either way, and the split lets the fused streaming combine (and
    # its tree fallback) pair with ANY products source — XLA scan or
    # any pallas kernel variant — without a cross-product of jits.

    def _scan_products(pend, op_ids, uops, slots, valid):
        mt_tab, oob_tab = uop_tables(uops)
        P0 = jnp.broadcast_to(eye, (G, MV, MV))
        (P, inexact), _ = lax.scan(make_step(mt_tab, oob_tab),
                                   (P0, jnp.zeros((G,), bool)),
                                   (pend, op_ids, slots, valid))
        return P, inexact

    scan_products = jax.jit(_scan_products)

    def _scan_total(pend, op_ids, uops, slots, valid, tot0):
        """The pre-split single-jit scan + tree combine: the fallback
        dispatch when neither a pallas products variant nor the fused
        combine is active (e.g. the CPU backend). One compile and one
        dispatch, exactly the old profile — the split stages below only
        engage when a pallas stage actually replaces one of them."""
        P, inexact = _scan_products(pend, op_ids, uops, slots, valid)
        return _combine(P, inexact, tot0)

    scan_total = jax.jit(_scan_total)

    _pallas_jits: dict = {}

    def pallas_products(variant: str):
        """The jitted products stage through one pallas kernel variant
        (the T-step chunk product fused into ONE program per chunk, P
        VMEM-resident across all its returns — ops/pallas_matrix.py).
        The oob → inexact reduction runs on the small id grids outside
        the kernel; boolean results are bit-identical to the scan path
        (exact accumulation of 0/1 addends, thresholded per product,
        whatever the operand representation)."""
        fn = _pallas_jits.get(variant)
        if fn is None:
            @jax.jit
            def fn(pend, op_ids, uops, slots, valid):
                from jepsen_tpu.ops import pallas_matrix
                mt_tab, oob_tab = uop_tables(uops)
                kfn = pallas_matrix.chunk_product(
                    S, V, T, uops.shape[0], variant=variant)
                mtT = jnp.transpose(mt_tab, (0, 2, 1)).astype(jnp.float32)
                P = kfn(pend, op_ids, mtT, slots, valid)
                inexact = (oob_tab[op_ids] & pend
                           & valid[..., None]).any(axis=(0, 2))
                return P, inexact
            _pallas_jits[variant] = fn
        return fn

    # --- stage 2: the chunk-product combine ---------------------------
    # donating the tot0 carry lets XLA compose chained resume segments'
    # [B, MV, MV] operator products in place. Kept as a SEPARATE
    # wrapper: a failed fused-combine dispatch already received tot0, so
    # its tree retry must never donate (use-after-donate), and the CPU
    # backend can't honor donation at all (it would warn per call).
    from jepsen_tpu.parallel.pipeline import donate_ok

    def _tree_combine(P, inexact, tot0):
        return _combine(P, inexact, tot0)

    combine_tree = jax.jit(_tree_combine)
    combine_tree_donate = (jax.jit(_tree_combine, donate_argnums=(2,))
                           if donate_ok() else combine_tree)
    scan_total_donate = (jax.jit(_scan_total, donate_argnums=(5,))
                         if donate_ok() else scan_total)

    @jax.jit
    def combine_fused(P, inexact, tot0):
        """The fused streaming combine: each key's C chunk products
        stream through HBM exactly once into a VMEM-resident running
        product (pallas_matrix._build_combine), instead of the tree's
        ceil(log2 C) levels of [B, C_l, MV, MV] HBM round-trips.
        Bit-identical: boolean matrix products are exact under any
        association."""
        from jepsen_tpu.ops import pallas_matrix
        cfn = pallas_matrix.combine_product(B, C, MV)
        total = cfn(P.reshape(B, C, MV, MV), tot0.astype(jnp.bfloat16))
        alive = (total[:, :, init_state] > 0).any(axis=1)
        return alive, inexact.reshape(B, C).any(axis=1), total

    synced_shapes: set = set()

    def _sync_first(key, out):
        # jitted dispatch is async: a Mosaic RUNTIME fault (vs the
        # lowering faults the probes catch) would otherwise surface at
        # the caller's readback, outside the dispatch try. Deterministic
        # per compiled shape, so force one sync on each shape's first
        # execution and keep later dispatches pipelined.
        if key not in synced_shapes:
            import jax
            jax.block_until_ready(out)
            synced_shapes.add(key)

    def _dispatch_total(pend, op_ids, uops, slots, valid, tot0):
        from jepsen_tpu.ops import pallas_matrix

        force_variant, force_fused = _dispatch_overrides()
        info = {"variant": "scan", "combine": "tree"}
        U = int(uops.shape[0])
        fused_want = (force_fused if force_fused is not None
                      else pallas_matrix.fuse_combine_mode())
        use_fused = (fused_want is not False
                     and pallas_matrix.combine_enabled(MV))
        prod = None
        while True:
            variant = pallas_matrix.best_variant(S, V, force=force_variant)
            if variant is None:
                break
            try:
                # warm the kernel cache (and the hbm-pretile probe)
                # OUTSIDE the jit trace below
                pallas_matrix.chunk_product(S, V, T, U, variant=variant)
                out_p = pallas_products(variant)(pend, op_ids, uops,
                                                 slots, valid)
                _sync_first((pend.shape, uops.shape, variant), out_p)
                prod = out_p
                info["variant"] = variant
                break
            except Exception:  # noqa: BLE001 — lowering/runtime failure
                logger.warning("pallas matrix variant %r failed at %s; "
                               "demoting", variant, (S, V, T),
                               exc_info=True)
                pallas_matrix.disable(S, V, variant)
                # loop: best_variant now yields the next representation
        if prod is not None or use_fused:
            if prod is None:
                prod = scan_products(pend, op_ids, uops, slots, valid)
            P, inexact = prod
            if use_fused:
                try:
                    out = combine_fused(P, inexact, tot0)
                    _sync_first((pend.shape, "combine"), out)
                    info["combine"] = "fused"
                    _DISPATCH_INFO.value = info
                    return out
                except Exception:  # noqa: BLE001
                    logger.warning("fused combine failed at MV=%d; "
                                   "using the tree combine", MV,
                                   exc_info=True)
                    pallas_matrix.disable_combine(MV)
                    _DISPATCH_INFO.value = info
                    # tot0 was handed to the failed fused dispatch —
                    # the non-donating wrapper is mandatory
                    return combine_tree(P, inexact, tot0)
            _DISPATCH_INFO.value = info
            return combine_tree_donate(P, inexact, tot0)
        # neither pallas stage is active (e.g. the CPU fallback): the
        # pre-split single-jit path — one compile, one dispatch,
        # donation as before. The combine_tree_donate return above is
        # mutually exclusive with this line (both RETURN), so tot0 is
        # never read after its donation on any one control path — the
        # line-based rule can't see the early returns, hence the
        # waiver.
        _DISPATCH_INFO.value = info
        return scan_total_donate(pend, op_ids, uops, slots, valid,
                                 tot0)  # lint: ignore[donation-reuse]

    def run(pend, op_ids, uops, slots, valid):
        """pend [T,G,S]; op_ids [T,G,S] (indices into uops [U,3]);
        slots [T,G]; valid [T,G], with chunk g = key * C + chunk.
        Returns (alive[B], inexact[B])."""
        alive, inexact, _ = _dispatch_total(pend, op_ids, uops, slots, valid,
                                       jnp.broadcast_to(eye, (B, MV, MV)))
        return alive, inexact

    def run_resume(pend, op_ids, uops, slots, valid, tot0):
        """Segmented-verification variant: ``tot0`` [B, MV, MV] is the
        composed operator product of the previous segments (block
        composition is associative, so chaining segment products equals
        one monolithic run provided segments cut at quiescent points —
        the per-segment prepass assumes no pending ops at entry).
        Returns (alive, inexact, total) with total staying on device."""
        return _dispatch_total(pend, op_ids, uops, slots, valid, tot0)

    run.resume = run_resume
    # bf16 identity: the carry dtype must match scan_total's output or
    # the second chained segment retraces (and recompiles) mid-run
    run.init_total = lambda: jnp.broadcast_to(
        jnp.eye(MV, dtype=jnp.bfloat16), (B, MV, MV))
    # the jitted stages _dispatch_total picks from, for ahead-of-time
    # compiles (tests/test_tpu_compile.py)
    run.stages = {"scan_total": scan_total, "products": pallas_products,
                  "combine_fused": combine_fused}
    return run


def _build_matrix_kernel_mesh(S: int, V: int, step_ids, init_state: int,
                              g_steps: int, n_chunks: int, n_keys: int,
                              mesh):
    """shard_map twin of _build_matrix_kernel over a device mesh.

    Two sharding modes, both built from the SAME step/combine math
    (_kernel_math) so mesh and single-device verdicts are bit-identical:

    * ``n_keys == 1`` — the segmented scale path / one long history:
      the chunk axis (C time-ordered chunks of T returns) shards over
      the mesh. Each device scans its CONTIGUOUS time span of chunks
      ([C/nd, MV, MV] local products), chains them locally, and the nd
      span products tree-combine device-side after one small
      ``all_gather`` ([nd, MV, MV] — the only collective). The composed
      total applies ``tot0`` and replicates, ready to carry into the
      next round. Exposes ``resume`` + ``init_total`` like the
      single-device kernel.
    * ``n_keys > 1`` — the jepsen.independent key batch: the key axis
      shards (the dispatch pads B to a device multiple upstream), each
      device runs the full scan + per-key combine for its own keys with
      ZERO cross-device traffic, and the per-key verdicts all_gather at
      the end — B bools over ICI instead of a host-side shard walk.

    Collectives unavailable (backend without mesh support) surface as
    dispatch exceptions; the checker ladder's ``sharded`` rung demotes
    to the single-device kernels rather than failing (checker/ladder.py,
    doc/robustness.md)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    nd = int(mesh.devices.size)
    ax = mesh.axis_names[0]
    B, C, T = n_keys, n_chunks, g_steps
    if B == 1:
        if C % nd:
            raise ValueError(
                f"chunk count {C} not divisible by {nd} devices: "
                f"_matrix_plan must pad the chunk axis first")
        G_local = C // nd
    else:
        if B % nd:
            raise ValueError(
                f"key count {B} not divisible by {nd} devices: "
                f"_matrix_dispatch must pad the key axis first")
        B_local = B // nd
        G_local = B_local * C
    math = _kernel_math(S, V, step_ids, G_local)
    MV, eye = math.MV, math.eye

    def local_products(pend, op_ids, uops, slots, valid):
        """This device's chunk block through the scan: [G_local, MV, MV]
        chunk products + per-chunk inexact flags."""
        mt_tab, oob_tab = math.uop_tables(uops)
        P0 = jnp.broadcast_to(eye, (G_local, MV, MV))
        (prod, inexact), _ = lax.scan(math.make_step(mt_tab, oob_tab),
                                      (P0, jnp.zeros((G_local,), bool)),
                                      (pend, op_ids, slots, valid))
        return prod, inexact

    if B == 1:
        def seg_total(pend, op_ids, uops, slots, valid, tot0):
            prod, inexact = local_products(pend, op_ids, uops, slots, valid)
            span = math.chain_time(prod)         # this device's time span
            # device order IS time order (contiguous chunk blocks), so
            # the gathered spans chain with the same later-on-the-LEFT
            # tree as the single-device combine
            spans = lax.all_gather(span, ax)     # [nd, MV, MV]
            total = math.chain_time(spans.astype(jnp.bfloat16))
            total = (jnp.einsum("ij,jk->ik", total,
                                tot0[0].astype(jnp.bfloat16),
                                preferred_element_type=jnp.bfloat16)
                     > 0).astype(jnp.bfloat16)
            alive = (total[:, init_state] > 0).any()
            ix = lax.psum(inexact.any().astype(jnp.int32), ax) > 0
            return alive[None], ix[None], total[None]

        fn = jax.jit(shard_map(
            seg_total, mesh=mesh,
            in_specs=(P(None, ax, None), P(None, ax, None), P(),
                      P(None, ax), P(None, ax), P()),
            out_specs=(P(), P(), P()), check_vma=False))

        def run(pend, op_ids, uops, slots, valid):
            alive, inexact, _ = fn(pend, op_ids, uops, slots, valid,
                                   run.init_total())
            return alive, inexact

        run.resume = fn
        run.init_total = lambda: jnp.broadcast_to(
            jnp.eye(MV, dtype=jnp.bfloat16), (1, MV, MV))
        return run

    combine = math.make_combine(B_local, C, init_state)

    def key_verdicts(pend, op_ids, uops, slots, valid):
        prod, inexact = local_products(pend, op_ids, uops, slots, valid)
        alive, ix, _ = combine(prod, inexact,
                               jnp.broadcast_to(eye, (B_local, MV, MV)))
        # gather so every device holds the full per-key verdict vector:
        # the caller's readback touches one shard instead of walking nd
        # (device order = key-block order, so the reshape restores the
        # original key order)
        return (lax.all_gather(alive, ax).reshape(-1),
                lax.all_gather(ix, ax).reshape(-1))

    run = jax.jit(shard_map(
        key_verdicts, mesh=mesh,
        in_specs=(P(None, ax, None), P(None, ax, None), P(),
                  P(None, ax), P(None, ax)),
        out_specs=(P(), P()), check_vma=False))
    return run


# matrix-path applicability: cost is quadratic in MV = 2^S * V (each
# return becomes an [MV, MV] operator), so the value domain must be small
# — the realistic register regime (a handful of distinct values), not
# arbitrary histories. Below MIN_RETURNS the event scan's sequential
# depth is short enough that composing matrices can't pay for itself.
MATRIX_MAX_SLOTS = 8
MATRIX_MAX_STATES = 16
MATRIX_MIN_RETURNS = 2000
# per-step [G, MV, MV] f32 intermediates: cap G * MV^2 (~1 GB at f32)
MATRIX_MAX_ELEMS = 1 << 28
# keys per dispatch: G = B*C beyond ~256 goes HBM-bound superlinearly,
# so bigger key batches pipeline as bounded sub-dispatches. 128 measured
# ~10% faster than 256 at both 256 and 1024 keys on the r05 chip —
# smaller dispatches overlap their transfers with compute better while
# C=2 keeps G at the ~256 sweet spot
MATRIX_SUB_KEYS = 128
# sub-batch size for mid-size key batches (33..128 keys): small enough
# that 2-4 dispatches pipeline host prep against device compute, large
# enough that each still fills the chunk-count target. Env-tunable for
# on-chip sweeps without an edit-recompile loop.
MATRIX_PIPELINE_KEYS = _env_int("JEPSEN_TPU_PIPELINE_KEYS", 32)
# dispatches in flight before the pipeline's delayed blocking kicks in
# (bounds the [G, MV, MV] working sets resident on device at once)
PIPELINE_DEPTH = _env_int("JEPSEN_TPU_PIPELINE_DEPTH", 2)
# events per segment of a resumable matrix chain (matrix_check_segmented
# / the checker's segmented matrix rung): also the routing threshold —
# streams longer than one segment take the resumable chain so a crash
# or demotion mid-check keeps its completed segments
MATRIX_SEGMENT_EVENTS = _env_int("JEPSEN_TPU_MATRIX_SEGMENT_EVENTS",
                                 1 << 20)


def matrix_ok(S: int, num_states: int | None, n_returns: int) -> bool:
    return (num_states is not None and S <= MATRIX_MAX_SLOTS
            and num_states <= MATRIX_MAX_STATES
            and n_returns >= MATRIX_MIN_RETURNS)


def matrix_check(stream, step_ids=None, init_state: int = 0,
                 num_states: int | None = None, force: bool = False,
                 mesh=None, variant: str | None = None,
                 combine_fused: bool | None = None):
    """Fast exact-aliveness check of ONE history via block-composed
    transfer matrices. Returns (alive, died, overflow, peak) with
    died=-1/peak=0 placeholders — callers that need the failing event or
    frontier stats re-run the event scan (only relevant when not alive).
    Returns None when the matrix regime doesn't apply (``force=True``
    skips the size gate, for differential tests). With a ``mesh`` the
    chunk axis shards over the devices (the checker ladder's ``sharded``
    rung passes parallel.auto_mesh()). ``variant`` pins the kernel
    representation and ``combine_fused`` the combine path for this call
    (both probe-gated, demote-not-fail — doc/performance.md "Packed
    boolean kernels")."""
    if step_ids is None:
        step_ids = _default_step_ids()
    num_states = num_states if num_states is not None else len(stream.intern)
    kind, slot = np.asarray(stream.kind), np.asarray(stream.slot)
    # gate BEFORE the O(E) prepass: everything the gate needs is
    # computable from cheap array reductions
    S = int(slot.max(initial=0)) + 1
    R = int((kind == EV_RETURN).sum())
    if not force and not matrix_ok(S, num_states, R):
        return None
    return matrix_check_batch([stream], step_ids=step_ids,
                              init_state=init_state,
                              num_states=num_states, mesh=mesh,
                              variant=variant,
                              combine_fused=combine_fused)[0]


def matrix_check_resume(stream, tot0=None, step_ids=None,
                        init_state: int = 0, num_states: int | None = None,
                        n_slots: int | None = None, mesh=None,
                        variant: str | None = None,
                        combine_fused: bool | None = None):
    """Segmented transfer-matrix verification of one long history: checks
    a segment starting from the composed operator product ``tot0`` of the
    prior segments (None = identity) and returns
    ``(alive, inexact, total)`` with ``total`` staying on device for the
    next segment. Block composition is associative, so chaining segment
    products equals one monolithic run — provided segments cut at
    quiescent points (the per-segment prepass assumes no pending ops at
    entry; see quiescent_cuts) and share the slot dimension (pass
    ``n_slots`` to pin S across segments whose own concurrency differs).

    This is the scale path for long SMALL-DOMAIN histories: each return
    costs one [MV, MV] composition on the MXU instead of a sequential
    frontier step, and the carry is a single [MV, MV] product.

    Segments must also share the STATE basis: pass ``num_states`` (and
    build segment streams against one interning scheme) so every
    segment's value ids mean the same thing — tot0 is checked against
    the resulting operator dimension and a mismatch raises rather than
    composing over a permuted basis.

    With a ``mesh`` the segment's chunk axis shards over the devices
    (each device scans a contiguous time span, the span products
    tree-combine device-side after one [nd, MV, MV] all_gather — see
    _build_matrix_kernel_mesh). The carry is the same replicated
    [1, MV, MV] product either way, so a chain may freely mix sharded
    and single-device segments (the ladder's sharded→device demotion
    mid-chain is sound)."""
    if step_ids is None:
        step_ids = _default_step_ids()
    if num_states is None:
        num_states = len(stream.intern)
    V = _bucket(num_states, floor=8)
    prep = _returns_prepass(np.asarray(stream.kind), np.asarray(stream.slot),
                            np.asarray(stream.f), np.asarray(stream.a),
                            np.asarray(stream.b))
    S = max(n_slots or 1, prep[3])
    if tot0 is not None and tot0.shape[-1] != (1 << S) * V:
        raise ValueError(
            f"carry dimension {tot0.shape[-1]} != (1<<{S})*{V}: segments "
            f"must share n_slots and num_states")
    R_max = prep[0].shape[0]
    if R_max == 0:
        # no returns in this segment: the chain's aliveness is whatever
        # the carried product says (a dead chain must not revive)
        if tot0 is None:
            return True, False, tot0
        alive = (np.asarray(tot0)[:, :, init_state] > 0).any(axis=1)
        return alive, False, tot0
    with _routing_overrides(variant, combine_fused):
        out = _matrix_dispatch([prep], S, R_max, V, step_ids, init_state,
                               mesh, resume=True, tot0=tot0)
    return out[0], out[1], out[2]


def matrix_segmented_config(S, V, init_state, num_states, max_segment,
                            variant, combine_fused, step_ids=None) -> dict:
    """The knob/shape fingerprint a segmented-matrix checkpoint is
    valid under — ONE constructor shared by the writer
    (matrix_check_segmented) and out-of-band checkpoint authors
    (bench.py's resume_savings stage, tests), so a fingerprint drift
    between them is impossible by construction. ``step_ids`` stamps
    the model identity: the prefix hash covers only the encoded
    columns, which are model-independent, so a model swap between
    interrupt and resume must discard on the config instead."""
    from jepsen_tpu.checker.checkpoint import step_identity
    if step_ids is None:
        step_ids = _default_step_ids()
    return {"path": "matrix", "S": S, "V": V, "init_state": init_state,
            "num_states": num_states, "max_segment": max_segment,
            "variant": variant, "combine_fused": combine_fused,
            "step": step_identity(step_ids)}


def matrix_check_segmented(stream, step_ids=None, init_state: int = 0,
                           num_states: int | None = None,
                           n_slots: int | None = None, mesh=None,
                           variant: str | None = None,
                           combine_fused: bool | None = None,
                           max_segment: int | None = None,
                           ckpt=None, carry: dict | None = None,
                           carry_sink=None):
    """One long small-domain history through a crash-resumable chain of
    :func:`matrix_check_resume` segments cut at quiescent points.
    Returns the :func:`matrix_check` quad ``(alive, -1, inexact, 0)``.

    Resumable two ways (doc/robustness.md "Resumable checks and the
    elastic mesh"):

    * ``ckpt`` — a :class:`~jepsen_tpu.checker.checkpoint.CheckpointStore`:
      the composed ``tot0`` product persists after each segment when
      the write interval elapses; a valid ``matrix`` checkpoint (same
      S/V/knobs, matching consumed-prefix hash) resumes the chain at
      its cut. Bit-identical: boolean operator products are exact
      under any association, so a resumed chain composes the same
      total as an uninterrupted one.
    * ``carry``/``carry_sink`` — the in-process twin for the checker
      ladder: after each exact segment ``carry_sink`` receives
      ``{"rep": "matrix", "tot0", "events_done", "S", "V",
      "init_state"}``, and a matching ``carry`` passed back in resumes
      mid-chain — how a watchdog-demoted or mesh-shrunk rung keeps its
      completed segments instead of restarting.

    Soundness: an INEXACT segment (oob transition) aborts the chain
    immediately WITHOUT sinking or persisting its carry — an
    under-approximate product must never seed an exact resume. Dead
    carries are likewise never persisted (the verdict settles now).
    With a ``mesh`` each segment's chunk axis shards over the devices;
    the carry is the same replicated product either way, so a chain
    may shrink or demote its mesh between segments freely."""
    if step_ids is None:
        step_ids = _default_step_ids()
    if num_states is None:
        num_states = len(stream.intern)
    V = _bucket(num_states, floor=8)
    kind = np.asarray(stream.kind)
    slot = np.asarray(stream.slot)
    S = max(n_slots or 1, int(slot.max(initial=0)) + 1)
    if max_segment is None:
        max_segment = MATRIX_SEGMENT_EVENTS
    cuts = quiescent_cuts(kind, max_segment)
    cut_set = set(cuts)
    n = len(kind)
    base, seg_i = 0, 0
    tot = None
    inexact_any = False
    config = ckpt_mod = None
    if ckpt is not None:
        from jepsen_tpu.checker import checkpoint as ckpt_mod
        config = matrix_segmented_config(S, V, init_state, num_states,
                                         max_segment, variant,
                                         combine_fused,
                                         step_ids=step_ids)
    # in-process carry first (it is at least as fresh as the durable
    # checkpoint: the sink runs every segment, the store on an interval)
    if carry is not None:
        if (carry.get("rep") == "matrix" and carry.get("S") == S
                and carry.get("V") == V
                and carry.get("init_state") == init_state
                and carry.get("events_done") in cut_set):
            tot = carry["tot0"]
            base = int(carry["events_done"])
            seg_i = cuts.index(base) + 1
            from jepsen_tpu.checker.checkpoint import count_resume
            count_resume("carry")
            logger.info("segmented matrix check resuming from in-process "
                        "carry at event %d/%d", base, n)
        else:
            logger.warning("matrix carry (S=%r V=%r events=%r) doesn't "
                           "fit this stream (S=%d V=%d); restarting",
                           carry.get("S"), carry.get("V"),
                           carry.get("events_done"), S, V)
    if tot is None and ckpt is not None:
        state = ckpt_mod.load_resume(ckpt, "matrix", config, stream)
        if state is not None and state["events_done"] in cut_set:
            tot = ckpt_mod.decode_array(state["carry"]["tot0"])
            base = int(state["events_done"])
            seg_i = cuts.index(base) + 1
            ckpt_mod.count_resume("ckpt")
            logger.info("resuming segmented matrix check from %s at "
                        "event %d/%d", ckpt.path, base, n)
        elif state is not None:
            logger.warning("matrix checkpoint's cut %d is not a "
                           "quiescent cut of this stream; restarting",
                           state["events_done"])
    from jepsen_tpu import trace as trace_mod
    tracer = trace_mod.get_tracer()
    for end in cuts:
        if end <= base:
            continue
        seg = _slice_stream(stream, base, end)
        seg_t0 = trace_mod.now_us() if tracer.enabled else 0
        alive, ix, tot = matrix_check_resume(
            seg, tot, step_ids=step_ids, init_state=init_state,
            num_states=num_states, n_slots=S, mesh=mesh, variant=variant,
            combine_fused=combine_fused)
        alive_b = bool(np.asarray(alive).all())
        ix_b = bool(np.asarray(ix).any())
        if tracer.enabled:
            tracer.complete(trace_mod.TRACK_CHECKPOINT, "segment",
                            seg_t0, trace_mod.now_us() - seg_t0,
                            args={"base": base, "end": end,
                                  "alive": alive_b, "inexact": ix_b})
        if ix_b:
            # an oob escape proves nothing — and its under-approximate
            # carry must never seed an exact resume: abort unsunk
            return alive_b, -1, True, 0
        if not alive_b:
            return False, -1, inexact_any, 0
        base = end
        seg_i += 1
        if carry_sink is not None:
            carry_sink({"rep": "matrix", "tot0": tot, "events_done": base,
                        "S": S, "V": V, "init_state": init_state})
        if ckpt is not None and base < n:
            def make_state(tot=tot, base=base, seg_i=seg_i):
                return {
                    "kind": "matrix", "config": config,
                    "events_done": base, "segment": seg_i,
                    "prefix_hash": ckpt_mod.stream_prefix_hash(stream,
                                                               base),
                    "carry": {"tot0": ckpt_mod.encode_array(
                        np.asarray(tot))},
                }
            ckpt.maybe_save(make_state, base)
    return True, -1, inexact_any, 0


def matrix_check_batch(streams, step_ids=None, init_state: int = 0,
                       num_states: int | None = None, mesh=None,
                       variant: str | None = None,
                       combine_fused: bool | None = None):
    """Batched transfer-matrix check over independent per-key histories
    (the jepsen.independent regime, BASELINE config 3). All keys' chunk
    products advance together in one [B*C, MV, MV] MXU matmul per scan
    step, then each key's chunks chain separately — so B keys cost the
    same sequential depth as one. With a mesh, the chunk axis G = B*C is
    sharded over the mesh's first axis (each device multiplies its own
    chunk block; the per-key combine re-shards on keys), so the batch
    scales over ICI like the rest of the checker data plane. Returns
    [(alive, -1, inexact, 0)] per stream; callers needing failure
    diagnostics re-run the event scan on the not-alive keys. Callers gate
    the regime (matrix_ok on max S / max V / total returns) before paying
    the prepass."""
    import jax

    if step_ids is None:
        step_ids = _default_step_ids()
    if num_states is None:
        num_states = max(len(s.intern) for s in streams)
    V = _bucket(num_states, floor=8)
    B = len(streams)
    # global (S, R_max) from cheap metadata passes, so the EXPENSIVE
    # prepass can run per sub-batch inside the dispatch pipeline below
    # (every sub-batch still compiles at the one shared shape)
    kinds = [np.asarray(s.kind) for s in streams]
    slots_np = [np.asarray(s.slot) for s in streams]
    S = max(int(sl.max(initial=0)) + 1 for sl in slots_np)
    R_max = max(int((k == EV_RETURN).sum()) for k in kinds)
    if R_max == 0:
        return [(True, -1, False, 0)] * B
    # every matrix dispatch — key batches, the ladder's sharded rung,
    # the live daemon's screens, segmented rounds via matrix_check —
    # feeds the per-device-count rate model here, so mesh_route's
    # measured-rate comparison activates no matter which caller runs
    # (doc/performance.md "The cost gate")
    total_events = sum(len(k) for k in kinds)
    t_start = time.perf_counter()

    def observe(n_devices: int) -> None:
        from jepsen_tpu.parallel import pipeline
        pipeline.observe_device_rate(n_devices, total_events,
                                     time.perf_counter() - t_start)

    def prep(i):
        s = streams[i]
        return _returns_prepass(kinds[i], slots_np[i], np.asarray(s.f),
                                np.asarray(s.a), np.asarray(s.b))

    # Key batches split into pipelined sub-dispatches: per-step cost
    # grows superlinearly with G = B*C past the measured sweet spot
    # (the [G, MV, MV] intermediates go HBM-bound), so a pipeline of
    # bounded dispatches beats one huge dispatch. Sub-batch k+1's host
    # prepass + grid build + H2D staging all run while batch k computes
    # on device (DispatchPipeline: async dispatches, delayed blocking at
    # the depth limit, one batched readback at the end), which hides
    # most of the host wall-clock.
    # MATRIX_PIPELINE_KEYS extends the overlap to mid-size batches
    # (r4 weak #4 / r5 weak #2: 64-key configs were host-bound).
    # (A mesh shards G across devices, shifting the sweet spot; the
    # mesh path keeps the single dispatch.)
    sub = MATRIX_SUB_KEYS if B > MATRIX_SUB_KEYS else MATRIX_PIPELINE_KEYS
    if mesh is None and B > sub:
        from jepsen_tpu.parallel.pipeline import DispatchPipeline

        # a short remainder sub-batch would compile at its own shape
        # (and a B'=1 tail would even flip the chunk target): pad it
        # with empty keys (R=0 -> identity product, trivially alive)
        # so EVERY dispatch shares the one compiled shape
        C, T = _matrix_plan(sub, S, R_max, V, None)
        run = _matrix_cache(S, V, step_ids, init_state, T, C, sub)
        pipe = DispatchPipeline(depth=PIPELINE_DEPTH, name="matrix")
        phases = {"prepass": 0.0, "grids": 0.0, "dispatch": 0.0}
        counts = []
        with _routing_overrides(variant, combine_fused):
            for lo in range(0, B, sub):
                def stage(lo=lo):
                    t0 = time.perf_counter()
                    sl = [prep(i) for i in range(lo, min(lo + sub, B))]
                    counts.append(len(sl))
                    sl += [_EMPTY_PREP] * (sub - len(sl))
                    t1 = time.perf_counter()
                    # build + STAGE the grids now (device_put issues the
                    # H2D copies immediately, overlapping in-flight
                    # compute)
                    grids, uops = _matrix_grids(sl, S, V, sub, C, T, None)
                    args = pipe.stage(*grids, uops)
                    phases["prepass"] += t1 - t0
                    phases["grids"] += time.perf_counter() - t1
                    return tuple(args)

                def dispatch(pend, ids, slots, valid, uops):
                    t0 = time.perf_counter()
                    out = run(pend, ids, uops, slots, valid)
                    phases["dispatch"] += time.perf_counter() - t0
                    return out

                pipe.submit(stage, dispatch)
            t0 = time.perf_counter()
            fetched = pipe.results()
        phases["fetch"] = time.perf_counter() - t0
        _publish_phases(phases)
        out = []
        for nb, (a, ix) in zip(counts, fetched):
            out += [(bool(a[b]), -1, bool(ix[b]), 0) for b in range(nb)]
        observe(1)
        return out

    phases = {}
    t0 = time.perf_counter()
    preps = [prep(i) for i in range(B)]
    phases["prepass"] = time.perf_counter() - t0
    with _routing_overrides(variant, combine_fused):
        handle = _matrix_dispatch(preps, S, R_max, V, step_ids, init_state,
                                  mesh, phases=phases)
        t0 = time.perf_counter()
        alive, inexact = jax.device_get(handle)
    phases["fetch"] = time.perf_counter() - t0
    _publish_phases(phases)
    observe(1 if mesh is None else int(mesh.devices.size))
    return [(bool(alive[b]), -1, bool(inexact[b]), 0) for b in range(B)]


def _publish_phases(phases: dict) -> None:
    """Rounds the measured host/device split and annotates it with the
    dispatch routing labels (variant + combine path) for this thread's
    ``last_phase_seconds`` readers — the per-variant attribution
    bench.py folds into the matrix metrics."""
    out = {k: round(v, 4) for k, v in phases.items()}
    out.update(last_dispatch_info())
    _PHASE.value = out


def _matrix_plan(B, S, R_max, V, mesh):
    """(C, T) for one sub-batch's chunk layout: per key, C chunks of T
    returns (padded with identity); chunk g = b*C + c. R is bucketed so
    (T, C, B) — and therefore the compiled program — is shared across
    nearby history lengths. The total chunk count targets G = B*C ≈ 256:
    measured on-device, the per-step cost grows superlinearly with G
    (the [G, MV, MV] intermediates become HBM-bound) while G ≥ ~128
    already saturates the matmul units, so more parallel chunks past
    that point only slows each of the fewer steps down. C is
    additionally capped by the element budget."""
    MV = (1 << S) * V
    nd = int(mesh.devices.size) if mesh is not None else 1
    # with a mesh the per-step [G, MV, MV] working set shards over the
    # devices, so the element budget binds PER DEVICE — the key count a
    # single device must hold is ceil(B/nd) (the dispatch pads B up to a
    # device multiple for the key-sharded kernel)
    budget_keys = B if mesh is None else -(-B // nd)
    if budget_keys * MV * MV > MATRIX_MAX_ELEMS:
        # even C=1 would allocate over-budget [B, MV, MV] intermediates;
        # callers pre-gate with matrix_ok, so a direct caller this large
        # must hear "out of regime" rather than OOM the device
        raise ValueError(
            f"matrix_check_batch out of regime: keys/device * MV^2 = "
            f"{budget_keys * MV * MV} > {MATRIX_MAX_ELEMS}; split the "
            f"key batch or use the scan")
    rb = _bucket(R_max, floor=64)
    # chunk-count target, measured on-chip (r5 sweep, 64x1k keys):
    # G = B*C ≈ 2048 beats the old 256 target by ~9% on key BATCHES
    # (234k -> 254k ops/s; 4096 flat, 8192 degrades HBM-bound), while
    # single histories (B=1, incl. the segmented scale path) measured
    # best at the old 256 — padding past their return count buys
    # nothing. Per-key C stays capped at 256.
    target_g = 256 if B == 1 else 2048
    C = int(np.clip(target_g // B, 1, 256))
    C = max(1, min(C, MATRIX_MAX_ELEMS // (budget_keys * MV * MV)))
    if mesh is not None and B == 1:
        # the chunk axis shards over the mesh: pad C up to a device
        # multiple (identity chunks, visible in the
        # checker_mesh_padding_frac gauge) instead of the old silent
        # fall-back to an unsharded dispatch. Always within budget: the
        # per-device block C/nd * MV^2 never exceeds the unsharded
        # C * MV^2 the budget already admitted.
        C = -(-max(C, nd) // nd) * nd
    T = -(-rb // C)
    return C, T


def _matrix_grids(preps, S, V, B, C, T, mesh):
    """HOST side of one sub-batch dispatch: pads each key's return
    grids into the (T, G) chunk layout and interns the batch's distinct
    ops. Returns ([pend, ids, slots, valid] grids, uops) — everything
    the kernel call needs, so a pipeline can run this (and the H2D
    staging) while the previous sub-batch computes."""
    import jax

    def key_arrays(p):
        r_slot, r_pend, r_ops, s_k = p
        R = r_slot.shape[0]
        pad = C * T - R
        slot_p = np.concatenate([r_slot, np.zeros((pad,), np.int32)])
        pend_p = np.zeros((C * T, S), bool)
        pend_p[:R, :s_k] = r_pend
        ops_p = np.zeros((C * T, S, 3), np.int64)
        ops_p[:R, :s_k] = r_ops
        val_p = np.concatenate([np.ones((R,), bool), np.zeros((pad,), bool)])
        return slot_p, pend_p, ops_p, val_p

    slots, pends, opss, vals = zip(*[key_arrays(p) for p in preps])
    # Intern the batch's distinct (f, a, b) ops: the kernel receives small
    # int id grids plus one [U, 3] table instead of a [T, G, S, 3] int64
    # op tensor — an ~8x transfer cut,
    # and the per-op transition matrices get built once instead of per
    # scan step.
    all_ops = np.concatenate([o.reshape(-1, 3) for o in opss])
    # interning via packed scalar keys when fields fit 21 bits (the
    # in-regime case: f codes and interned value ids are tiny) — a 1-D
    # unique sorts ~10x faster than np.unique(axis=0)'s row view
    if all_ops.size and 0 <= all_ops.min() and all_ops.max() < (1 << 21):
        packed = ((all_ops[:, 0] << 42) | (all_ops[:, 1] << 21)
                  | all_ops[:, 2])
        keys, inv = np.unique(packed, return_inverse=True)
        uops = np.stack([keys >> 42, (keys >> 21) & 0x1FFFFF,
                         keys & 0x1FFFFF], axis=1)
    else:
        uops, inv = np.unique(all_ops, axis=0, return_inverse=True)
    # id/slot grids ride the narrowest exact dtype — the grids are the
    # bulk of host→device traffic
    id_dtype = np.int16 if len(uops) < (1 << 15) else np.int32
    ids = inv.astype(id_dtype).reshape(B, C * T, S)
    ub = _bucket(len(uops), floor=16)
    uops = np.concatenate(
        [uops, np.zeros((ub - len(uops), 3), uops.dtype)]).astype(np.int32)

    def as_tg(x):
        # [B, C*T, ...] → [B, C, T, ...] → [T, B, C, ...] → [T, B*C, ...]
        x = np.asarray(x).reshape((B, C, T) + x.shape[2:])
        x = np.moveaxis(x, 2, 0)
        return x.reshape((T, B * C) + x.shape[3:])

    grids = [as_tg(np.stack(pends)), as_tg(ids),
             as_tg(np.stack(slots).astype(np.int8)), as_tg(np.stack(vals))]
    if mesh is not None:
        # the chunk axis G = B*C is a device multiple by construction
        # (_matrix_plan bumps C for B == 1, _matrix_dispatch pads the
        # key axis otherwise — the old path here silently DROPPED the
        # sharding on a non-divisible G): stage each device's block down
        # its own transfer lane
        from jepsen_tpu.parallel import shard_chunked
        grids = shard_chunked(mesh, grids, axis=1)
    return grids, uops


# empty key prep (R=0): its chunks are all-invalid, so its product is
# the identity — trivially alive, trivially exact. The key-axis pad for
# mesh divisibility, and the pipelined path's tail pad, both use it.
_EMPTY_PREP = (np.zeros(0, np.int32), np.zeros((0, 1), bool),
               np.zeros((0, 1, 3), np.int64), 1)


def _publish_mesh_padding(B_real, B_pad, S, R_max, V, C, T):
    """``checker_mesh_padding_frac``: the fraction of a sharded
    dispatch's chunk-step work (G * T) spent on mesh-divisibility
    padding — identity chunks from bumping C (B == 1) or padded keys.
    The cost of never silently dropping sharding, kept visible."""
    from jepsen_tpu import telemetry
    reg = telemetry.get_registry()
    if not reg.enabled:
        return
    try:
        c0, t0 = _matrix_plan(B_real, S, R_max, V, None)
        frac = max(0.0, 1.0 - (B_real * c0 * t0) / float(B_pad * C * T))
    except ValueError:
        # the unsharded plan can be out of budget where the per-device
        # sharded one is not: no meaningful baseline, skip the gauge
        return
    reg.gauge("checker_mesh_padding_frac",
              "fraction of sharded chunk-step work spent on mesh "
              "divisibility padding, last sharded dispatch").set(frac)


def _matrix_dispatch(preps, S, R_max, V, step_ids, init_state, mesh,
                     resume: bool = False, tot0=None, phases: dict | None
                     = None):
    """Builds one sub-batch's chunk grids and dispatches the kernel,
    returning UNSYNCED device arrays (alive[B], inexact[B]; plus the
    composed total[B, MV, MV] when ``resume``) so callers can pipeline
    several dispatches before reading any back. With a mesh the dispatch
    shards (chunk axis for B == 1, key axis otherwise — the key axis is
    padded HERE with empty keys to a device multiple; callers index only
    their real keys). ``phases`` (optional) collects the host
    grids/dispatch wall split for attribution."""
    B_real = len(preps)
    if mesh is not None and B_real > 1:
        nd = int(mesh.devices.size)
        if B_real % nd:
            preps = list(preps) + [_EMPTY_PREP] * ((-B_real) % nd)
    B = len(preps)
    C, T = _matrix_plan(B, S, R_max, V, mesh)
    if mesh is not None:
        _publish_mesh_padding(B_real, B, S, R_max, V, C, T)
        # the mesh twin runs the XLA scan + device-side tree combine by
        # construction (collectives pair with the tree — see
        # _build_matrix_kernel_mesh); label the routing accordingly
        _DISPATCH_INFO.value = {"variant": "scan", "combine": "tree"}
    t0 = time.perf_counter()
    grids, uops = _matrix_grids(preps, S, V, B, C, T, mesh)
    t1 = time.perf_counter()
    run = _matrix_cache(S, V, step_ids, init_state, T, C, B, mesh)
    if resume:
        if tot0 is None:
            tot0 = run.init_total()
        out = run.resume(grids[0], grids[1], uops, grids[2], grids[3],
                         tot0)
    else:
        out = run(grids[0], grids[1], uops, grids[2], grids[3])
    if phases is not None:
        phases["grids"] = phases.get("grids", 0.0) + (t1 - t0)
        phases["dispatch"] = (phases.get("dispatch", 0.0)
                              + time.perf_counter() - t1)
    return out


_MATRIX_CACHE: dict = {}
_DEFAULT_STEP_IDS = None


def _default_step_ids():
    """One shared default spec — a fresh object per call would defeat
    the id()-keyed compile cache."""
    global _DEFAULT_STEP_IDS
    if _DEFAULT_STEP_IDS is None:
        from jepsen_tpu.models import cas_register_spec
        _DEFAULT_STEP_IDS = cas_register_spec().step_ids
    return _DEFAULT_STEP_IDS


def _matrix_cache(S, V, step_ids, init_state, T, C, B=1, mesh=None):
    # the uop-table length is a runtime array shape — jax.jit retraces on
    # it, so it doesn't belong in this key. A mesh keys on its device ids
    # + axis names: parallel.auto_mesh caches one Mesh per device count,
    # so repeated sharded dispatches hit the same compiled kernel.
    mesh_key = (None if mesh is None else
                (tuple(int(d.id) for d in mesh.devices.flat),
                 tuple(mesh.axis_names)))
    key = (S, V, id(step_ids), init_state, T, C, B, mesh_key)
    fn = _MATRIX_CACHE.get(key)
    if fn is None:
        if mesh is not None:
            fn = _build_matrix_kernel_mesh(S, V, step_ids, init_state, T,
                                           C, n_keys=B, mesh=mesh)
        else:
            fn = _build_matrix_kernel(S, V, step_ids, init_state, T, C,
                                      n_keys=B)
        _MATRIX_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Anomaly forensics: device-side first-anomaly localization
# (checker/explain.py drives these — doc/observability.md "Anomaly
# forensics")
# ---------------------------------------------------------------------------

def _build_forensics_kernel(S: int, V: int, step_ids, T: int, C: int):
    """Device programs for localizing WHERE a transfer-matrix verdict
    went invalid, built from the same `_kernel_math` as the checking
    kernels so localization can never disagree with the verdict:

    * ``products`` — the chunk scan WITHOUT the final combine: every
      chunk's composed [MV, MV] operator product comes back instead of
      one verdict, so localization can bisect over them.
    * ``prefix_alive`` — an associative inclusive scan composing the
      chunk products into prefix products (log-depth on device; boolean
      matrix products are exact under any association, so the scan's
      re-pairing cannot change a verdict) and testing each prefix's
      frontier for survivors: the first dead prefix names the guilty
      chunk in O(log C) combine depth instead of a CPU re-scan.
    * ``vec_batch`` — a vmapped per-return re-scan of ONE chunk's
      operators applied to a [MV] frontier *vector* (not the [MV, MV]
      matrix — ~MV× cheaper per step), returning each candidate's first
      dead return: the within-chunk localization step AND the witness
      shrinker's candidate-mask evaluator (checker/explain.py ddmin).
    """
    import types

    import jax
    import jax.numpy as jnp
    from jax import lax

    math = _kernel_math(S, V, step_ids, C)
    MV, eye = math.MV, math.eye

    @jax.jit
    def products(pend, op_ids, uops, slots, valid):
        mt_tab, oob_tab = math.uop_tables(uops)
        P0 = jnp.broadcast_to(eye, (C, MV, MV))
        (P, inexact), _ = lax.scan(math.make_step(mt_tab, oob_tab),
                                   (P0, jnp.zeros((C,), bool)),
                                   (pend, op_ids, slots, valid))
        return P, inexact

    @jax.jit
    def prefix_alive(P, v0):
        def comb(a, b):
            # a holds earlier chunks' accumulated product, b later ones:
            # time order composes later-on-the-LEFT like chain_time
            out = jnp.einsum("...ij,...jk->...ik", b, a,
                             preferred_element_type=jnp.bfloat16)
            return (out > 0).astype(jnp.bfloat16)

        prefix = lax.associative_scan(comb, P)
        # frontier after chunk c = column init of prefix[c] @ tot0, i.e.
        # prefix[c] @ v0 with v0 the carry's init column
        w = jnp.einsum("cij,j->ci", prefix, v0.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return (w > 0).any(axis=1), prefix

    vmath = _kernel_math(S, V, step_ids, 1)

    def _vec_scan(pend, valid, op_ids, uops, slots, v0):
        """One candidate: the chunk's T return operators applied to the
        frontier vector ``v0``; returns (first dead return or -1,
        inexact)."""
        mt_tab, oob_tab = vmath.uop_tables(uops)
        base = vmath.make_step(mt_tab, oob_tab)

        def step(carry, inp):
            carry2, _ = base(carry, inp)
            vec, _ = carry2
            return carry2, (vec[0, :, 0] > 0).any()

        # ride make_step's [G=1, MV, MV] @ [G=1, MV, k] matmul with the
        # vector as a k=1 matrix — same operators, MV× less work
        P0 = v0.astype(jnp.bfloat16).reshape(1, MV, 1)
        (_, inexact), alive = lax.scan(
            step, (P0, jnp.zeros((1,), bool)),
            (pend[:, None, :], op_ids[:, None, :], slots[:, None],
             valid[:, None]))
        first = jnp.where(alive.all(), jnp.int32(-1),
                          jnp.argmax(~alive).astype(jnp.int32))
        return first, inexact.any()

    vec_batch = jax.jit(jax.vmap(_vec_scan,
                                 in_axes=(0, 0, None, None, None, None)))
    return types.SimpleNamespace(products=products,
                                 prefix_alive=prefix_alive,
                                 vec_batch=vec_batch)


_FORENSICS_CACHE: dict = {}


def _forensics_cache(S, V, step_ids, T, C):
    key = (S, V, id(step_ids), T, C)
    fk = _FORENSICS_CACHE.get(key)
    if fk is None:
        fk = _build_forensics_kernel(S, V, step_ids, T, C)
        _FORENSICS_CACHE[key] = fk
    return fk


class MatrixLocalization:
    """A settled device-side localization: WHERE the transfer-matrix
    frontier first died, plus the handles checker/explain.py needs to
    delta-debug a minimal witness over the guilty window (the chunk's
    host grids and the frontier vector at its entry)."""

    def __init__(self, failed_return, failed_event, failed_op_index,
                 bisect_steps, chunk, step, n_chunks, chunk_returns,
                 kernel, uops, window_pend, window_ids, window_slots,
                 window_valid, v_start, ret_idx):
        self.failed_return = failed_return      # global return index
        self.failed_event = failed_event        # stream event index
        self.failed_op_index = failed_op_index  # history op index
        self.bisect_steps = bisect_steps
        self.chunk = chunk                      # guilty chunk c*
        self.step = step                        # chunk-relative return t*
        self.n_chunks = n_chunks
        self.chunk_returns = chunk_returns      # T
        self.kernel = kernel                    # forensics kernel ns
        self.uops = uops
        self.window_pend = window_pend          # [T, S] guilty chunk grids
        self.window_ids = window_ids
        self.window_slots = window_slots
        self.window_valid = window_valid
        self.v_start = v_start                  # [MV] frontier at entry
        self.ret_idx = ret_idx                  # return -> event index map


def matrix_localize(stream, tot0=None, step_ids=None, init_state: int = 0,
                    num_states: int | None = None, n_slots: int | None = None):
    """Localizes the first anomaly of an INVALID matrix-family verdict
    entirely on device: re-derives the per-chunk operator products (one
    dispatch of the same cost as the check), bisects the composable
    prefix products for the first dead chunk (O(log C) combine depth —
    `prefix_alive`), then pinpoints the return within it with a cheap
    [MV]-vector re-scan. The result's ``failed_event`` is bit-identical
    to the exact CPU frontier's first rejection (the operators ARE the
    frontier transition — pinned by tests/test_explain.py across
    single-device, segmented, sharded-mesh, and live-screen backends).

    ``tot0`` carries a segmented chain's composed prior product
    (matrix_check_resume's output), so a failing segment localizes
    without re-scanning the chain; event/op indices are then relative to
    THIS segment's stream (its ``op_index`` column keeps them absolute).

    Returns a :class:`MatrixLocalization`, or None when the stream is
    alive, out of plan budget, or inexact (an oob transition proves
    nothing — the exact CPU frontier must settle it instead)."""
    import jax.numpy as jnp

    if step_ids is None:
        step_ids = _default_step_ids()
    if num_states is None:
        num_states = len(stream.intern)
    V = _bucket(num_states, floor=8)
    kind = np.asarray(stream.kind)
    prep = _returns_prepass(kind, np.asarray(stream.slot),
                            np.asarray(stream.f), np.asarray(stream.a),
                            np.asarray(stream.b))
    S = max(n_slots or 1, prep[3])
    R = prep[0].shape[0]
    if R == 0:
        return None
    MV = (1 << S) * V
    if tot0 is not None and np.asarray(tot0).shape[-1] != MV:
        raise ValueError(
            f"carry dimension {np.asarray(tot0).shape[-1]} != {MV}: "
            f"segments must share n_slots and num_states")
    try:
        C, T = _matrix_plan(1, S, R, V, None)
    except ValueError:
        return None  # out of element budget: the CPU frontier settles it
    grids, uops = _matrix_grids([prep], S, V, 1, C, T, None)
    fk = _forensics_cache(S, V, step_ids, T, C)
    P, inexact = fk.products(grids[0], grids[1], uops, grids[2], grids[3])
    if bool(np.asarray(inexact).any()):
        return None  # oob transition: localization would prove nothing
    if tot0 is not None:
        v0 = (jnp.asarray(tot0).reshape(-1, MV, MV)[0][:, init_state]
              > 0).astype(jnp.bfloat16)
    else:
        v0 = jnp.zeros((MV,), jnp.bfloat16).at[init_state].set(1)
    alive, prefix = fk.prefix_alive(P, v0)
    alive = np.asarray(alive)
    if alive.all():
        return None  # the (carried) history is alive: nothing to localize
    c_star = int(np.argmax(~alive))
    if c_star == 0:
        v_start = v0
    else:
        v_start = (jnp.einsum("ij,j->i", prefix[c_star - 1], v0,
                              preferred_element_type=jnp.float32)
                   > 0).astype(jnp.bfloat16)
    pend_c = np.asarray(grids[0])[:, c_star]
    ids_c = np.asarray(grids[1])[:, c_star]
    slots_c = np.asarray(grids[2])[:, c_star]
    valid_c = np.asarray(grids[3])[:, c_star]
    first, inexact2 = fk.vec_batch(pend_c[None], valid_c[None], ids_c,
                                   uops, slots_c, v_start)
    t_star = int(np.asarray(first)[0])
    if t_star < 0 or bool(np.asarray(inexact2).any()):
        # the chunk verdict and its per-return re-scan disagree — a bug
        # or an oob escape; never report a guessed position
        logger.warning("matrix localization inconsistency at chunk %d "
                       "(first=%d); declining", c_star, t_star)
        return None
    r_star = c_star * T + t_star
    ret_idx = np.nonzero(kind == EV_RETURN)[0]
    event = int(ret_idx[r_star])
    op_index = int(np.asarray(stream.op_index)[event])
    bisect_steps = max(1, int(np.ceil(np.log2(max(C, 2))))) + 1
    return MatrixLocalization(
        failed_return=r_star, failed_event=event, failed_op_index=op_index,
        bisect_steps=bisect_steps, chunk=c_star, step=t_star, n_chunks=C,
        chunk_returns=T, kernel=fk, uops=uops, window_pend=pend_c,
        window_ids=ids_c, window_slots=slots_c, window_valid=valid_c,
        v_start=v_start, ret_idx=ret_idx)


def matrix_window_rescan(loc: MatrixLocalization, pend_batch, valid_batch):
    """First dead return (chunk-relative; -1 = survives) for each
    candidate's masked (pend, valid) grids over the localized chunk,
    evaluated as ONE vmapped device dispatch — the witness shrinker's
    inner loop (checker/explain.py). Callers bucket the candidate count
    so the vmapped kernel compiles at a handful of batch shapes."""
    first, _ = loc.kernel.vec_batch(
        np.ascontiguousarray(pend_batch),
        np.ascontiguousarray(valid_batch),
        loc.window_ids, loc.uops, loc.window_slots, loc.v_start)
    return np.asarray(first)


# dense-table applicability bounds. Besides the per-axis caps, the closure
# materializes an [S, 2^S, V] f32 intermediate per batch element, so gate
# on the product too: S * 2^S * V elements (4 bytes each) must stay under
# a few MB or a vmapped batch of keys would blow device memory where the
# sparse kernel needs kilobytes.
DENSE_MAX_SLOTS = 12
DENSE_MAX_STATES = 512
DENSE_MAX_ELEMS = 1 << 21  # 2M elements ≈ 8 MB f32 per batch element


def _dense_ok(S: int, num_states: int | None) -> bool:
    if num_states is None:
        return False
    vb = _bucket(num_states, floor=16)
    return (S <= DENSE_MAX_SLOTS and num_states <= DENSE_MAX_STATES
            and S * (1 << S) * vb <= DENSE_MAX_ELEMS)


class _ResumeKernel:
    """A jitted resume-scan plus its initial-frontier constructor (jit
    wrappers don't take attributes, so the pair rides a tiny holder)."""

    def __init__(self, fn, init_carry):
        self.fn = fn
        self.init_carry = init_carry

    def __call__(self, *args):
        return self.fn(*args)


def quiescent_cuts(kind, max_segment: int) -> list[int]:
    """Cut positions for segmented verification: indices where no op is
    pending (every invoke has returned), at most ``max_segment`` events
    apart. Vectorized over the event-kind array; returns cumulative end
    positions including the final one."""
    kind = np.asarray(kind)
    delta = np.where(kind == EV_INVOKE, 1,
                     np.where(kind == EV_RETURN, -1, 0))
    pending = np.cumsum(delta)
    quiet = np.nonzero(pending == 0)[0] + 1  # cut AFTER these events
    cuts: list[int] = []
    pos = 0
    n = len(kind)
    while pos < n:
        limit = pos + max_segment
        if limit >= n:
            cuts.append(n)
            break
        j = np.searchsorted(quiet, limit, side="right") - 1
        if j >= 0 and quiet[j] > pos:
            nxt = int(quiet[j])
        else:
            # no quiescent point inside the window: a raw cut would DROP
            # pending-op state and could convict a valid history, so
            # extend to the next quiescent point (or the end) instead —
            # soundness beats the segment-size preference
            k = np.searchsorted(quiet, limit, side="right")
            nxt = int(quiet[k]) if k < len(quiet) else n
        cuts.append(nxt)
        pos = nxt
    return cuts


def segmented_check(stream, max_segment: int = 1 << 21, kernel=None,
                    capacity: int = 256, num_states: int | None = None,
                    ckpt=None):
    """Checks one long history as a chain of bounded segments, carrying
    the frontier on device between them — arbitrarily long histories in
    bounded device memory (and bounded single-dispatch size: r2's
    monolithic multi-million-event scans crashed the TPU worker).

    The stream is cut ONLY at quiescent points (no pending ops across a
    cut): the resume carry holds the frontier but not pending-op state,
    so a mid-operation cut would drop obligations and could convict a
    valid history. When a window has no quiescent point, the segment
    extends to the next one (or the end) — soundness beats the
    segment-size preference. Returns (alive, died_event, overflow, peak).

    ``ckpt`` (a :class:`jepsen_tpu.checker.checkpoint.CheckpointStore`)
    makes the chain crash-resumable: the frontier carry persists after
    each segment when the write interval elapses, and a valid
    ``frontier`` checkpoint (same cuts, same kernel config, matching
    consumed-prefix hash) resumes the chain at its cut instead of
    restarting — bit-identical, the carry IS the frontier the
    uninterrupted chain holds there (doc/robustness.md "Resumable
    checks and the elastic mesh")."""
    if kernel is None:
        kernel = JitLinKernel()
    if num_states is None and getattr(stream, "intern", None) is not None:
        num_states = len(stream.intern)
    S = max(1, stream.n_slots)
    run = kernel._get(S, capacity, batched=False, num_states=num_states,
                      resume=True)
    kind = np.asarray(stream.kind)
    cuts = quiescent_cuts(kind, max_segment)
    carry = run.init_carry()
    alive, died, ovf, peak = True, -1, False, 0
    base = 0
    config = ckpt_state = None
    if ckpt is not None:
        from jepsen_tpu.checker import checkpoint as ckpt_mod
        config = {"path": "segmented", "S": S, "capacity": capacity,
                  "num_states": num_states, "max_segment": max_segment,
                  "dense": bool(_dense_ok(S, num_states)),
                  "step": ckpt_mod.step_identity(kernel.step_ids)}
        ckpt_state = ckpt_mod.load_resume(ckpt, "frontier", config, stream)
        if ckpt_state is not None and ckpt_state["events_done"] in set(cuts):
            base = ckpt_state["events_done"]
            carry = tuple(ckpt_mod.decode_array(a).astype(d.dtype)
                          for a, d in zip(ckpt_state["carry"]["arrays"],
                                          (np.asarray(c) for c in carry)))
            ovf = bool(ckpt_state["carry"].get("overflow", False))
            peak = int(ckpt_state["carry"].get("peak", 0))
            ckpt_mod.count_resume("ckpt")
            logger.info("resuming segmented check from %s at event %d/%d",
                        ckpt.path, base, len(kind))
        elif ckpt_state is not None:
            logger.warning("segmented checkpoint's cut %d is not a "
                           "quiescent cut of this stream; restarting",
                           ckpt_state["events_done"])
            ckpt_state = None
    from jepsen_tpu.checker.linear_encode import pad_streams
    for end in cuts:
        if end <= base:
            continue  # already covered by the resumed carry
        seg = _slice_stream(stream, base, end)
        batch = pad_streams([seg], length=_bucket(len(seg)))
        args, ret_event = scan_inputs(
            *(batch[k] for k in ("kind", "slot", "f", "a", "b")), S,
            num_states)
        out = run(*(x[0] for x in args), *carry)
        a, d, o, p = out[0], out[1], out[2], out[3]
        carry = out[4:]
        d = died_events(d, None if ret_event is None else ret_event[0])
        a, d, o, p = (bool(np.asarray(a)), int(d),
                      bool(np.asarray(o)), int(np.asarray(p)))
        ovf |= o
        peak = max(peak, p)
        if not a:
            return False, base + d if d >= 0 else -1, ovf, peak
        base = end
        if ckpt is not None and base < len(kind):
            from jepsen_tpu.checker import checkpoint as ckpt_mod

            def make_state(carry=carry, base=base, ovf=ovf, peak=peak):
                return {
                    "kind": "frontier", "config": config,
                    "events_done": base, "segment": cuts.index(base),
                    "prefix_hash": ckpt_mod.stream_prefix_hash(stream,
                                                               base),
                    "carry": {
                        "arrays": [ckpt_mod.encode_array(np.asarray(c))
                                   for c in carry],
                        "overflow": ovf, "peak": peak,
                    },
                }
            ckpt.maybe_save(make_state, base)
    return True, -1, ovf, peak


def _slice_stream(stream, lo: int, hi: int):
    """A view-slice of an EventStream's arrays (shared intern/slots)."""
    import copy
    seg = copy.copy(stream)
    # op_index slices too: a segment's diagnostics (matrix_localize's
    # failed_op_index) must resolve through ITS events, not the full
    # stream's row numbering
    for field in ("kind", "slot", "f", "a", "b", "op_index"):
        setattr(seg, field, np.asarray(getattr(stream, field))[lo:hi])
    return seg


class JitLinKernel:
    """Compiled-kernel cache keyed by backend + (S, K|V, batched?)."""

    def __init__(self, step_ids=None, init_state: int = 0):
        # the shared default spec keeps id(step_ids)-keyed compile caches
        # (matrix kernels) warm across kernel instances
        self.step_ids = step_ids if step_ids is not None else _default_step_ids()
        self.init_state = init_state
        self._cache: dict = {}

    def _get(self, S: int, K: int, batched: bool, num_states: int | None = None,
             resume: bool = False):
        """Picks the dense exact kernel when the configuration space is
        small enough, else the capacity-K sort-based frontier; either
        takes :func:`scan_inputs`' arrays for its (S, num_states). With
        ``resume`` the returned callable takes and returns the frontier
        carry (dense: +table; sparse: +mask,state) for segmented
        verification; it also exposes ``.init_carry()``."""
        import jax
        if _dense_ok(S, num_states):
            vb = _bucket(num_states, floor=16)
            key = ("dense", S, vb, batched, resume)
            fn = self._cache.get(key)
            if fn is None:
                run = _build_dense_step(S, vb, self.step_ids, self.init_state)
                if resume:
                    fn = _ResumeKernel(jax.jit(run.resume),
                                       lambda: (run.init_table(),))
                else:
                    fn = jax.jit(jax.vmap(run)) if batched else jax.jit(run)
                self._cache[key] = fn
            return fn
        key = ("sparse", S, K, batched, resume)
        fn = self._cache.get(key)
        if fn is None:
            run = _build_step(S, K, self.step_ids, self.init_state)
            if resume:
                fn = _ResumeKernel(jax.jit(run.resume),
                                   lambda: run.init_frontier())
            else:
                fn = jax.jit(jax.vmap(run)) if batched else jax.jit(run)
            self._cache[key] = fn
        return fn

    def check(self, stream, capacity: int = 256):
        """Single history. Returns (alive, died_event, overflow, peak).
        Delegates to parallel.batch_check (the one batching/sharding
        implementation)."""
        return self.check_batch([stream], capacity=capacity)[0]

    def check_batch(self, streams, capacity: int = 256, mesh=None):
        """vmapped per-key batch, sharded over a mesh when available.
        Returns [(alive, died, ovf, peak)] per stream."""
        from jepsen_tpu.parallel import batch_check
        return batch_check(streams, capacity=capacity, mesh=mesh, kernel=self)


def _bucket(n: int, floor: int = 64) -> int:
    """Round counts up to a power of two >= floor so jit caches hit
    (floor 64 for event lengths, 16 for state counts)."""
    b = floor
    while b < n:
        b *= 2
    return b


def verdict(alive: bool, overflow: bool):
    """Soundness rules: a surviving (possibly truncated) frontier proves
    linearizability; an empty frontier after overflow proves nothing."""
    if alive:
        return True
    return "unknown" if overflow else False
