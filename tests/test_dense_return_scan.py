"""The dense frontier scan steps over returns (ops/jitlin.py
``_build_dense_step`` fed by ``scan_inputs``): held to the exact CPU
frontier's answers on histories of Jepsen's linearizable-register shape,
single and batched, one-shot and segmented, and to its structure — one
closure per return step, no per-event branch, the program ``jit_run``."""
from __future__ import annotations

import random

import jax
import numpy as np
import pytest

from jepsen_tpu.checker.linear_cpu import check_stream
from jepsen_tpu.checker.linear_encode import encode_register_ops, pad_streams
from jepsen_tpu.ops import jitlin
from jepsen_tpu.ops.jitlin import (EV_INVOKE, EV_RETURN, JitLinKernel,
                                   _bucket, scan_inputs, segmented_check)
from jepsen_tpu.parallel import _scan_batch

FIELDS = ("kind", "slot", "f", "a", "b")


def register_history(rng: random.Random, n_ops: int, plant: str | None = None,
                     drain_every: int | None = None) -> list[dict]:
    """A linearizable-register history of the source's shape: 10 threads
    on one key, each with one op in flight; threads 0-4 only read, the
    others write or cas 1:2, values ``(rand-int 5)``; each op takes
    effect at its completion. ``plant`` makes it invalid at about 3/4:
    ``stale`` (drain, write 1, write 2, then a read of 1) or ``never``
    (an ok read of a value no op writes). ``drain_every`` lets every op
    complete after that many completions, which leaves quiescent cuts."""
    h: list[dict] = []
    inflight: dict = {}
    reg = [None]

    def invoke(p):
        if p < 5:
            op = {"f": "read", "value": None}
        elif rng.randrange(3) == 0:
            op = {"f": "write", "value": rng.randrange(5)}
        else:
            op = {"f": "cas", "value": [rng.randrange(5), rng.randrange(5)]}
        h.append({"type": "invoke", "process": p, **op})
        inflight[p] = op

    def complete(p):
        op = inflight.pop(p)
        typ, value = "ok", op["value"]
        if op["f"] == "read":
            value = reg[0]
        elif op["f"] == "write":
            reg[0] = value
        elif reg[0] == value[0]:
            reg[0] = value[1]
        else:
            typ = "fail"
        h.append({"type": typ, "process": p, "f": op["f"], "value": value})

    def drain():
        while inflight:
            complete(rng.choice(sorted(inflight)))

    planted = plant is None
    done = 0
    for p in range(10):
        invoke(p)
    while done < n_ops:
        if not planted and done >= 3 * n_ops // 4:
            if plant == "stale":
                drain()
                for p, f, v in ((5, "write", 1), (5, "write", 2),
                                (0, "read", 1)):
                    h.append({"type": "invoke", "process": p, "f": f,
                              "value": None if f == "read" else v})
                    h.append({"type": "ok", "process": p, "f": f,
                              "value": v})
                reg[0] = 2
                planted = True
                for p in range(10):
                    invoke(p)
                continue
        p = rng.choice(sorted(inflight))
        complete(p)
        done += 1
        if (not planted and plant == "never" and done >= 3 * n_ops // 4
                and h[-1]["f"] == "read"):
            h[-1]["value"] = 99
            planted = True
        if drain_every and done % drain_every == 0:
            drain()
            for q in range(10):
                invoke(q)
        elif p not in inflight:
            invoke(p)
    drain()
    return h


def streams_of(seed: int, lengths, plants):
    rng = random.Random(seed)
    return [encode_register_ops(register_history(rng, n, plant))
            for n, plant in zip(lengths, plants)]


def oracle(stream):
    r = check_stream(stream)
    return (r.valid is True, r.failed_event, False, r.configs_max)


CASES = {
    "valid": (11, [120, 37, 81], [None, None, None]),
    "stale": (12, [90, 140, 33], ["stale", None, "stale"]),
    "never_written": (13, [64, 150, 20], ["never", "never", None]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_return_scan_matches_cpu_frontier(case):
    """Same verdict, failed event and peak as the exact CPU frontier,
    one stream at a time and as one ragged batch (padding exercised)."""
    seed, lengths, plants = CASES[case]
    streams = streams_of(seed, lengths, plants)
    want = [oracle(s) for s in streams]
    assert [w[0] for w in want] == [p is None for p in plants]
    k = JitLinKernel()
    n = max(len(s.intern) for s in streams)
    assert jitlin._dense_ok(max(s.n_slots for s in streams), n)
    single = [_scan_batch([s], 256, None, k, n)[0] for s in streams]
    batched = _scan_batch(streams, 256, None, k, n)
    assert single == want
    assert batched == want


@pytest.mark.parametrize("plant", [None, "stale", "never"])
def test_segmented_return_scan_matches_one_shot(plant):
    """Across quiescent cuts, each segment's op table starts empty and
    the table carries the frontier: the segmented chain gives the
    one-shot tuple."""
    rng = random.Random(21)
    stream = encode_register_ops(
        register_history(rng, 160, plant, drain_every=25))
    cuts = jitlin.quiescent_cuts(np.asarray(stream.kind), 60)
    assert len(cuts) >= 3
    k = JitLinKernel()
    one_shot = _scan_batch([stream], 256, None, k, len(stream.intern))[0]
    seg = segmented_check(stream, max_segment=60, kernel=k)
    assert seg == one_shot == oracle(stream)


def test_out_of_range_state_surfaces_as_overflow():
    """A transition past the dense intern range degrades the verdict to
    unknown through the overflow channel: for an op pending at a return,
    and for one invoked after the last return, which no closure sees."""
    def write(p, v):
        return [{"type": "invoke", "process": p, "f": "write", "value": v},
                {"type": "ok", "process": p, "f": "write", "value": v}]

    # 20 distinct values intern beyond the 16 states the scan is told of
    pending = [op for v in range(20) for op in write(0, v)]
    # the same values, the last ten written by crashed ops invoked after
    # the last completion
    tail = [op for v in range(10) for op in write(0, v)] + [
        {"type": "invoke", "process": p, "f": "write", "value": 9 + p}
        for p in range(1, 11)]
    k = JitLinKernel()
    # the transitions leaving the range are dropped, so the first history
    # dies at the write of value 15
    for h, want in ((pending, (False, 31, True, 2)),
                    (tail, (True, -1, True, 2))):
        stream = encode_register_ops(h)
        assert len(stream.intern) > 16
        assert jitlin._dense_ok(stream.n_slots, 5)
        assert _scan_batch([stream], 256, None, k, 5) == [want]


def reference_prepass(kind, slot, f, a, b, S):
    """Event-by-event replay of the old scan's invoke/return branches:
    each return's (slot, pending set, op table, event)."""
    pend = np.zeros(S, bool)
    ops = np.zeros((S, 3), np.int64)
    out = []
    for i, (k, s) in enumerate(zip(kind, slot)):
        if k == EV_INVOKE:
            pend[s] = True
            ops[s] = (f[i], a[i], b[i])
        elif k == EV_RETURN:
            out.append((s, pend.copy(), ops.copy(), i))
            pend[s] = False
    return out, pend.copy(), ops.copy()


def test_prepass_matches_event_replay():
    streams = streams_of(31, [45, 3, 70, 12], [None, None, "stale", "never"])
    batch = pad_streams(streams, length=_bucket(max(map(len, streams))))
    S = batch["n_slots"]
    (r_slot, r_pend, r_ops, tail_pend, tail_ops), ret_event = scan_inputs(
        *(batch[k] for k in FIELDS), S, 16)
    R_max = max(int((np.asarray(s.kind) == EV_RETURN).sum())
                for s in streams)
    assert r_slot.shape == (len(streams), _bucket(R_max, floor=16))
    for j, s in enumerate(streams):
        want, end_pend, end_ops = reference_prepass(
            *(np.asarray(getattr(s, k)) for k in FIELDS), S)
        R = len(want)
        assert (r_slot[j, R:] == -1).all() and (ret_event[j, R:] == -1).all()
        assert not r_pend[j, R:].any()
        for r, (slot, pend, ops, ev) in enumerate(want):
            assert r_slot[j, r] == slot and ret_event[j, r] == ev
            assert (r_pend[j, r] == pend).all()
            assert (r_ops[j, r][pend] == ops[pend]).all()
        assert (tail_pend[j] == end_pend).all()
        assert (tail_ops[j][end_pend] == end_ops[end_pend]).all()


def primitives(jaxpr, out=None):
    """Every equation of a closed jaxpr, nested ones included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    primitives(inner, out)
    return out


def test_dense_scan_structure():
    """One scan step per padded return, no switch (``cond``) on the
    scan's path, and the jitted program still named ``jit_run`` — the
    name the benchmark's ``scan_us_per_event`` reads."""
    streams = streams_of(41, [30, 55, 9], [None, "never", None])
    S = max(s.n_slots for s in streams)
    n = max(len(s.intern) for s in streams)
    batch = pad_streams(streams, length=_bucket(max(map(len, streams))))
    args, _ = scan_inputs(*(batch[k] for k in FIELDS), S, n)
    R_max = max(int((np.asarray(s.kind) == EV_RETURN).sum())
                for s in streams)
    k = JitLinKernel()

    one = k._get(S, 256, batched=False, num_states=n)
    eqns = primitives(jax.make_jaxpr(one)(*(x[0] for x in args)).jaxpr)
    names = {e.primitive.name for e in eqns}
    assert "scan" in names and "cond" not in names

    fn = k._get(S, 256, batched=True, num_states=n)
    eqns = primitives(jax.make_jaxpr(fn)(*args).jaxpr)
    [scan] = [e for e in eqns if e.primitive.name == "scan"]
    assert scan.params["length"] == _bucket(R_max, floor=16) \
        < batch["kind"].shape[1]
    assert fn.lower(*args).as_text().splitlines()[0].startswith(
        "module @jit_run")
