"""The plain reference: linearizability of a CAS register, from op dicts.

Knossos's ``cas-register`` model (initial value nil; a read of nil is an
unknown read and matches any state; ``cas [old new]`` succeeds only where
the register holds ``old``) checked by just-in-time linearization (Lowe,
"Testing for linearizability", 2017), the search Knossos's ``linear``
checker makes. It shares nothing with the program under test: it reads
the history's op dicts, pairs invocations with completions itself, and
keeps its own configuration sets.

A configuration is (register value, set of pending ops already
linearized). At an op's completion every configuration is first closed
under linearizing any pending op whose precondition holds, then only
those in which the completing op is linearized survive. The history is
linearizable iff a configuration survives every completion; otherwise
the completion at which none survives is the failing op, the op Knossos
and the program report.

Failed ops (``:fail``) never took effect and are dropped with their
invocation. Crashed ops (``:info``) are outside the benchmark's traffic
and are refused rather than half supported.
"""
from __future__ import annotations

from dataclasses import dataclass

READ, WRITE, CAS = 0, 1, 2
_F = {"read": READ, "write": WRITE, "cas": CAS}


@dataclass(frozen=True)
class Verdict:
    """A single register's verdict: ``failed_at`` is the index, in the
    history given, of the completion at which no configuration survives
    (-1 when valid)."""
    valid: bool
    failed_at: int = -1


def _pair(history: list[dict]) -> tuple[dict, set]:
    """({invoke index: completion op}, indices of dropped fail pairs)."""
    open_at: dict = {}
    completion: dict = {}
    dropped: set = set()
    for i, op in enumerate(history):
        typ, p = op["type"], op["process"]
        if typ == "invoke":
            if p in open_at:
                raise ValueError(f"process {p} invokes twice at op {i}")
            open_at[p] = i
            continue
        j = open_at.pop(p, None)
        if j is None:
            raise ValueError(f"completion without invocation at op {i}")
        if typ == "ok":
            completion[j] = op
        elif typ == "fail":
            dropped.add(j)
            dropped.add(i)
        else:
            raise ValueError(f"op {i} has type {typ!r}: crashed ops are "
                             f"outside this reference")
    if open_at:
        raise ValueError(f"ops never completed: {sorted(open_at.values())}")
    return completion, dropped


def check(history: list[dict]) -> Verdict:
    """The verdict on one single-register history."""
    completion, dropped = _pair(history)
    # state ids: 0 is nil, other values interned as they appear
    ids: dict = {None: 0}

    def sid(v) -> int:
        if v not in ids:
            ids[v] = len(ids)
        return ids[v]

    # a configuration is one int: state id << 32 | linearized-slot mask
    configs = {0}
    slot_of: dict = {}        # process -> slot of its pending op
    pending: dict = {}        # slot -> (f, a, b)
    free: list[int] = []
    n_slots = 0
    for i, op in enumerate(history):
        if i in dropped:
            continue
        p = op["process"]
        if op["type"] == "invoke":
            done = completion[i]
            f = _F[op["f"]]
            if f == READ:
                # the read's value arrives with its completion; nil
                # reads are unknown and match any state (a = -1)
                v = done["value"]
                a, b = (-1 if v is None else sid(v)), 0
            elif f == WRITE:
                a, b = sid(op["value"]), 0
            else:
                a, b = sid(op["value"][0]), sid(op["value"][1])
            if free:
                s = free.pop()
            else:
                s, n_slots = n_slots, n_slots + 1
            slot_of[p] = s
            pending[s] = (f, a, b)
            continue
        s = slot_of.pop(p)
        bit = 1 << s
        # close the configurations under linearizing pending ops
        seen = set(configs)
        stack = list(configs)
        ops = list(pending.items())
        while stack:
            c = stack.pop()
            state, mask = c >> 32, c & 0xFFFFFFFF
            for t, (f, a, b) in ops:
                tb = 1 << t
                if mask & tb:
                    continue
                if f == READ:
                    if a != -1 and a != state:
                        continue
                    nxt = c | tb
                elif f == WRITE:
                    nxt = (a << 32) | mask | tb
                else:
                    if state != a:
                        continue
                    nxt = (b << 32) | mask | tb
                if nxt not in seen:
                    seen.add(nxt)
                    stack.append(nxt)
        configs = {c & ~bit for c in seen if c & bit}
        if not configs:
            return Verdict(False, i)
        del pending[s]
        free.append(s)
    return Verdict(True)


def check_no_realtime(history: list[dict]) -> Verdict:
    """The control: the reference with real-time order dropped.

    Each ok read need only return nil or a value some write or cas had
    been invoked to install before the read completed; when the value
    was installed, and what ran between, is not looked at. It breaks
    the guarantee the configuration states, linearizability, and keeps
    the weaker one a reads-from check gives: it accepts a stale read
    and rejects a never-written one."""
    completion, dropped = _pair(history)
    installed: set = set()
    for i, op in enumerate(history):
        if i in dropped:
            continue
        if op["type"] == "invoke":
            done = completion[i]
            if op["f"] == "write":
                installed.add(op["value"])
            elif op["f"] == "cas":
                installed.add(done["value"][1])
            continue
        if op["f"] == "read" and op["value"] is not None \
                and op["value"] not in installed:
            return Verdict(False, i)
    return Verdict(True)


CHECKS = {"reference": check, "control": check_no_realtime}


def split_keys(history: list[dict]) -> dict:
    """{key: single-register history} of a jepsen.independent history
    whose values are ``[key, value]``."""
    subs: dict = {}
    for op in history:
        k, v = op["value"]
        subs.setdefault(k, []).append({**op, "value": v})
    return subs
