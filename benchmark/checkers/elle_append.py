"""Jepsen's list-append workload through the program's list-append
checker (``jepsen_tpu.workloads.append.checker()``, its defaults: the
``auto`` accelerator, strict serializability), with its own generator
and its own plain reference (``benchmark/list_append_reference.py``).

The generator: ``threads`` workers each keep one txn in flight and
invoke the next as soon as the last completes. Txns come from Elle's
list-append generator, as ``jepsen_tpu.elle.list_append.gen`` mirrors
it: ``key_count`` active keys, each txn of ``min_txn_length`` to
``max_txn_length`` micro-ops, each a read of an active key or, as often,
an append of the key's next value, a key retired (and a fresh one
opened in its slot) after ``max_writes_per_key`` appends. Each txn's
micro-ops take effect at once at a point drawn inside its interval
(the next event is drawn uniformly among the txns in flight: an
unapplied one applies, an applied one completes), and a read returns
the key's whole list, so every history is strict-serializable.

The plant (``stale_read``, in every other history): the first ok txn R,
from 3/4 of the history on, that only reads one key k and whose read's
last element e was appended by a txn W which completed before R was
invoked, appended nothing else to k, and took effect after R's
process's previous txn; and where another read of k is at least as long
(so e stays in k's version order). Where no txn from 3/4 on fits (a
short history), the first from the start. R's read loses e. R then
reads the state just before W took effect, so the history stays
serializable (R's place moves to just before W, which
breaks no dependency and no process order), while W completed before R
was invoked: W ->realtime R ->rw W, a cycle only strict serializability
forbids.

What ``--seed`` changes: the shapes (interleaving, txns, place of the
plant) come from the mix's ``base_seed``; the run's seed renames keys,
values and processes, so every seed checks the same work under other
names.
"""
from __future__ import annotations

import json
from dataclasses import dataclass

import numpy as np

from benchmark import list_append_reference
from benchmark import traffic

PLANT_AT = 0.75
# seed streams, as in traffic.py
_HISTORY, _LABELS = 1, 3


@dataclass(frozen=True)
class Mix(traffic.Mix):
    """A list-append mix: ``ops_per_key`` txns in one history (``keys``
    1) by ``threads`` workers, and the generator's settings. It extends
    the register generator's Mix, whose shape fields other than
    ``threads`` (``readers``, ``values``, the write and cas weights) do
    not apply to txns and keep their defaults:
    ``tests/benchmark/test_bench_traffic.py::test_mix_from_files``
    reads them from every cell's mix."""
    key_count: int = 3
    min_txn_length: int = 1
    max_txn_length: int = 4
    max_writes_per_key: int = 256
    read_share: float = 0.5


def mix(config: dict, path) -> Mix:
    raw = json.loads(path.read_text())
    raw.pop("why", None)
    g = config["generator"]
    return Mix(ops_per_key=raw.pop("txns"), threads=config["workers"],
               plants=tuple(raw.pop("plants")), **g, **raw)


def _txns(mix: Mix, n: int, rng) -> list[list]:
    """``n`` txns as Elle's generator makes them, in invocation order:
    micro-ops ``[is_read, key, value]``."""
    lengths = rng.integers(mix.min_txn_length, mix.max_txn_length + 1,
                           size=n).tolist()
    total = sum(lengths)
    slots = rng.integers(mix.key_count, size=total).tolist()
    reads = (rng.random(total) < mix.read_share).tolist()
    active = list(range(mix.key_count))
    fresh = mix.key_count
    count: dict = {}
    out = []
    m = 0
    for length in lengths:
        txn = []
        for _ in range(length):
            s = slots[m]
            k = active[s]
            if reads[m]:
                txn.append([True, k, None])
            else:
                if count.get(k, 0) >= mix.max_writes_per_key:
                    k = active[s] = fresh
                    fresh += 1
                count[k] = count.get(k, 0) + 1
                txn.append([False, k, count[k]])
            m += 1
        out.append(txn)
    return out


@dataclass
class _Sim:
    """One simulated history in internal names."""
    txns: list          # micro-ops per txn, reads filled in when applied
    worker: list        # worker per txn
    invoked: list       # history position of each txn's invocation
    done: list          # ... of its completion
    applied: list       # step at which it took effect
    events: list        # (txn, is completion) per history position


def _simulate(mix: Mix, rng) -> _Sim:
    n, T = mix.ops_per_key, mix.threads
    txns = _txns(mix, n, rng)
    pick = rng.random(3 * n).tolist()
    state: dict = {}
    sim = _Sim(txns, [0] * n, [0] * n, [0] * n, [-1] * n, [])
    free = list(range(T))
    flight: list = []
    nxt = 0
    for s in range(3 * n):
        if nxt < n and free:
            sim.worker[nxt] = free.pop(int(pick[s] * len(free)))
            sim.invoked[nxt] = len(sim.events)
            sim.events.append((nxt, False))
            flight.append(nxt)
            nxt += 1
            continue
        i = int(pick[s] * len(flight))
        t = flight[i]
        if sim.applied[t] < 0:
            sim.applied[t] = s
            for mop in txns[t]:
                lst = state.setdefault(mop[1], [])
                if mop[0]:
                    mop[2] = list(lst)
                else:
                    lst.append(mop[2])
            continue
        flight.pop(i)
        free.append(sim.worker[t])
        sim.done[t] = len(sim.events)
        sim.events.append((t, True))
    return sim


def _plant_stale_read(sim: _Sim) -> int:
    """Plants the stale read (module docstring) and returns the
    history position of R's completion."""
    writer: dict = {}
    n_appends: dict = {}
    longest: dict = {}          # key -> the two longest read lengths
    for t, txn in enumerate(sim.txns):
        for is_read, k, v in txn:
            if is_read:
                top = longest.setdefault(k, [0, 0])
                if len(v) > top[0]:
                    top[:] = [len(v), top[0]]
                elif len(v) > top[1]:
                    top[1] = len(v)
            else:
                writer[k, v] = t
                n_appends[t, k] = n_appends.get((t, k), 0) + 1
    prev: dict = {}             # txn -> its worker's previous txn
    last: dict = {}
    for t, is_done in sim.events:
        if is_done:
            if sim.worker[t] in last:
                prev[t] = last[sim.worker[t]]
            last[sim.worker[t]] = t
    start = int(PLANT_AT * len(sim.events))
    for pos in [*range(start, len(sim.events)), *range(start)]:
        r, is_done = sim.events[pos]
        txn = sim.txns[r]
        if not is_done or len(txn) != 1 or not txn[0][0] or not txn[0][2]:
            continue
        _, k, read = txn[0]
        w = writer[k, read[-1]]
        p = prev.get(r)
        if (sim.done[w] < sim.invoked[r] and n_appends[w, k] == 1
                and (p is None or sim.applied[p] < sim.applied[w])
                and (len(read) < longest[k][0]
                     or longest[k][1] >= len(read))):
            txn[0][2] = read[:-1]
            return pos
    raise ValueError("no txn takes a stale_read plant")


def _ops(sim: _Sim, mix: Mix, labels) -> list[dict]:
    """The history's op dicts, keys, values and workers renamed."""
    names = np.random.default_rng(labels)
    n_keys = 1 + max(k for txn in sim.txns for _, k, _ in txn)
    key_of = names.permutation(n_keys).tolist()
    val_of = [0] + (1 + names.permutation(mix.max_writes_per_key)).tolist()
    proc_of = names.permutation(mix.threads).tolist()
    out = []
    for t, is_done in sim.events:
        value = []
        for is_read, k, v in sim.txns[t]:
            if not is_read:
                value.append(["append", key_of[k], val_of[v]])
            elif is_done:
                value.append(["r", key_of[k], [val_of[x] for x in v]])
            else:
                value.append(["r", key_of[k], None])
        out.append({"type": "ok" if is_done else "invoke",
                    "process": proc_of[sim.worker[t]], "f": "txn",
                    "value": value})
    return out


def history(mix: Mix, seed: int, j: int) -> traffic.Planted:
    """History ``j`` of the pool: valid and with one plant in turn."""
    kind = None if j % 2 == 0 else mix.plants[(j // 2) % len(mix.plants)]
    sim = _simulate(mix, np.random.default_rng(
        traffic.seed_seq(mix.base_seed, _HISTORY, j)))
    plants = []
    if kind == "stale_read":
        plants.append((None, kind, _plant_stale_read(sim)))
    elif kind is not None:
        raise ValueError(f"unknown plant kind {kind!r}")
    return traffic.Planted(
        _ops(sim, mix, traffic.seed_seq(seed, _LABELS, j)), plants)


def check(history: list[dict], test: dict) -> dict:
    """One check as a user makes it: the workload's checker with its
    defaults and a fresh test map, so the history IR is built anew."""
    from jepsen_tpu.workloads import append
    return append.checker().check(dict(test), history, {})


def answer(result: dict, history: list[dict]) -> dict:
    """{"history": (valid, sorted anomaly types)}."""
    return {"history": (result.get("valid?"),
                        tuple(sorted(result.get("anomaly-types", ()))))}


def reference(history: list[dict], which: str) -> dict:
    """The plain reference's answer (``which="reference"``: strict
    serializability) or the control's (``"control"``: the process and
    realtime order dropped, which is serializability)."""
    return {"history": list_append_reference.check(
        history, timing=which != "control")}
