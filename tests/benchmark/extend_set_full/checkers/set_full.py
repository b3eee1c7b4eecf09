"""A grow-only set through ``SetFullChecker(accelerator="tpu")``, with its
own generator and its own plain reference: a configuration of another
kind than the registers, brought as files alone.

The generator: ``adders`` clients each keep one add of a new element in
flight, completing in an order drawn from the mix's ``base_seed``; after
every ``read_every`` completed adds one reader reads the set, and sees
every element whose add has completed. ``--seed`` renames the elements.
Every other history of the pool has one element lost: the element whose
add completed halfway is left out of every read from 3/4 of the history
on.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field

import numpy as np

from benchmark.traffic import Planted

READER = -1


@dataclass(frozen=True)
class Mix:
    elements: int
    read_every: int
    adders: int
    pool: int = 2
    base_seed: int = 0
    test: dict = field(default_factory=dict)


def mix(config: dict, path) -> Mix:
    return Mix(adders=config["adders"], **json.loads(path.read_text()))


def history(mix: Mix, seed: int, j: int) -> Planted:
    order = np.random.default_rng([mix.base_seed, j]).random(mix.elements)
    names = np.random.default_rng([seed % (1 << 64), j]).permutation(
        mix.elements).tolist()
    h: list[dict] = []
    done: list = []
    flight: list[tuple[int, int]] = []        # (process, element)
    n = 0
    while n < mix.elements or flight:
        if n < mix.elements and len(flight) < mix.adders:
            p = n % mix.adders
            h.append({"type": "invoke", "process": p, "f": "add",
                      "value": names[n]})
            flight.append((p, names[n]))
            n += 1
            continue
        p, e = flight.pop(int(order[len(done)] * len(flight)))
        h.append({"type": "ok", "process": p, "f": "add", "value": e})
        done.append(e)
        if len(done) % mix.read_every == 0:
            h.append({"type": "invoke", "process": READER, "f": "read",
                      "value": None})
            h.append({"type": "ok", "process": READER, "f": "read",
                      "value": sorted(done)})
    if j % 2 == 0:
        return Planted(h)
    lost = done[len(done) // 2]
    first = None
    for i in range(3 * len(h) // 4, len(h)):
        op = h[i]
        if op["f"] == "read" and op["type"] == "ok":
            h[i] = {**op, "value": [e for e in op["value"] if e != lost]}
            first = i if first is None else first
    return Planted(h, [(None, "lost", first)])


def check(history: list[dict], test: dict) -> dict:
    from jepsen_tpu.checker import SetFullChecker
    return SetFullChecker(accelerator="tpu").check(dict(test), history, {})


def answer(result: dict, history: list[dict]) -> dict:
    """{None: (valid, the lost elements)}."""
    return {None: (result.get("valid?"), frozenset(result.get("lost", ())))}


def reference(history: list[dict], which: str) -> dict:
    """An element is lost when the last read begun after its add was
    acknowledged leaves it out. The control drops that order: it calls
    an element lost only when no read at all holds it."""
    acked: dict = {}
    reads: list[tuple[int, set]] = []
    began: dict = {}
    for i, op in enumerate(history):
        if op["f"] == "add" and op["type"] == "ok":
            acked.setdefault(op["value"], i)
        elif op["f"] == "read" and op["type"] == "invoke":
            began[op["process"]] = i
        elif op["f"] == "read" and op["type"] == "ok":
            reads.append((began.pop(op["process"]), set(op["value"])))
    ever = set().union(*(s for _, s in reads))
    lost = set()
    for e, at in acked.items():
        later = [s for t, s in reads if t >= at]
        if later and e not in (later[-1] if which == "reference" else ever):
            lost.add(e)
    return {None: (not lost, frozenset(lost))}
