"""The register generator: register histories from a mix file and a seed.

The register configurations' checker modules (``checkers/linearizable.py``
and ``checkers/independent.py``) make their pools here: a traffic mix is
a JSON file under ``benchmark/traffic/``, and for those configurations
this module is the only code that reads its generation parameters. A
configuration of another kind brings its own generator in its checker
module and may share ``Planted``. Every history a run checks is made
from ``--seed``, so the same seed gives the same histories, op for op.

The process model is Jepsen's ``linearizable_register`` workload
(``jepsen/src/jepsen/tests/linearizable_register.clj``) against a correct
in-memory register, with the shape its configuration states: per key,
``threads`` clients each keep one op in flight; the first ``readers``
of them only read (``gen/reserve n r``), the others write or cas by the
mix's weights (``gen/mix [w cas cas]``), every value ``(rand-int
values)``, so most cas ops fail. Every op takes effect at its
completion, which makes the history linearizable by construction; the
planted anomalies below are what make one invalid.

Planted anomalies (``plants`` in the mix, one per invalid history, in
turn):

* ``stale_read``: an ok read past the plant point is given a value that
  was written earlier but that no linearization can let it see, because
  a later write had completed before the read began. A check that drops
  real-time order accepts it; a linearizability checker must not.
* ``never_written``: an ok read returns a value no op ever writes.

Either way the history becomes non-linearizable at exactly that read's
completion, and the plant records its index.

What ``--seed`` changes, and what it does not. The interleavings, the op
kinds, each key's length, the place and value of each plant and the key
that carries it come from the mix's ``base_seed``; the run's seed draws
a relabelling of the values and of the processes of every register. So
every seed checks histories of the same sizes and the same shape, which
cost the checker the same work, under different names: a seed that
changed the work would move the measured rate by itself (a failure
report re-scans its history with a frontier whose size depends on the
interleaving).
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

# a read value no op writes (the mix's values are 0 .. values-1)
NEVER_WRITTEN = 1_000_003

# where a plant goes: the first ok read that takes it at or after this
# fraction of the history. The place is fixed and not drawn from the
# seed: how far into a history its anomaly lies sets how much of it the
# checker's report re-scans, so a seeded place would make some seeds'
# runs do more work than others'.
PLANT_AT = 0.75

# seed streams: each purpose draws from its own SeedSequence branch, so
# adding a draw to one never shifts another
_HISTORY, _PLANT, _LABELS, _LIMITS = 1, 2, 3, 4


@dataclass(frozen=True)
class Mix:
    """A traffic mix: the per-key shape its configuration states and the
    lengths, pool and plants its data file states."""
    ops_per_key: int
    keys: int = 1
    threads: int = 10
    readers: int = 5
    values: int = 5
    write_w: float = 1.0
    cas_w: float = 2.0
    limit_jitter: float = 0.0
    pool: int = 2
    invalid: str = "alternate"
    plants: tuple = ("stale_read", "never_written")
    base_seed: int = 0
    test: dict = field(default_factory=dict)


def load_mix(config: dict, path: Path) -> Mix:
    """The mix in the traffic file ``path`` under the per-key shape the
    configuration states (threads and readers per key, values, the
    writers' mix, the per-key limit's jitter); a key the Mix does not
    know is an error."""
    raw = json.loads(path.read_text())
    raw.pop("why", None)
    w = config["mix"]
    return Mix(threads=config["threads_per_key"],
               readers=config["readers_per_key"], values=config["values"],
               write_w=w["write"], cas_w=w["cas"],
               limit_jitter=config.get("limit_jitter", 0.0),
               plants=tuple(raw.pop("plants")), **raw)


def seed_seq(seed: int, *path: int) -> np.random.SeedSequence:
    """A SeedSequence for ``seed`` (any Python int, negative included)
    and a branch path."""
    return np.random.SeedSequence([seed % (1 << 64), *path])


def key_lengths(mix: Mix) -> list[int]:
    """Ops per key: ``ops_per_key``, or with a jitter, Jepsen's
    ``(gen/limit (* (+ (rand jitter) (- 1 jitter)) limit))`` drawn per
    key from the base seed (keys of one history then end out of step)."""
    if not mix.limit_jitter:
        return [mix.ops_per_key] * mix.keys
    u = np.random.default_rng(seed_seq(mix.base_seed, _LIMITS)).random(
        mix.keys)
    return np.ceil((1 - mix.limit_jitter + mix.limit_jitter * u)
                   * mix.ops_per_key).astype(int).tolist()


def register_history(mix: Mix, n: int, ss: np.random.SeedSequence,
                     labels: np.random.SeedSequence) -> list[dict]:
    """One valid single-register history of ``n`` ops: its shape drawn
    from ``ss``, its value and process names from ``labels``.

    ``mix.threads`` clients each keep one op in flight and invoke the
    next as soon as the last completes; the first ``mix.readers`` only
    read, the others draw write or cas by the mix's weights, with
    ``(rand-int values)`` for every value. Completions come in an order
    drawn uniformly among the ops in flight. All random draws are made
    up front in bulk; the loop only walks them, so a 1M-op history takes
    seconds, not tens of seconds."""
    rng = np.random.default_rng(ss)
    T, R = mix.threads, mix.readers
    pick = rng.random(2 * n).tolist()
    is_write = (rng.random(n) * (mix.write_w + mix.cas_w)
                < mix.write_w).tolist()
    names = np.random.default_rng(labels)
    value_of = names.permutation(mix.values)
    vals = value_of[rng.integers(mix.values, size=n)].tolist()
    olds = value_of[rng.integers(mix.values, size=n)].tolist()
    process_of = names.permutation(T).tolist()

    reg = None
    history: list[dict] = []
    append = history.append
    free = list(range(T))
    pending: list[int] = []
    op_of: dict[int, dict] = {}
    invoked = 0
    for s in range(2 * n):
        if invoked < n and free:
            t = free.pop(int(pick[s] * len(free)))
            q = process_of[t]
            if t < R:
                op = {"type": "invoke", "process": q, "f": "read",
                      "value": None}
            elif is_write[invoked]:
                op = {"type": "invoke", "process": q, "f": "write",
                      "value": vals[invoked]}
            else:
                op = {"type": "invoke", "process": q, "f": "cas",
                      "value": [olds[invoked], vals[invoked]]}
            append(op)
            pending.append(t)
            op_of[t] = op
            invoked += 1
            continue
        t = pending.pop(int(pick[s] * len(pending)))
        free.append(t)
        inv = op_of.pop(t)
        f, value, q = inv["f"], inv["value"], inv["process"]
        if f == "read":
            append({"type": "ok", "process": q, "f": f, "value": reg})
        elif f == "write":
            reg = value
            append({"type": "ok", "process": q, "f": f, "value": value})
        elif reg == value[0]:
            reg = value[1]
            append({"type": "ok", "process": q, "f": f, "value": value})
        else:
            append({"type": "fail", "process": q, "f": f, "value": value})
    return history


def _stale_value(history: list[dict], i: int, window: int = 400):
    """A value the ok read at ``i`` cannot see under any linearization
    but that some op wrote before the read completed, or None: of those,
    the one written last, so that the choice is the same under any
    renaming of values.

    The read linearizes somewhere in [invoke, i]. The state it sees is
    the value of the mutation linearized last before that point. A
    mutation that completed before the invoke of another mutation that
    itself completed before the read's invoke is overwritten for sure;
    the values of all other mutations invoked before ``i`` bound what
    the read can see from above. Only the ``window`` events before ``i``
    are looked at; where they cannot settle the bound, None."""
    lo = max(0, i - window)
    p = history[i]["process"]
    inv = i - 1
    while inv >= lo and (history[inv]["process"] != p
                         or history[inv]["type"] != "invoke"):
        inv -= 1
    if inv < lo:
        return None
    open_at: dict = {}
    muts = []  # (invoke index, completion index, installed value)
    for j in range(lo, i):
        op = history[j]
        if op["type"] == "invoke":
            open_at[op["process"]] = j
            continue
        # an op invoked before the window gets the earliest invoke the
        # window allows, which only widens what the read may see
        k = open_at.pop(op["process"], lo - 1)
        if op["type"] == "ok" and op["f"] != "read":
            v = op["value"]
            muts.append((k, j, v[1] if op["f"] == "cas" else v))
    for k in open_at.values():
        op = history[k]
        if op["f"] == "write":
            muts.append((k, i + 1, op["value"]))
        elif op["f"] == "cas":
            muts.append((k, i + 1, op["value"][1]))
    done_before = [m for m in muts if m[1] < inv]
    bound = max((m[0] for m in done_before), default=-1)
    if bound < lo:
        return None
    # mutations completed before the window are all overwritten: they
    # completed before ``bound``, the invoke of one done before ``inv``
    possible = {m[2] for m in muts if m[1] > bound}
    stale = [m[2] for m in sorted(done_before, key=lambda m: m[1])
             if m[2] not in possible]
    return stale[-1] if stale else None


def plant(history: list[dict], kind: str) -> tuple[list[dict], int]:
    """A copy of ``history`` with one ``kind`` anomaly at the first ok read
    it fits at or after the fraction ``PLANT_AT`` of the history; returns
    (copy, index of the planted read's completion)."""
    n = len(history)
    for i in range(int(n * PLANT_AT), n):
        op = history[i]
        if op["type"] != "ok" or op["f"] != "read":
            continue
        if kind == "never_written":
            value = NEVER_WRITTEN
        elif kind == "stale_read":
            value = _stale_value(history, i)
            if value is None:
                continue
        else:
            raise ValueError(f"unknown plant kind {kind!r}")
        bad = list(history)
        bad[i] = {**op, "value": value}
        return bad, i
    raise ValueError(f"no ok read takes a {kind} plant after "
                     f"{PLANT_AT} of the history")


@dataclass
class Planted:
    """One history of a run's pool and what was planted in it."""
    history: list[dict]
    # (key, plant kind, completion index within the key's history) per
    # planted anomaly; key is None for a single-register history
    plants: list = field(default_factory=list)

    @property
    def ops(self) -> int:
        """Ops in the history: an op is an invocation and its completion."""
        return len(self.history) // 2


def _plant_kind(mix: Mix, j: int) -> str | None:
    """The anomaly history ``j`` of the pool carries, or None: valid and
    invalid in turn, valid first (``alternate``), or one bad key in
    every history (``one_key``)."""
    if mix.invalid == "alternate":
        return None if j % 2 == 0 else mix.plants[(j // 2) % len(mix.plants)]
    if mix.invalid == "one_key":
        return mix.plants[j % len(mix.plants)]
    raise ValueError(f"unknown invalid policy {mix.invalid!r}")


def make_history(mix: Mix, seed: int, j: int) -> Planted:
    """History ``j`` of the run's pool.

    Single register (``keys == 1``): valid and with one planted anomaly
    in turn.

    Keyed (``keys > 1``, jepsen.independent): every key its own register
    with its own length, shape and names, the keys one after another,
    values lifted to ``[key, value]`` and processes renumbered per key
    (``threads`` to a key); one key carries the anomaly (``invalid:
    one_key``): the one drawn from the base seed, or where that key has
    no read to take it, the next that has."""
    kind = _plant_kind(mix, j)
    base = mix.base_seed
    lengths = key_lengths(mix)
    if mix.keys == 1:
        h = register_history(mix, lengths[0], seed_seq(base, _HISTORY, j),
                             seed_seq(seed, _LABELS, j))
        if kind is None:
            return Planted(h)
        bad, i = plant(h, kind)
        return Planted(bad, [(None, kind, i)])
    hs = [register_history(mix, lengths[k], seed_seq(base, _HISTORY, j, k),
                           seed_seq(seed, _LABELS, j, k))
          for k in range(mix.keys)]
    plants = []
    if kind is not None:
        # the drawn key, or the next one that has a read to take the plant
        drawn = int(np.random.default_rng(seed_seq(base, _PLANT, j, 0))
                    .integers(mix.keys))
        for k in [*range(drawn, mix.keys), *range(drawn)]:
            try:
                hs[k], i = plant(hs[k], kind)
            except ValueError:
                continue
            plants.append((k, kind, i))
            break
        else:
            raise ValueError(f"no key takes a {kind} plant")
    out: list[dict] = []
    for k, h in enumerate(hs):
        first = k * mix.threads
        for op in h:
            out.append({**op, "process": first + op["process"],
                        "value": [k, op["value"]]})
    return Planted(out, plants)
