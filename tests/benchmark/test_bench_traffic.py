"""The traffic generator and the plain reference."""
from __future__ import annotations

import itertools
import json

import pytest

from benchmark import harness, reference, traffic

def mix(**kw) -> traffic.Mix:
    base = dict(ops_per_key=1000, pool=4)
    base.update(kw)
    return traffic.Mix(**base)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, -3])
def test_same_seed_same_history(seed):
    m = mix()
    a = [traffic.make_history(m, seed, j) for j in range(4)]
    b = [traffic.make_history(m, seed, j) for j in range(4)]
    assert [x.history for x in a] == [x.history for x in b]
    assert [x.plants for x in a] == [x.plants for x in b]
    assert a[0].history != traffic.make_history(m, seed + 1, 0).history


def test_seeds_rename_but_keep_the_shape():
    """Another seed relabels values and processes; the interleaving, the
    sizes and the plants' places stay, so every seed costs the same."""
    m = mix(keys=3, ops_per_key=800, invalid="one_key", pool=2)
    for j in range(2):
        a = traffic.make_history(m, 11, j)
        b = traffic.make_history(m, 12, j)
        assert a.history != b.history
        assert [p[0::2] for p in a.plants] == [p[0::2] for p in b.plants]
        assert [(op["type"], op["f"]) for op in a.history] == \
            [(op["type"], op["f"]) for op in b.history]
        # one renaming of each key's values maps the one onto the other
        rename = {}
        for x, y in zip(a.history, b.history):
            k = x["value"][0]
            for u, v in zip(json.dumps(x["value"][1]).split(","),
                            json.dumps(y["value"][1]).split(",")):
                assert rename.setdefault((k, u), v) == v


def test_register_history_shape():
    """The source's shape: 10 threads with one op in flight each, all
    busy; 5 of them only read, the others write or cas at 1:2, every
    value drawn from (rand-int 5)."""
    m = mix(ops_per_key=5000)
    h = traffic.make_history(m, 5, 0).history
    assert len(h) == 2 * m.ops_per_key
    assert {op["process"] for op in h} == set(range(10))
    fs = {}
    for op in h:
        if op["type"] == "invoke":
            fs.setdefault(op["process"], []).append(op["f"])
    readers = [p for p, f in fs.items() if set(f) == {"read"}]
    assert len(readers) == 5
    writers = [f for p, f in fs.items() if p not in readers]
    assert all("read" not in f for f in writers)
    ops = [f for w in writers for f in w]
    assert 0.28 < ops.count("write") / len(ops) < 0.39
    assert 0.45 < sum(map(len, (fs[p] for p in readers))) / 5000 < 0.55
    assert {op["value"] for op in h if op["f"] == "write"} == set(range(5))
    cas = [op for op in h if op["f"] == "cas" and op["type"] != "invoke"]
    assert 0.7 < sum(op["type"] == "fail" for op in cas) / len(cas) < 0.9
    # no thread waits: after the first ten invocations, every completion
    # is followed at once by its thread's next invocation
    assert [op["type"] for op in h[:10]] == ["invoke"] * 10
    for a, b in zip(h[10:-10:2], h[11:-10:2]):
        assert a["type"] != "invoke" and b["type"] == "invoke"
        assert a["process"] == b["process"]
    assert reference.check(h).valid


def test_key_lengths_jitter_as_the_source_does():
    m = mix(keys=500, limit_jitter=0.1, base_seed=3)
    lengths = traffic.key_lengths(m)
    assert lengths == traffic.key_lengths(m)
    assert 900 <= min(lengths) < 910 and 990 < max(lengths) <= 1000
    assert traffic.key_lengths(mix(keys=3)) == [1000] * 3


@pytest.mark.parametrize("seed", [1, 2, 3, 2**31 + 5])
def test_plants_sit_where_stated(seed):
    m = mix(base_seed=seed)
    pool = [traffic.make_history(m, seed, j) for j in range(4)]
    assert [p.plants and p.plants[0][1] for p in pool] == \
        [[], "stale_read", [], "never_written"]
    for p in pool:
        verdict = reference.check(p.history)
        if not p.plants:
            assert verdict == reference.Verdict(True)
            continue
        (_, kind, at), = p.plants
        n = len(p.history)
        assert n // 2 <= at < n
        op = p.history[at]
        assert op["type"] == "ok" and op["f"] == "read"
        assert verdict == reference.Verdict(False, at)
        # the control drops real-time order: blind to the stale read only
        control = reference.check_no_realtime(p.history)
        assert control.valid is (kind == "stale_read")


def test_warm_up_checks_the_first_history_of_each_kind():
    m = mix()
    pool = [traffic.make_history(m, 4, j) for j in range(4)]
    assert harness.warm_up(pool) == [0, 1, 3]
    keyed = mix(keys=8, ops_per_key=400, invalid="one_key", pool=2)
    pool = [traffic.make_history(keyed, 4, j) for j in range(2)]
    assert harness.warm_up(pool) == [0, 1]


def test_keyed_history_plants_one_key():
    m = mix(keys=8, ops_per_key=400, invalid="one_key", pool=2)
    for j in range(2):
        p = traffic.make_history(m, 99, j)
        subs = reference.split_keys(p.history)
        assert sorted(subs) == list(range(8))
        (key, kind, at), = p.plants
        assert kind == ("stale_read", "never_written")[j]
        verdicts = {k: reference.check(h) for k, h in subs.items()}
        assert {k for k, v in verdicts.items() if not v.valid} == {key}
        assert verdicts[key].failed_at == at
        assert all(op["process"] // 10 == op["value"][0]
                   for op in p.history)


def _brute_force(history) -> bool:
    """Linearizable iff some order of the ok ops, consistent with real
    time, replays on the register: every permutation, tiny histories
    only."""
    ops, open_at, dropped = [], {}, set()
    for i, op in enumerate(history):
        if op["type"] == "invoke":
            open_at[op["process"]] = i
        elif op["type"] == "fail":
            dropped.add(open_at.pop(op["process"]))
        else:
            j = open_at.pop(op["process"])
            ops.append((j, i, history[j]["f"],
                        op["value"] if op["f"] == "read"
                        else history[j]["value"]))
    for order in itertools.permutations(ops):
        ok, reg = True, None
        for x, y in itertools.combinations(order, 2):
            if y[1] < x[0]:
                ok = False
                break
        for _, _, f, v in order if ok else ():
            if f == "read":
                if v is not None and v != reg:
                    ok = False
                    break
            elif f == "write":
                reg = v
            elif reg == v[0]:
                reg = v[1]
            else:
                ok = False
                break
        if ok:
            return True
    return False


@pytest.mark.parametrize("seed", range(40))
def test_reference_agrees_with_brute_force(seed):
    """Tiny histories, some altered at random so many are invalid."""
    import random
    m = mix(ops_per_key=6, threads=3, readers=1, values=2, base_seed=seed)
    h = traffic.make_history(m, seed, 0).history
    rnd = random.Random(seed)
    reads = [i for i, op in enumerate(h)
             if op["type"] == "ok" and op["f"] == "read"]
    if reads and rnd.random() < 0.6:
        i = rnd.choice(reads)
        h = list(h)
        h[i] = {**h[i], "value": rnd.choice([0, 1, None])}
    assert reference.check(h).valid is _brute_force(h)


@pytest.mark.parametrize("seed", range(6))
def test_reference_agrees_with_the_program_cpu_search(seed):
    """A second witness: the program's exact host search on the same
    histories (the reference itself imports nothing of the program)."""
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    m = mix(ops_per_key=400, base_seed=seed)
    for j in range(4):
        h = traffic.make_history(m, seed, j).history
        mine = reference.check(h)
        theirs = check_stream(encode_register_ops(h))
        assert mine.valid is theirs.valid
        if not mine.valid:
            assert mine.failed_at == theirs.failed_op_index


def test_reference_refuses_crashed_ops():
    h = [{"type": "invoke", "process": 0, "f": "write", "value": 1},
         {"type": "info", "process": 0, "f": "write", "value": 1}]
    with pytest.raises(ValueError):
        reference.check(h)


def test_mix_from_files():
    b = harness.load()
    for w in b.spec["workloads"]:
        m = b.mix(w)
        assert (m.threads, m.readers, m.values) == (10, 5, 5)
        assert (m.write_w, m.cas_w) == (1, 2)
        assert m.limit_jitter == (0.1 if m.keys > 1 else 0.0)
