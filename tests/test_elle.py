"""Elle-equivalent txn checker tests: classic Adya anomaly constructions +
serializable histories + device/CPU trim agreement."""
import copy
import functools
import json
import random
from collections import Counter, defaultdict
from types import SimpleNamespace

import numpy as np
import pytest

from jepsen_tpu.elle import Graph, RW, WR, WW, check_cycles, list_append, rw_register
from jepsen_tpu.ops.scc import has_cycle, tarjan_scc, trim_to_cycles


def ok(process, txn):
    return {"type": "ok", "process": process, "f": "txn", "value": txn}


def fail(process, txn):
    return {"type": "fail", "process": process, "f": "txn", "value": txn}


# ---------------------------------------------------------------------------
# graph machinery
# ---------------------------------------------------------------------------

def test_trim_finds_cycle():
    # 0->1->2->0 plus a tail 3->0
    src = np.array([0, 1, 2, 3], dtype=np.int32)
    dst = np.array([1, 2, 0, 0], dtype=np.int32)
    mask = trim_to_cycles(4, src, dst)
    assert mask.tolist() == [True, True, True, False]


def test_trim_acyclic_empty():
    src = np.array([0, 1, 2], dtype=np.int32)
    dst = np.array([1, 2, 3], dtype=np.int32)
    assert not trim_to_cycles(4, src, dst).any()
    assert not has_cycle(4, src, dst)


def test_tarjan():
    edges = [(0, 1), (1, 2), (2, 0), (3, 4)]
    sccs = tarjan_scc(5, edges)
    assert sorted(sccs[0]) == [0, 1, 2]
    assert len(sccs) == 1


def test_check_cycles_classification():
    g = Graph(2)
    g.add(0, 1, WW)
    g.add(1, 0, WW)
    r = check_cycles(g)
    assert "G0" in r

    g = Graph(2)
    g.add(0, 1, WR)
    g.add(1, 0, WW)
    r = check_cycles(g)
    assert "G1c" in r

    g = Graph(2)
    g.add(0, 1, WR)
    g.add(1, 0, RW)
    r = check_cycles(g)
    assert "G-single" in r
    assert "G2" not in r

    g = Graph(2)
    g.add(0, 1, RW)
    g.add(1, 0, RW)
    r = check_cycles(g)
    assert "G2" in r


# ---------------------------------------------------------------------------
# list-append anomalies
# ---------------------------------------------------------------------------

def test_append_serializable_ok():
    h = [
        ok(0, [["append", "x", 1]]),
        ok(1, [["r", "x", [1]], ["append", "x", 2]]),
        ok(0, [["r", "x", [1, 2]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is True
    assert r["anomaly-types"] == []


def test_append_g0():
    h = [
        ok(0, [["append", "x", 1], ["append", "y", 1]]),
        ok(1, [["append", "x", 2], ["append", "y", 2]]),
        ok(2, [["r", "x", [1, 2]], ["r", "y", [2, 1]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G0" in r["anomaly-types"]


def test_append_g1c():
    h = [
        ok(0, [["append", "x", 1], ["r", "y", [1]]]),
        ok(1, [["append", "y", 1], ["r", "x", [1]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G1c" in r["anomaly-types"]


def test_append_g_single():
    h = [
        ok(0, [["append", "x", 1], ["append", "y", 1]]),
        ok(1, [["r", "x", [1]], ["r", "y", []]]),
        ok(2, [["r", "y", [1]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G-single" in r["anomaly-types"]


def test_append_g2_write_skew():
    h = [
        ok(0, [["r", "x", []], ["append", "y", 1]]),
        ok(1, [["r", "y", []], ["append", "x", 1]]),
        ok(2, [["r", "x", [1]], ["r", "y", [1]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G2" in r["anomaly-types"]


def test_append_g1a_aborted_read():
    h = [
        fail(0, [["append", "x", 9]]),
        ok(1, [["r", "x", [9]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G1a" in r["anomaly-types"]


def test_append_g1b_intermediate_read():
    h = [
        ok(0, [["append", "x", 1], ["append", "x", 2]]),
        ok(1, [["r", "x", [1]]]),
        ok(2, [["r", "x", [1, 2]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G1b" in r["anomaly-types"]


def test_append_internal():
    h = [ok(0, [["append", "x", 1], ["r", "x", []]])]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "internal" in r["anomaly-types"]


def test_append_incompatible_order():
    h = [
        ok(0, [["append", "x", 1]]),
        ok(1, [["append", "x", 2]]),
        ok(2, [["r", "x", [1, 2]]]),
        ok(3, [["r", "x", [2]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "incompatible-order" in r["anomaly-types"]


def serializable_append_history(rng, n_txns=300, n_keys=5, n_procs=5):
    """Executes random append txns sequentially against real lists: the
    resulting history is serializable by construction."""
    state = {k: [] for k in range(n_keys)}
    h = []
    counter = {k: 0 for k in range(n_keys)}
    for i in range(n_txns):
        txn = []
        for _ in range(rng.randint(1, 4)):
            k = rng.randrange(n_keys)
            if rng.random() < 0.5:
                txn.append(["r", k, list(state[k])])
            else:
                counter[k] += 1
                state[k].append(counter[k])
                txn.append(["append", k, counter[k]])
        h.append(ok(i % n_procs, txn))
    # final reads pin down version orders
    for k in range(n_keys):
        h.append(ok(0, [["r", k, list(state[k])]]))
    return h


def test_append_random_serializable():
    rng = random.Random(42)
    h = serializable_append_history(rng)
    r = list_append.check(h)
    assert r["valid?"] is True, r["anomaly-types"]
    assert r["txn-count"] == len(h)


def test_append_cpu_and_device_agree():
    rng = random.Random(1)
    good = serializable_append_history(rng, n_txns=100)
    bad = [
        ok(0, [["append", "x", 1], ["append", "y", 1]]),
        ok(1, [["append", "x", 2], ["append", "y", 2]]),
        ok(2, [["r", "x", [1, 2]], ["r", "y", [2, 1]]]),
    ]
    for h in (good, bad):
        r_dev = list_append.check(h, accelerator="auto")
        r_cpu = list_append.check(h, accelerator="cpu")
        assert r_dev["valid?"] == r_cpu["valid?"]
        assert r_dev["anomaly-types"] == r_cpu["anomaly-types"]


# ---------------------------------------------------------------------------
# rw-register
# ---------------------------------------------------------------------------

def test_wr_register_serializable():
    h = [
        ok(0, [["w", "x", 1]]),
        ok(1, [["r", "x", 1], ["w", "x", 2]]),
        ok(0, [["r", "x", 2]]),
    ]
    r = rw_register.check(h)
    assert r["valid?"] is True


def test_wr_register_g1a():
    h = [
        fail(0, [["w", "x", 9]]),
        ok(1, [["r", "x", 9]]),
    ]
    r = rw_register.check(h)
    assert r["valid?"] is False
    assert "G1a" in r["anomaly-types"]


def test_wr_register_internal():
    h = [ok(0, [["w", "x", 1], ["r", "x", 5]])]
    r = rw_register.check(h)
    assert r["valid?"] is False
    assert "internal" in r["anomaly-types"]


def test_wr_register_wr_cycle():
    # T0 reads T1's write, T1 reads T0's write: wr cycle (G1c)
    h = [
        ok(0, [["w", "x", 1], ["r", "y", 1]]),
        ok(1, [["w", "y", 1], ["r", "x", 1]]),
    ]
    r = rw_register.check(h)
    assert r["valid?"] is False
    assert "G1c" in r["anomaly-types"]


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

def test_append_gen_produces_txns():
    from jepsen_tpu.generator.simulate import default_context, invocations, quick
    import jepsen_tpu.generator as gen
    g = gen.limit(20, list_append.gen(key_count=3))
    h = quick({"concurrency": 2}, g)
    inv = invocations(h)
    assert len(inv) == 20
    for op in inv:
        assert op["f"] == "txn"
        for m in op["value"]:
            assert m[0] in ("r", "append")


def test_append_g1b_partial_observation_mid_read():
    # T3 observes T1's append of 1 without its 2, with T2's 3 after it —
    # an intermediate state even though the read's last element is final.
    h = [
        ok(0, [["append", "x", 1], ["append", "x", 2]]),
        ok(1, [["append", "x", 3]]),
        ok(2, [["r", "x", [1, 3]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "G1b" in r["anomaly-types"]


def test_append_txn_elements_out_of_order():
    # read observes a txn's own appends in the wrong order
    h = [
        ok(0, [["append", "x", 1], ["append", "x", 2]]),
        ok(1, [["r", "x", [2, 1]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is False
    assert "incompatible-order" in r["anomaly-types"]


def test_append_full_observation_not_g1b():
    h = [
        ok(0, [["append", "x", 1], ["append", "x", 2]]),
        ok(1, [["append", "x", 3]]),
        ok(2, [["r", "x", [1, 2, 3]]]),
    ]
    r = list_append.check(h)
    assert r["valid?"] is True


def test_wr_register_g1b_intermediate_read():
    # T0 writes x=1 then overwrites with x=2; T1 reads the intermediate 1
    h = [
        ok(0, [["w", "x", 1], ["w", "x", 2]]),
        ok(1, [["r", "x", 1]]),
    ]
    r = rw_register.check(h)
    assert r["valid?"] is False
    assert "G1b" in r["anomaly-types"]


def test_wr_register_consistency_models_forwarded():
    # A single-rw-edge cycle (G-single): T2 reads x before T1's overwrite
    # (rw T2->T1) but also observes T1's write to y (wr T1->T2). Blocked
    # under strict-serializable, allowed under read-committed.
    h = [
        ok(0, [["w", "x", 1]]),
        ok(1, [["r", "x", 1], ["w", "x", 2], ["w", "y", 2]]),
        ok(2, [["r", "y", 2], ["r", "x", 1]]),
    ]
    strict = rw_register.check(h)
    assert strict["valid?"] is False
    assert "G-single" in strict["anomaly-types"]
    rc = rw_register.check(h, consistency_models=("read-committed",))
    assert rc["valid?"] is True


def test_txn_utils():
    from jepsen_tpu.txn import (ext_reads, ext_writes, int_write_mops,
                                is_read, is_write, reduce_mops)
    txn = [["r", "x", 1], ["w", "x", 2], ["w", "x", 3], ["r", "y", None],
           ["w", "y", 9]]
    assert ext_reads(txn) == {"x": 1, "y": None}
    assert ext_writes(txn) == {"x": 3, "y": 9}
    assert int_write_mops(txn) == [["w", "x", 2]]
    assert is_read(["r", "x", None]) and is_write(["append", "x", 1])
    n = reduce_mops(lambda acc, op, m: acc + 1, 0,
                    [ok(0, txn), ok(1, [["r", "z", None]])])
    assert n == 6
    # appends never overwrite within a txn
    assert int_write_mops([["append", "x", 1], ["append", "x", 2]]) == []


def test_long_chain_no_false_cycle():
    """A serial history longer than the trim's iteration cap must not be
    reported cyclic: the capped peel leaves an acyclic residue and the
    exact pass must overrule it (regression: 35k-txn fake-mode append
    runs were flagged G1c with zero witness cycles)."""
    n = 2000
    g = Graph(n)
    for i in range(n - 1):
        g.add(i, i + 1, WW if i % 2 else WR)
    # trim with a cap far below the chain length: residue stays non-empty
    src, dst = g.arrays(None)
    mask = trim_to_cycles(n, src, dst, max_iters=16)
    assert mask.any()
    anoms = check_cycles(g)
    assert anoms == {}

    # same chain plus one real 3-cycle deep inside: found and classified
    g.add(500, 400, WR)  # 400..500 chain back-edge => ww+wr cycle
    anoms = check_cycles(g)
    assert "G1c" in anoms and anoms["G1c"]


def test_result_map_drops_empty_anomaly_lists():
    from jepsen_tpu.elle import result_map
    r = result_map({"G1c": []}, [], {})
    assert r["valid?"] is True and r["anomaly-types"] == []


# ---------------------------------------------------------------------------
# soundness differential vs a brute-force serializability oracle
# ---------------------------------------------------------------------------

def _brute_force_serializable(txns) -> bool:
    """Tries every ordering of the committed txns; serializable iff some
    order replays with every read seeing the exact current list state."""
    from itertools import permutations

    for perm in permutations(txns):
        lists: dict = {}
        ok = True
        for txn in perm:
            for f, k, v in txn:
                if f == "r":
                    if list(lists.get(k, [])) != list(v or []):
                        ok = False
                        break
                else:
                    lists.setdefault(k, []).append(v)
            if not ok:
                break
        if ok:
            return True
    return False


@pytest.mark.parametrize("accelerator,seed", [("cpu", 99), ("auto", 131)])
def test_append_checker_soundness_vs_brute_force(accelerator, seed):
    """Whenever the cycle checker CONVICTS a history (valid? False), a
    brute-force search over all serializations must agree no valid
    order exists — the checker must never accuse a serializable
    history, on the cpu oracle NOR the production columnar/φ-cluster
    path. Histories are tiny (<= 6 txns) so permutations are cheap;
    reads are randomly corrupted to produce both verdicts."""
    import random

    from jepsen_tpu.elle import list_append

    rng = random.Random(seed)
    convictions = acquittals = 0
    for trial in range(120):
        # build a sequentially-applied (serializable) history over 2 keys
        lists: dict = {}
        history = []
        txns = []
        for i in range(rng.randrange(3, 7)):
            ops = []
            k = rng.randrange(2)
            if rng.random() < 0.6:
                ops.append(["r", k, list(lists.get(k, []))])
            lists.setdefault(k, []).append(i)
            ops.append(["append", k, i])
            txns.append(ops)
            history.append({"type": "invoke", "f": "txn", "process": i % 3,
                            "value": [[f, kk, None if f == "r" else vv]
                                      for f, kk, vv in ops], "index": 2 * i})
            history.append({"type": "ok", "f": "txn", "process": i % 3,
                            "value": ops, "index": 2 * i + 1})
        if rng.random() < 0.6:
            # corrupt one read to a random (often impossible) state
            reads = [(ti, oi) for ti, t in enumerate(txns)
                     for oi, (f, _, _) in enumerate(t) if f == "r"]
            if reads:
                ti, oi = reads[rng.randrange(len(reads))]
                k = txns[ti][oi][1]
                # the ok op's value aliases txns[ti], so this mutates
                # the history entry too
                txns[ti][oi] = ["r", k, [rng.randrange(10)]]
        out = list_append.check(history, accelerator=accelerator,
                                consistency_models=("serializable",))
        if out.get("valid?") is False:
            convictions += 1
            assert not _brute_force_serializable(txns), (
                f"trial {trial}: checker convicted a serializable history "
                f"{txns}\nanomalies: {out.get('anomaly-types')}")
        else:
            acquittals += 1
    # the fuzz must have exercised both verdicts to mean anything
    assert convictions >= 10 and acquittals >= 10, (convictions, acquittals)


def test_wr_checker_soundness_vs_brute_force():
    """rw-register twin of the append soundness fuzz: a conviction must
    mean NO serialization replays with every read seeing the latest
    write (writes are unique ints, so version attribution is exact)."""
    import random
    from itertools import permutations

    from jepsen_tpu.elle import rw_register

    def brute_force_serializable(txns) -> bool:
        for perm in permutations(txns):
            regs: dict = {}
            ok = True
            for txn in perm:
                for f, k, v in txn:
                    if f == "r":
                        if regs.get(k) != v:
                            ok = False
                            break
                    else:
                        regs[k] = v
                if not ok:
                    break
            if ok:
                return True
        return False

    rng = random.Random(41)
    convictions = acquittals = 0
    for trial in range(150):
        regs: dict = {}
        versions: dict = {0: [None], 1: [None]}  # per-key version order
        history = []
        txns = []
        for i in range(rng.randrange(3, 7)):
            # mix same-key read-then-write txns (they trace version
            # successions, powering rw-edge inference) with cross-key ones
            ops = []
            k = rng.randrange(2)
            wk = k if rng.random() < 0.5 else 1 - k
            if rng.random() < 0.8:
                ops.append(["r", k, regs.get(k)])
            regs[wk] = i  # unique write values
            versions[wk].append(i)
            ops.append(["w", wk, i])
            txns.append(ops)
            history.append({"type": "invoke", "f": "txn", "process": i % 3,
                            "value": [[f, kk, None if f == "r" else vv]
                                      for f, kk, vv in ops], "index": 2 * i})
            history.append({"type": "ok", "f": "txn", "process": i % 3,
                            "value": ops, "index": 2 * i + 1})
        if rng.random() < 0.7:
            # corrupt one read to a STALE version of its key (a value the
            # key really held earlier, or the initial None) — phantom
            # values would be unattributable and prove nothing
            reads = [(ti, oi) for ti, t in enumerate(txns)
                     for oi, (f, _, _) in enumerate(t) if f == "r"]
            if reads:
                ti, oi = reads[rng.randrange(len(reads))]
                k = txns[ti][oi][1]
                cur = txns[ti][oi][2]
                older = [v for v in versions[k] if v != cur]
                if older:
                    # the ok op's value aliases txns[ti]
                    txns[ti][oi] = ["r", k, rng.choice(older)]
        out = rw_register.check(history, accelerator="cpu",
                                consistency_models=("serializable",))
        if out.get("valid?") is False:
            convictions += 1
            assert not brute_force_serializable(txns), (
                f"trial {trial}: convicted a serializable history {txns}\n"
                f"anomalies: {out.get('anomaly-types')}")
        else:
            acquittals += 1
    assert convictions >= 10 and acquittals >= 10, (convictions, acquittals)


def test_wr_written_none_is_not_the_initial_state():
    """A txn can WRITE a literal None; reading it must not be conflated
    with reading the initial state (which would fabricate rw edges and
    convict a serializable history)."""
    from jepsen_tpu.elle import rw_register

    txns = [[["w", 0, None], ["w", 1, 1]],
            [["r", 1, 1], ["r", 0, None]]]
    h = []
    for i, ops in enumerate(txns):
        h.append({"type": "invoke", "f": "txn", "process": i,
                  "value": [[f, k, None if f == "r" else v]
                            for f, k, v in ops], "index": 2 * i})
        h.append({"type": "ok", "f": "txn", "process": i, "value": ops,
                  "index": 2 * i + 1})
    out = rw_register.check(h, accelerator="cpu",
                            consistency_models=("serializable",))
    assert out["valid?"] is True, out  # T1;T2 replays fine


# ---------------------------------------------------------------------------
# realtime / process precedence (strict-serializable surface)
# ---------------------------------------------------------------------------

def inv(process, txn):
    return {"type": "invoke", "process": process, "f": "txn",
            "value": [[f, k, None if f in ("r",) else v] for f, k, v in txn]}


def test_append_realtime_cycle_stale_read():
    # T1 appends 1 and completes; T2, invoked strictly after, still reads
    # the empty list. Serializable (order T2 < T1) but not strictly so.
    h = [
        inv(0, [["append", "x", 1]]),
        ok(0, [["append", "x", 1]]),
        inv(1, [["r", "x", []]]),
        ok(1, [["r", "x", []]]),
        inv(2, [["r", "x", [1]]]),
        ok(2, [["r", "x", [1]]]),
    ]
    strict = list_append.check(h, accelerator="cpu")
    assert strict["valid?"] is False
    assert "realtime-cycle" in strict["anomaly-types"]
    serial = list_append.check(h, accelerator="cpu",
                               consistency_models=("serializable",))
    assert serial["valid?"] is True


def test_append_process_cycle_completion_only_history():
    # Same stale read by ONE process, with no invocation events at all:
    # the per-process succession still orders T1 < T2.
    h = [
        ok(0, [["append", "x", 1]]),
        ok(0, [["r", "x", []]]),
        ok(1, [["r", "x", [1]]]),
    ]
    strict = list_append.check(h, accelerator="cpu")
    assert strict["valid?"] is False
    assert "process-cycle" in strict["anomaly-types"]
    seq = list_append.check(h, accelerator="cpu",
                            consistency_models=("sequential",))
    assert seq["valid?"] is False
    serial = list_append.check(h, accelerator="cpu",
                               consistency_models=("serializable",))
    assert serial["valid?"] is True


def test_wr_register_realtime_cycle_stale_read():
    # rw-register twin: T1 writes x=1 and completes, then T2 reads the
    # initial state. The init-successor inference yields rw T2 -> T1;
    # realtime yields T1 -> T2.
    h = [
        inv(0, [["w", "x", 1]]),
        ok(0, [["w", "x", 1]]),
        inv(1, [["r", "x", None]]),
        ok(1, [["r", "x", None]]),
    ]
    strict = rw_register.check(h, accelerator="cpu")
    assert strict["valid?"] is False
    assert "realtime-cycle" in strict["anomaly-types"]
    serial = rw_register.check(h, accelerator="cpu",
                               consistency_models=("serializable",))
    assert serial["valid?"] is True


def test_concurrent_txns_no_false_realtime_cycle():
    # Overlapping intervals: T1 and T2 both in flight; T2 reads [] while
    # T1's append lands after. Strictly serializable -> no anomaly.
    h = [
        inv(0, [["append", "x", 1]]),
        inv(1, [["r", "x", []]]),
        ok(0, [["append", "x", 1]]),
        ok(1, [["r", "x", []]]),
        inv(2, [["r", "x", [1]]]),
        ok(2, [["r", "x", [1]]]),
    ]
    strict = list_append.check(h, accelerator="cpu")
    assert strict["valid?"] is True, strict


def test_realtime_soundness_fuzz_linearized_store():
    """Histories generated by applying each txn atomically at a random
    point inside its [invoke, complete] interval are strictly
    serializable by construction; the checker must never convict one."""
    rng = random.Random(4242)
    for trial in range(60):
        n_txns = rng.randrange(6, 14)
        concurrency = rng.randrange(2, 5)
        # build txn intents
        intents = []
        ctr = 0
        for _ in range(n_txns):
            txn = []
            for _ in range(rng.randrange(1, 4)):
                k = rng.randrange(2)
                if rng.random() < 0.5:
                    txn.append(["r", k, None])
                else:
                    ctr += 1
                    txn.append(["append", k, ctr])
            intents.append(txn)
        # schedule: each txn has invoke < apply < complete events; at most
        # `concurrency` txns in flight; apply executes against the store
        lists: dict = {}
        history = []
        in_flight: list = []  # (txn_idx, applied?)
        next_txn = 0
        done = 0
        state: dict = {}
        while done < n_txns:
            choices = []
            if next_txn < n_txns and len(in_flight) < concurrency:
                choices.append("invoke")
            for idx, (ti, applied) in enumerate(in_flight):
                choices.append(("apply", idx) if not applied
                               else ("complete", idx))
            ev = choices[rng.randrange(len(choices))]
            if ev == "invoke":
                p = next_txn  # fresh process per txn keeps pairing simple
                history.append({"type": "invoke", "process": p, "f": "txn",
                                "value": [[f, k, None if f == "r" else v]
                                          for f, k, v in intents[next_txn]]})
                in_flight.append((next_txn, False))
                next_txn += 1
            elif ev[0] == "apply":
                ti, _ = in_flight[ev[1]]
                executed = []
                for f, k, v in intents[ti]:
                    if f == "r":
                        executed.append(["r", k, list(lists.get(k, []))])
                    else:
                        lists.setdefault(k, []).append(v)
                        executed.append(["append", k, v])
                state[ti] = executed
                in_flight[ev[1]] = (ti, True)
            else:
                ti, _ = in_flight.pop(ev[1])
                history.append({"type": "ok", "process": ti, "f": "txn",
                                "value": state[ti]})
                done += 1
        out = list_append.check(history, accelerator="cpu")
        assert out["valid?"] is True, (
            f"trial {trial}: convicted a linearized history: "
            f"{out['anomaly-types']}\n{history}")


def test_strict_soundness_fuzz_sequential_histories():
    """For a fully sequential history (each txn completes before the next
    invokes) the ONLY realtime-respecting serialization is history order;
    a strict-serializable conviction must mean that order fails replay."""
    rng = random.Random(777)

    def replays_in_order(txns):
        lists: dict = {}
        for txn in txns:
            for f, k, v in txn:
                if f == "r":
                    if list(lists.get(k, [])) != list(v or []):
                        return False
                else:
                    lists.setdefault(k, []).append(v)
        return True

    convictions = acquittals = 0
    for trial in range(120):
        lists = {}
        history = []
        txns = []
        for i in range(rng.randrange(3, 7)):
            ops = []
            k = rng.randrange(2)
            if rng.random() < 0.6:
                ops.append(["r", k, list(lists.get(k, []))])
            lists.setdefault(k, []).append(i)
            ops.append(["append", k, i])
            txns.append(ops)
            history.append(inv(i % 3, ops))
            history.append(ok(i % 3, ops))
        if rng.random() < 0.7:
            reads = [(ti, oi) for ti, t in enumerate(txns)
                     for oi, (f, _, _) in enumerate(t) if f == "r"]
            if reads:
                ti, oi = reads[rng.randrange(len(reads))]
                k = txns[ti][oi][1]
                corrupt = rng.choice([[], [rng.randrange(8)]])
                txns[ti][oi] = ["r", k, corrupt]
        out = list_append.check(history, accelerator="cpu")
        if out["valid?"] is False:
            convictions += 1
            assert not replays_in_order(txns), (
                f"trial {trial}: strict conviction of a history that "
                f"replays in realtime order {txns}\n{out['anomaly-types']}")
        else:
            acquittals += 1
    assert convictions >= 10 and acquittals >= 10, (convictions, acquittals)


def test_mixed_process_and_realtime_cycle_detected():
    """A strict-serializability violation whose cycle needs BOTH a
    process edge (between completion-only txns) and a realtime edge:
    A ->process B ->wr C ->realtime D ->rw A. Neither order alone closes
    the cycle, so the realtime search must walk process edges too."""
    h = [
        ok(0, [["append", "x", 1]]),            # A (no invoke events)
        ok(0, [["append", "y", 1]]),            # B: process A -> B
        inv(1, [["r", "y", [1]]]),
        ok(1, [["r", "y", [1]]]),               # C: wr B -> C
        inv(2, [["r", "x", []]]),               # invoked after C completed
        ok(2, [["r", "x", []]]),                # D: realtime C -> D, rw D -> A
        inv(3, [["r", "x", [1]]]),
        ok(3, [["r", "x", [1]]]),               # E: establishes x order [1]
    ]
    strict = list_append.check(h, accelerator="cpu")
    assert strict["valid?"] is False
    assert "realtime-cycle" in strict["anomaly-types"], strict["anomaly-types"]
    serial = list_append.check(h, accelerator="cpu",
                               consistency_models=("serializable",))
    assert serial["valid?"] is True, serial


# ---------------------------------------------------------------------------
# richer rw-register version-order inference (round-2 strengthening)
# ---------------------------------------------------------------------------

def _rw_history(txns, procs=3):
    h = []
    for i, ops in enumerate(txns):
        h.append({"type": "invoke", "f": "txn", "process": i % procs,
                  "value": [[f, k, None if f == "r" else v]
                            for f, k, v in ops], "index": 2 * i})
        h.append({"type": "ok", "f": "txn", "process": i % procs,
                  "value": ops, "index": 2 * i + 1})
    return h


def test_wr_init_read_orders_before_all_writers():
    """G-single the old single-writer-only init inference missed: key 1
    has TWO writers, yet a None read of key 1 still proves the reader
    precedes both."""
    txns = [
        [["w", 0, 10], ["w", 1, 100]],            # W1: writes both keys
        [["w", 1, 101]],                          # W2: second writer of 1
        [["r", 0, 10], ["r", 1, None]],           # T: saw W1's key-0 write
    ]
    out = rw_register.check(_rw_history(txns), accelerator="cpu",
                            consistency_models=("serializable",))
    # wr edge W1->T (read 10); rw edge T->W1 (init read of key 1): cycle
    assert out["valid?"] is False
    assert "G-single" in out["anomaly-types"]


def test_wr_init_read_two_writers_acquits_consistent():
    """Same shape but consistent: T read key 0's initial state too, so T
    precedes everything — acyclic, serializable."""
    txns = [
        [["w", 0, 10], ["w", 1, 100]],
        [["w", 1, 101]],
        [["r", 0, None], ["r", 1, None]],
    ]
    out = rw_register.check(_rw_history(txns), accelerator="cpu",
                            consistency_models=("serializable",))
    assert out["valid?"] is True


def test_wr_cyclic_versions_detected():
    """Two txns whose traces order each other's writes both ways: the
    version graph 1->2->1 can't come from any register execution."""
    txns = [
        [["r", 0, 1], ["w", 0, 2]],   # traces 1 -> 2
        [["r", 0, 2], ["w", 0, 1]],   # traces 2 -> 1
    ]
    out = rw_register.check(_rw_history(txns), accelerator="cpu",
                            consistency_models=("read-uncommitted",))
    assert out["valid?"] is False
    assert "cyclic-versions" in out["anomaly-types"]
    (anom,) = out["anomalies"]["cyclic-versions"]
    assert anom["key"] == 0 and set(anom["versions"]) == {1, 2}


def test_wr_version_chain_composes_g_single():
    """Write-follows-read chains compose: T read v1; v1's successor chain
    v1->v2->v3 gives T rw-> writer(v2) ww-> writer(v3); if writer(v3)'s
    write was read by a txn T depends on, the cycle closes."""
    txns = [
        [["w", 0, 1]],                 # A
        [["r", 0, 1], ["w", 0, 2]],    # B traces 1->2
        [["r", 0, 2], ["w", 0, 3], ["w", 1, 30]],  # C traces 2->3, writes k1
        [["r", 1, 30], ["r", 0, 1]],   # T: depends on C (wr), but read STALE 1
    ]
    out = rw_register.check(_rw_history(txns), accelerator="cpu",
                            consistency_models=("serializable",))
    # T rw-> B (succ of 1) ww-> C wr-> T
    assert out["valid?"] is False
    assert "G-single" in out["anomaly-types"] or "G2" in out["anomaly-types"]


def test_list_append_fast_scan_matches_python_twin(monkeypatch):
    """The columnar per-key read scan and the pure-Python twin must emit
    identical anomalies across random histories seeded with every
    anomaly class it classifies (G1a, G1b, duplicates, incompatible
    orders, unobserved writers)."""
    import json
    import random as rnd

    def run(history, force_py):
        if force_py:
            with_mp = monkeypatch.context()
            with with_mp as m:
                m.setattr(list_append, "_scan_reads_fast",
                          lambda *a, **kw: False)
                return list_append.check(history, accelerator="cpu")
        return list_append.check(history, accelerator="cpu")

    rng = rnd.Random(97)
    for trial in range(40):
        n_keys = rng.randint(1, 3)
        vals = {k: [] for k in range(n_keys)}
        txns = []
        for i in range(rng.randint(3, 8)):
            ops = []
            k = rng.randrange(n_keys)
            n_app = rng.choice([1, 1, 1, 2])  # sometimes multi-append
            for _ in range(n_app):
                v = len(vals[k]) + 1000 * k
                vals[k].append(v)
                ops.append(["append", k, v])
            if rng.random() < 0.8:
                rk = rng.randrange(n_keys)
                ops.append(["r", rk, list(vals[rk])])
            txns.append(ops)
        history = []
        for i, ops in enumerate(txns):
            history.append({"type": "invoke", "process": i % 3, "f": "txn",
                            "value": [[f, k, None if f == "r" else v]
                                      for f, k, v in ops]})
            history.append({"type": "ok", "process": i % 3, "f": "txn",
                            "value": ops})
        # corruptions: drop a mid element (G1b/incompatible), duplicate an
        # element, insert a phantom, read a failed write
        c = rng.random()
        reads = [(ti, oi) for ti, t in enumerate(txns)
                 for oi, m in enumerate(t) if m[0] == "r" and len(m[2]) >= 2]
        if c < 0.5 and reads:
            ti, oi = reads[rng.randrange(len(reads))]
            r = list(txns[ti][oi][2])
            kind = rng.random()
            if kind < 0.3:
                del r[rng.randrange(len(r) - 1)]       # lose a mid element
            elif kind < 0.6:
                r.append(r[rng.randrange(len(r))])     # duplicate
            elif kind < 0.8:
                r.append(999_999)                      # phantom value
            else:
                r[0], r[1] = r[1], r[0]                # reorder
            txns[ti][oi][2] = r
        if c >= 0.5 and c < 0.6:
            history.append({"type": "fail", "process": 9, "f": "txn",
                            "value": [["append", 0, 777]]})
            if reads:
                ti, oi = reads[rng.randrange(len(reads))]
                txns[ti][oi][2] = list(txns[ti][oi][2]) + [777]

        fast = run(history, force_py=False)
        slow = run(history, force_py=True)
        assert fast["valid?"] == slow["valid?"], trial
        assert fast["anomaly-types"] == slow["anomaly-types"], (
            trial, fast["anomaly-types"], slow["anomaly-types"])
        for typ in fast["anomalies"]:
            f_recs = fast["anomalies"][typ]
            s_recs = slow["anomalies"][typ]
            if typ in ("G1c", "realtime-cycle", "process-cycle"):
                continue  # cycle exemplars may legitimately differ
            norm = lambda rs: sorted(  # noqa: E731
                json.dumps(x, sort_keys=True, default=repr) for x in rs)
            assert norm(f_recs) == norm(s_recs), (trial, typ)


def test_list_append_fast_scan_trailing_empty_read():
    """Regression: a trailing empty read must not steal the final element
    of its neighbour's segment (reduceat-clipping bug)."""
    txns = [
        [["append", 0, 1], ["append", 0, 2], ["append", 0, 3]],
        [["r", 0, [1, 2, 3]]],
        [["r", 0, [1, 9]]],   # stale/invented tail: incompatible-order
        [["r", 0, []]],
    ]
    history = []
    for i, ops in enumerate(txns):
        history.append({"type": "invoke", "process": i % 3, "f": "txn",
                        "value": ops})
        history.append({"type": "ok", "process": i % 3, "f": "txn",
                        "value": ops})
    out = list_append.check(history, accelerator="cpu",
                            consistency_models=("read-committed",))
    assert "incompatible-order" in out["anomaly-types"]


def test_list_append_fast_scan_rejects_float_domain():
    """Regression: float values must fall back to the Python twin, not
    truncate (2.7 -> 2 fabricated a G1a against a failed write)."""
    history = [
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["append", 0, 2.1]]},
        {"type": "fail", "process": 1, "f": "txn",
         "value": [["append", 0, 2.7]]},
        {"type": "ok", "process": 2, "f": "txn",
         "value": [["r", 0, [2.1]]]},
    ]
    out = list_append.check(history, accelerator="cpu",
                            consistency_models=("serializable",))
    assert out["valid?"] is True, out["anomaly-types"]


def test_list_append_fast_scan_big_int_fallback():
    """Values at/above 2^53 can't be float-verified: the fast path must
    fall back to the Python twin rather than silently rounding them."""
    big = (1 << 53) + 1
    history = [
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["append", 0, big]]},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["r", 0, [big]]]},
    ]
    out = list_append.check(history, accelerator="cpu",
                            consistency_models=("serializable",))
    assert out["valid?"] is True, out["anomaly-types"]
    assert out["read-scan-keys"]["python"] == 1


# ---------------------------------------------------------------------------
# φ-interval cluster path (production check_cycles) vs the cpu oracle
# ---------------------------------------------------------------------------

def test_phi_clusters_merge_intervals():
    from jepsen_tpu.elle import _phi_clusters
    import numpy as np

    # back edges (src_phi, dst_phi): [2,7], [5,9] overlap; [20,21] apart
    src_phi = np.asarray([7, 9, 21])
    dst_phi = np.asarray([2, 5, 20])
    assert _phi_clusters(src_phi, dst_phi) == [(2, 9), (20, 21)]
    # self-loop (equal phi) is its own point interval
    assert _phi_clusters(np.asarray([4]), np.asarray([4])) == [(4, 4)]


def test_batch_cluster_screen_exact():
    from jepsen_tpu.ops.scc import batch_cluster_screen
    import numpy as np

    # cluster 0: 3-cycle; cluster 1: acyclic chain; cluster 2: self-loop
    cid = np.asarray([0, 0, 0, 1, 1, 2], np.int32)
    src = np.asarray([0, 1, 2, 0, 1, 0], np.int32)
    dst = np.asarray([1, 2, 0, 1, 2, 0], np.int32)
    flags = batch_cluster_screen(cid, src, dst, 3, 3)
    assert flags.tolist() == [True, False, True]
    # empty edge set: nothing flagged
    z = np.zeros(0, np.int32)
    assert batch_cluster_screen(z, z, z, 2, 4).tolist() == [False, False]


def _interleaved_history(rng, n_txns=60, n_keys=3, corrupt=0):
    """Concurrent-process append history with real invoke/ok intervals
    (so φ exists), optionally corrupting reads to inject anomalies."""
    lists: dict = {}
    history = []
    open_ops: dict = {}
    procs = list(range(4))
    i = 0
    while i < n_txns or open_ops:
        p = rng.choice(procs)
        if p in open_ops:
            mops = open_ops.pop(p)
            applied = []
            for f, k, v in mops:
                if f == "append":
                    lists.setdefault(k, []).append(v)
                    applied.append(["append", k, v])
                else:
                    applied.append(["r", k, list(lists.get(k, []))])
            history.append({"type": "ok", "process": p, "f": "txn",
                            "value": applied})
        elif i < n_txns:
            mops = []
            for _ in range(rng.randrange(1, 3)):
                k = rng.randrange(n_keys)
                if rng.random() < 0.5:
                    mops.append(["r", k, None])
                else:
                    mops.append(["append", k, 1000 * (i + 1) + len(mops)])
            history.append({"type": "invoke", "process": p, "f": "txn",
                            "value": mops})
            open_ops[p] = mops
            i += 1
    for _ in range(corrupt):
        oks = [op for op in history if op["type"] == "ok"]
        op = rng.choice(oks)
        reads = [m for m in op["value"] if m[0] == "r"]
        if reads:
            m = rng.choice(reads)
            m[2] = list(m[2][:-1]) if m[2] else [rng.randrange(5)]
    return history


def test_phi_path_parity_fuzz_vs_cpu_oracle():
    """The φ-cluster production path must reach the same verdict and
    anomaly-type set as the trim+Tarjan cpu oracle on fuzzed concurrent
    histories, clean and corrupted alike."""
    rng = random.Random(7)
    saw_invalid = saw_valid = 0
    for trial in range(40):
        h = _interleaved_history(rng, corrupt=rng.randrange(3))
        r_fast = list_append.check(h, accelerator="auto")
        r_cpu = list_append.check(h, accelerator="cpu")
        assert r_fast["valid?"] == r_cpu["valid?"], (trial, r_fast, r_cpu)
        assert r_fast["anomaly-types"] == r_cpu["anomaly-types"], (
            trial, r_fast["anomaly-types"], r_cpu["anomaly-types"])
        if r_cpu["valid?"]:
            saw_valid += 1
        else:
            saw_invalid += 1
    assert saw_valid >= 5 and saw_invalid >= 5, (saw_valid, saw_invalid)


def test_phi_path_device_screen_parity():
    """Force the device (virtual-cpu jax here) batched screen and check it
    agrees with the oracle on a history with injected wr cycles."""
    rng = random.Random(11)
    h = _interleaved_history(rng, corrupt=2)
    r_dev = list_append.check(h, accelerator="tpu")
    r_cpu = list_append.check(h, accelerator="cpu")
    assert r_dev["valid?"] == r_cpu["valid?"]
    assert r_dev["anomaly-types"] == r_cpu["anomaly-types"]


def test_phi_path_timing_cycles_parity():
    """Realtime/process cycles must survive the cluster decomposition:
    a stale read closed by realtime order is found by both paths."""
    h = [
        {"type": "invoke", "process": 0, "f": "txn",
         "value": [["append", "x", 1]]},
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["append", "x", 1]]},
        {"type": "invoke", "process": 1, "f": "txn",
         "value": [["append", "x", 2], ["r", "x", None]]},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["append", "x", 2], ["r", "x", [1, 2]]]},
        # realtime-after both, but reads the pre-2 state: stale
        {"type": "invoke", "process": 2, "f": "txn",
         "value": [["r", "x", None]]},
        {"type": "ok", "process": 2, "f": "txn",
         "value": [["r", "x", [1]]]},
    ]
    r_fast = list_append.check(h, accelerator="auto")
    r_cpu = list_append.check(h, accelerator="cpu")
    assert r_fast["valid?"] is False and r_cpu["valid?"] is False
    assert r_fast["anomaly-types"] == r_cpu["anomaly-types"]


def test_phi_path_oversized_cluster_falls_back(monkeypatch):
    """Clusters beyond MATRIX_CLUSTER_MAX must still be classified
    exactly (straight to the host pass, no matrix)."""
    import jepsen_tpu.elle as elle_mod

    monkeypatch.setattr(elle_mod, "MATRIX_CLUSTER_MAX", 2)
    rng = random.Random(13)
    h = _interleaved_history(rng, corrupt=2)
    r_fast = list_append.check(h, accelerator="auto")
    r_cpu = list_append.check(h, accelerator="cpu")
    assert r_fast["valid?"] == r_cpu["valid?"]
    assert r_fast["anomaly-types"] == r_cpu["anomaly-types"]


# ---------------------------------------------------------------------------
# columnar builder vs Python-builder oracle
# ---------------------------------------------------------------------------

def _messy_history(rng, n_txns=50):
    """History exercising every columnar corner: multi-appends, failed
    writes, info txns, empty reads, then random corruptions (dropped
    elements, duplicated elements, failed-value reads, phantom values)."""
    lists: dict = {}
    history = []
    vc = [0]

    def nv():
        vc[0] += 1
        return vc[0]

    for i in range(n_txns):
        p = i % 5
        k = rng.randrange(3)
        kind = rng.random()
        if kind < 0.15:
            # failed multi-append
            vals = [nv() for _ in range(rng.randrange(1, 3))]
            mops = [["append", k, v] for v in vals]
            history.append({"type": "invoke", "process": p, "f": "txn",
                            "value": [[f, kk, vv] for f, kk, vv in mops]})
            history.append({"type": "fail", "process": p, "f": "txn",
                            "value": mops})
            continue
        mops = []
        for _ in range(rng.randrange(1, 4)):
            if rng.random() < 0.5:
                mops.append(["r", k, None])
            else:
                v = nv()
                lists.setdefault(k, []).append(v)
                mops.append(["append", k, v])
        applied = [
            ["r", m[1], list(lists.get(m[1], []))] if m[0] == "r" else m
            for m in mops]
        history.append({"type": "invoke", "process": p, "f": "txn",
                        "value": mops})
        t = "info" if kind < 0.22 else "ok"
        history.append({"type": t, "process": p, "f": "txn",
                        "value": applied if t == "ok" else mops})
    # corruptions
    for _ in range(rng.randrange(4)):
        oks = [op for op in history if op["type"] == "ok"]
        op = rng.choice(oks)
        reads = [m for m in op["value"] if m[0] == "r"]
        if not reads:
            continue
        m = rng.choice(reads)
        roll = rng.random()
        if roll < 0.3 and m[2]:
            m[2] = list(m[2][:-1])          # dropped tail element
        elif roll < 0.5 and m[2]:
            m[2] = list(m[2]) + [m[2][0]]   # duplicated element
        elif roll < 0.75:
            m[2] = list(m[2]) + [vc[0] + rng.randrange(1, 9)]  # phantom
        else:
            m[2] = [rng.randrange(1, vc[0] + 1)]  # arbitrary single value
    return history


def test_columnar_builder_parity_fuzz():
    """The columnar builder must reach the oracle's verdict and
    anomaly-type set on messy histories (multi-appends, fails, infos,
    corrupted reads)."""
    rng = random.Random(23)
    invalid = 0
    for trial in range(60):
        h = _messy_history(rng)
        r_col = list_append.check(h, accelerator="auto")
        r_cpu = list_append.check(h, accelerator="cpu")
        assert r_col.get("builder") == "columnar", "fast path must engage"
        assert r_col["valid?"] == r_cpu["valid?"], (trial, r_col, r_cpu)
        assert r_col["anomaly-types"] == r_cpu["anomaly-types"], (
            trial, r_col["anomaly-types"], r_cpu["anomaly-types"])
        assert r_col["edge-count"] == r_cpu["edge-count"], trial
        invalid += 0 if r_cpu["valid?"] else 1
    assert invalid >= 15, invalid


def test_columnar_falls_back_on_non_int_domains():
    for bad_val in ("s", 2.5, True, (1 << 53) + 1):
        h = [
            {"type": "ok", "process": 0, "f": "txn",
             "value": [["append", 0, bad_val]]},
            {"type": "ok", "process": 1, "f": "txn",
             "value": [["r", 0, [bad_val]]]},
        ]
        r = list_append.check(h, accelerator="auto")
        assert "builder" not in r, bad_val  # python builder took over


def test_columnar_out_of_range_read_value_no_writer_collision():
    """Regression: a corrupt read ending in a value >= 2^32 must not
    alias another key's writer through the 32-bit composite join."""
    h = [
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["append", 0, 7]]},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["append", 1, 1]]},
        # key-1 read whose last element is (1<<32)|7 — with kid=1 the
        # composite equals key-0's append of 7 if unmasked
        {"type": "ok", "process": 2, "f": "txn",
         "value": [["r", 1, [(1 << 32) | 7]]]},
    ]
    r_col = list_append.check(h, accelerator="auto")
    r_cpu = list_append.check(h, accelerator="cpu")
    assert r_col["edge-count"] == r_cpu["edge-count"]
    assert r_col["anomaly-types"] == r_cpu["anomaly-types"]


def test_columnar_spine_tie_break_matches_oracle():
    """Regression: on equal-length conflicting reads the spine must be
    the FIRST longest read (the oracle's max(key=len) semantics)."""
    h = [
        {"type": "ok", "process": 0, "f": "txn",
         "value": [["append", 0, 1]]},
        {"type": "ok", "process": 1, "f": "txn",
         "value": [["append", 0, 2]]},
        {"type": "ok", "process": 2, "f": "txn",
         "value": [["append", 0, 3]]},
        {"type": "ok", "process": 3, "f": "txn",
         "value": [["r", 0, [1, 2]]]},
        {"type": "ok", "process": 4, "f": "txn",
         "value": [["r", 0, [1, 3]]]},
    ]
    r_col = list_append.check(h, accelerator="auto")
    r_cpu = list_append.check(h, accelerator="cpu")
    assert r_col["anomaly-types"] == r_cpu["anomaly-types"]
    assert r_col["edge-count"] == r_cpu["edge-count"]


def test_batch_cluster_screen_chunks_over_budget(monkeypatch):
    """Batches beyond the element budget split along the cluster axis
    without changing verdicts."""
    from jepsen_tpu.ops import scc as scc_mod
    import numpy as np

    monkeypatch.setattr(scc_mod, "SCREEN_MAX_ELEMS", 8 * 8 * 2)  # 2/chunk
    cid = np.asarray([0, 0, 1, 2, 2, 2, 4], np.int32)
    src = np.asarray([0, 1, 0, 0, 1, 2, 0], np.int32)
    dst = np.asarray([1, 0, 1, 1, 2, 0, 0], np.int32)
    flags = scc_mod.batch_cluster_screen(cid, src, dst, 5, 3)
    assert flags.tolist() == [True, False, True, False, True]


def test_columnar_fast_flatten_fallbacks():
    """The vectorized pass B declines regimes the general loop handles —
    huge int keys (beyond int64), non-int keys, bool append values —
    and _build still produces a columnar result for them."""
    from jepsen_tpu.elle import columnar

    def h(key, val=1):
        return [
            {"type": "invoke", "process": 0,
             "value": [["append", key, val]]},
            {"type": "ok", "process": 0, "value": [["append", key, val]]},
            {"type": "invoke", "process": 0, "value": [["r", key, None]]},
            {"type": "ok", "process": 0, "value": [["r", key, [val]]]},
        ]

    for key in (1 << 63, -(1 << 63) - 1, "k"):
        types = [op.get("type") for op in h(key)]
        txns = [op for op, t in zip(h(key), types) if t == "ok"]
        assert columnar._flatten_mops_fast(txns) is None, key
        parts = columnar._build(h(key))   # general loop still builds
        assert parts is not None, key
        graph, txns_out, extras, n_keys = parts
        assert n_keys == 1 and len(txns_out) == 2

    # bool append value: BOTH paths decline (python builder territory)
    txns = [op for op in h(0, True) if op["type"] == "ok"]
    assert columnar._flatten_mops_fast(txns) is None
    assert columnar._build(h(0, True)) is None


def test_c_front_vs_python_front_parity_fuzz(monkeypatch):
    """The native C parser front (native/columnar_ext.c) and the
    numpy Python front must produce identical results — verdict,
    anomaly types, edge counts, extras — on messy histories. When the
    C extension is unavailable this reduces to a self-check."""
    from jepsen_tpu.elle import columnar
    from jepsen_tpu.native import columnar_c

    if not columnar_c.available():
        pytest.skip("C toolchain unavailable")

    rng = random.Random(71)
    engaged = 0
    for trial in range(40):
        h = _messy_history(rng)
        r_c = list_append.check(h, accelerator="auto")
        with monkeypatch.context() as mp:
            mp.setattr(columnar, "_cmod", lambda: None)
            r_py = list_append.check(h, accelerator="auto")
        if r_c.get("builder") != "columnar":
            assert r_py.get("builder") != "columnar", trial
            continue
        engaged += 1
        assert r_c["valid?"] == r_py["valid?"], (trial, r_c, r_py)
        assert r_c["anomaly-types"] == r_py["anomaly-types"], trial
        assert r_c["edge-count"] == r_py["edge-count"], trial
        assert r_c["txn-count"] == r_py["txn-count"], trial
        assert r_c["anomalies"] == r_py["anomalies"], trial
    assert engaged >= 30, engaged


def test_c_front_bails_match_python_front(monkeypatch):
    """Inputs the C parser declines must still produce the same final
    result through whichever builder takes over."""
    from jepsen_tpu.native import columnar_c

    if not columnar_c.available():
        pytest.skip("C toolchain unavailable")
    cases = [
        # non-int key (general loop path)
        [{"type": "ok", "process": 0, "value": [["append", "k", 1]]},
         {"type": "ok", "process": 1, "value": [["r", "k", [1]]]}],
        # bool append value (python builder path)
        [{"type": "ok", "process": 0, "value": [["append", 0, True]]}],
        # tuple micro-op container and tuple payload
        [{"type": "ok", "process": 0, "value": (("append", 0, 1),)},
         {"type": "ok", "process": 1, "value": [("r", 0, (1,))]}],
        # out-of-range append value
        [{"type": "ok", "process": 0, "value": [["append", 0, 1 << 33]]}],
        # huge int key: C path interns objects, numpy front declines
        [{"type": "ok", "process": 0, "value": [["append", 1 << 70, 1]]},
         {"type": "ok", "process": 1, "value": [["r", 1 << 70, [1]]]}],
        # non-string process on an ok op (dropped from txn set)
        [{"type": "ok", "process": "nemesis", "value": [["append", 0, 1]]},
         {"type": "ok", "process": 0, "value": [["append", 0, 2]]},
         {"type": "ok", "process": 1, "value": [["r", 0, [2]]]}],
    ]
    from jepsen_tpu.elle import columnar
    for i, h in enumerate(cases):
        r_c = list_append.check(h, accelerator="auto")
        with monkeypatch.context() as mp:
            mp.setattr(columnar, "_cmod", lambda: None)
            r_py = list_append.check(h, accelerator="auto")
        assert r_c["valid?"] == r_py["valid?"], (i, r_c, r_py)
        assert r_c["anomaly-types"] == r_py["anomaly-types"], i


def test_stored_columns_roundtrip_clean(tmp_path):
    """parse_columns -> npz save/load -> check_columns must equal the
    object-path check on a clean history, with no object access."""
    import numpy as np

    from jepsen_tpu.elle import columnar

    h = []
    t = 0
    for i in range(400):
        k = i % 7
        seen = list(range(k, i + 1, 7))
        h.append({"type": "invoke", "process": i % 5,
                  "value": [["append", k, i], ["r", k, None]], "time": t})
        h.append({"type": "ok", "process": i % 5,
                  "value": [["append", k, i], ["r", k, seen]],
                  "time": t + 1})
        t += 2
    cols = columnar.parse_columns(h)
    if cols is None:
        pytest.skip("C parser unavailable")
    p = tmp_path / "cols.npz"
    np.savez_compressed(p, **cols)
    with np.load(p) as z:
        loaded = {k: z[k] for k in z.files}
    r = columnar.check_columns(loaded, accelerator="auto")
    r0 = list_append.check(h, accelerator="auto")
    for key in ("valid?", "anomaly-types", "edge-count", "txn-count"):
        assert r[key] == r0[key], key
    assert r["builder"] == "columnar-store"


def test_stored_columns_anomalous_needs_objects():
    """Findings that cite txn objects must raise NeedsObjects instead
    of fabricating citations."""
    from jepsen_tpu.elle import columnar

    h = [
        {"type": "ok", "process": 0, "value": [["append", 0, 1]]},
        {"type": "ok", "process": 1,
         "value": [["r", 0, [1, 99]]]},   # phantom + order trouble
        {"type": "fail", "process": 2, "value": [["append", 0, 99]]},
    ]
    cols = columnar.parse_columns(h)
    if cols is None:
        pytest.skip("C parser unavailable")
    with pytest.raises(columnar.NeedsObjects):
        columnar.check_columns(cols)


def test_stored_columns_non_txn_extras_complete():
    """Extras that never cite txns (duplicate appends) complete from
    columns alone."""
    from jepsen_tpu.elle import columnar

    h = [
        {"type": "ok", "process": 0, "value": [["append", 0, 1]]},
        {"type": "ok", "process": 1, "value": [["append", 0, 1]]},  # dup
        {"type": "ok", "process": 2, "value": [["r", 0, [1]]]},
    ]
    cols = columnar.parse_columns(h)
    if cols is None:
        pytest.skip("C parser unavailable")
    r = columnar.check_columns(cols)
    r0 = list_append.check(h, accelerator="auto")
    assert r["anomaly-types"] == r0["anomaly-types"]
    assert "duplicate-appends" in r["anomalies"]


def test_check_stored_prefers_sidecar(tmp_path):
    """An append-workload run saved through the store re-checks from
    the elle_* sidecar columns (and matches a fresh object check)."""
    from jepsen_tpu import store
    from jepsen_tpu.elle import columnar, list_append as la

    h = []
    for i in range(50):
        k = i % 3
        seen = list(range(k, i + 1, 3))
        h.append({"type": "invoke", "process": i % 5,
                  "value": [["append", k, i]], "time": 2 * i})
        h.append({"type": "ok", "process": i % 5,
                  "value": [["append", k, i], ["r", k, seen]],
                  "time": 2 * i + 1})
    test = {"name": "elle-store-t", "start_time": "20260731T000000",
            "store_dir": str(tmp_path), "history": h}
    store.write_history(test)
    store.write_columnar(test)
    cols = store.load_elle_columns("elle-store-t", "20260731T000000",
                                   str(tmp_path))
    if cols is None:
        pytest.skip("C parser unavailable")
    r = la.check_stored("elle-store-t", "20260731T000000", str(tmp_path),
                        accelerator="auto")
    assert r["builder"] == "columnar-store"
    assert r["valid?"] == la.check(h)["valid?"] is True


def test_stored_columns_parity_fuzz():
    """On messy histories, the stored-column check must either agree
    with the object path in full or raise NeedsObjects exactly when the
    object path's findings cite txn values."""
    from jepsen_tpu.elle import columnar

    rng = random.Random(97)
    compared = deferred = 0
    for trial in range(40):
        h = _messy_history(rng)
        cols = columnar.parse_columns(h)
        if cols is None:
            continue
        r0 = list_append.check(h, accelerator="auto")
        try:
            r = columnar.check_columns(cols, accelerator="auto")
        except columnar.NeedsObjects:
            deferred += 1
            # the object path must indeed have txn-citing output:
            # a cycle, or a G1a/G1b style extra carrying txn values
            citing = bool(r0.get("anomalies")) and any(
                k in r0["anomalies"]
                for k in ("G1a", "G1b", "G0", "G1c", "G-single", "G2",
                          "G2-item", "realtime", "process"))
            assert citing or not r0["valid?"], (trial, r0)
            continue
        compared += 1
        assert r["valid?"] == r0["valid?"], (trial, r, r0)
        assert r["anomaly-types"] == r0["anomaly-types"], trial
        assert r["edge-count"] == r0["edge-count"], trial
    assert compared >= 5 and deferred >= 5, (compared, deferred)


# ---------------------------------------------------------------------------
# the columnar G1b walk: only reads a multi-append writer could break
# ---------------------------------------------------------------------------

G1B_TYPES = ("G1b", "incompatible-order")


def _txn(t, p, value):
    return [{"type": "invoke", "process": p, "f": "txn",
             "value": [[f, k, None if f == "r" else v] for f, k, v in value]},
            {"type": t, "process": p, "f": "txn", "value": value}]


def _g1b_hand_histories() -> dict:
    """name -> (history, G1b-family types it must show)."""
    def h(*txns):
        return [op for t in txns for op in _txn(*t)]

    return {
        # T0 appends 1, 2; T2 reads [1]: it ends inside T0's group
        "read-inside-group": (h(
            ("ok", 0, [["append", 0, 1], ["append", 0, 2]]),
            ("ok", 1, [["r", 0, [1, 2]]]),
            ("ok", 2, [["r", 0, [1]]])), {"G1b"}),
        # the spine holds T0's appends as 2, 1
        "spine-out-of-order": (h(
            ("ok", 0, [["append", 0, 1], ["append", 0, 2]]),
            ("ok", 1, [["r", 0, [2, 1]]]),
            ("ok", 2, [["r", 0, [2]]])), {"incompatible-order"}),
        # T0 appends 1, 2, 3; no read ever shows 3
        "fewer-spine-elements-than-appends": (h(
            ("ok", 0, [["append", 0, 1], ["append", 0, 2],
                       ["append", 0, 3]]),
            ("ok", 1, [["r", 0, [1, 2]]]),
            ("ok", 2, [["r", 0, [1]]])), {"G1b"}),
        # T0 appends 5, 6, 5: the second 5 is only a duplicate append,
        # so [5, 6] shows T0 whole and [5] shows it in part
        "same-value-twice": (h(
            ("ok", 0, [["append", 0, 5], ["append", 0, 6],
                       ["append", 0, 5]]),
            ("ok", 1, [["r", 0, [5, 6]]]),
            ("ok", 2, [["r", 0, [5]]])), {"G1b"}),
        # T0 reads its own first append between its two appends
        "own-read-inside-group": (h(
            ("ok", 0, [["append", 0, 1], ["r", 0, [1]], ["append", 0, 2]]),
            ("ok", 1, [["r", 0, [1, 2]]])), set()),
        # an indeterminate writer seen in part is no G1b
        "info-writer": (h(
            ("info", 0, [["append", 0, 1], ["append", 0, 2]]),
            ("ok", 1, [["r", 0, [1, 2]]]),
            ("ok", 2, [["r", 0, [1]]])), set()),
    }


@functools.lru_cache(maxsize=1)
def _g1b_fuzz_histories() -> tuple:
    """test_columnar_builder_parity_fuzz's 60 histories (seed 23)."""
    rng = random.Random(23)
    return tuple(_messy_history(rng) for _ in range(60))


def _checked(h, accelerator, monkeypatch):
    """(result, every G1b-family entry as a multiset, the stats of
    each ``encode.elle_build`` span) of one ``list_append.check``. The
    entries are read where the check hands them to ``result_map``,
    which keeps only ten of each type."""
    from jepsen_tpu import elle, trace
    from jepsen_tpu.trace.flight import FlightRecorder

    handed = []
    result_map = elle.result_map

    def keep(anomalies, txns, extras=None, **kw):
        handed.append(extras or {})
        return result_map(anomalies, txns, extras, **kw)

    tracer = trace.RunTracer(flight=FlightRecorder(256))
    with monkeypatch.context() as mp, trace.use(tracer):
        mp.setattr(elle, "result_map", keep)
        r = list_append.check(copy.deepcopy(h), accelerator=accelerator)
    [extras] = handed
    entries = {t: Counter(json.dumps(e, sort_keys=True)
                          for e in extras.get(t, ())) for t in G1B_TYPES}
    builds = [ev["args"] for ev in tracer.flight.snapshot()
              if isinstance(ev, dict) and ev.get("name") == "encode.elle_build"]
    return r, entries, builds


@pytest.mark.parametrize(
    "case", [f"fuzz-{i}" for i in range(60)] + sorted(_g1b_hand_histories()))
def test_g1b_walk_matches_oracle(case, monkeypatch):
    """Every G1b and incompatible-order entry the columnar build finds
    (both fronts) is the CPU oracle's, as a multiset, though it walks
    only the reads that end where a committed multi-append writer can
    be seen in part or out of order."""
    from jepsen_tpu.elle import columnar

    if case.startswith("fuzz-"):
        h, shows = _g1b_fuzz_histories()[int(case[5:])], None
    else:
        h, shows = _g1b_hand_histories()[case]
    _, want, _ = _checked(h, "cpu", monkeypatch)
    if shows is not None:
        assert {t for t in G1B_TYPES if want[t]} == shows
    for front in ("c", "py"):
        with monkeypatch.context() as mp:
            if front == "py":
                mp.setattr(columnar, "_cmod", lambda: None)
            r, got, [build] = _checked(h, "auto", monkeypatch)
        assert r.get("builder") == "columnar", front
        assert got == want, (front, got, want)
        assert 0 <= build["g1b_walked"] <= build["g1b_candidates"], build


def _strict_append_history(n_txns: int, seed: int, workers: int = 10):
    """Elle's list-append txns (``list_append.gen``: 3 rotating keys,
    1-4 micro-ops) by ``workers`` workers, each txn applied whole at a
    point inside its interval: strict serializable, every read the key's
    whole list."""
    rng = random.Random(seed)
    one_txn = list_append.gen()
    ctx = SimpleNamespace(rng=rng)
    lists: dict = defaultdict(list)
    h: list = []
    flight: dict = {}   # process -> (micro-ops, applied value or None)
    invoked = 0

    def invoke(p):
        nonlocal invoked
        invoked += 1
        mops = one_txn(None, ctx)["value"]
        h.append({"type": "invoke", "process": p, "f": "txn",
                  "value": mops})
        flight[p] = (mops, None)

    for p in range(workers):
        invoke(p)
    while flight:
        p = rng.choice(sorted(flight))
        mops, applied = flight.pop(p)
        if applied is None:
            applied = []
            for f, k, v in mops:
                if f == "append":
                    lists[k].append(v)
                    applied.append([f, k, v])
                else:
                    applied.append([f, k, list(lists[k])])
            flight[p] = (mops, applied)
            continue
        h.append({"type": "ok", "process": p, "f": "txn",
                  "value": applied})
        if invoked < n_txns:
            invoke(p)
    return h


def test_g1b_walk_is_a_sliver_of_a_valid_history(monkeypatch):
    """On a valid history nearly every read reaches some multi-append
    writer's elements, and only the own reads that end inside their
    txn's append group are walked: a few percent at most."""
    h = _strict_append_history(3000, seed=11)
    r, entries, [build] = _checked(h, "auto", monkeypatch)
    assert r["valid?"] is True and r.get("builder") == "columnar"
    assert not any(entries.values())
    n_reads = sum(m[0] == "r" for op in h if op["type"] == "ok"
                  for m in op["value"])
    assert build["g1b_candidates"] >= n_reads // 2, (build, n_reads)
    assert build["g1b_walked"] <= 0.03 * build["g1b_candidates"], build
