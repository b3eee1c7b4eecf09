"""Same seed, same histories, same reference answers: the pools of both
full-size cells and of the test twins, and the plain reference's and the
control's answers on them, hashed and pinned.

The digests were computed on commit a09ff01, where harness.py still made
the pool with ``traffic.make_history`` and the answers with
``reference.CHECKS`` itself; since then each configuration's checker
module does both, and a digest that moves means the cells check other
histories, or hold them to other answers, than they did there. The
full-size 10k pool's answers take the reference ~10 s on the CPU, so
they are pinned on one seed; its histories on two.
"""
from __future__ import annotations

import hashlib
import json

import bench_testing
import pytest

from benchmark import harness

SEED = bench_testing.SEED

# (bench, cell, seed) -> (digest of the pool, digest of the pool with
# the reference's and the control's answers, or None: histories alone)
PINNED = {
    ("full", "cas_register.10k", SEED): ("3c7e0f35ef5d1dbb",
                                         "d652b569fde74630"),
    ("full", "cas_register.10k", 5): ("33b2c549797a0a63", None),
    ("full", "independent.512x20", SEED): ("eb55196f8cbf31d2",
                                           "a237533475e82cdc"),
    ("full", "independent.512x20", 5): ("74b00b88d63b34bd", None),
    ("test", "t.register", SEED): ("d9cbf50e689ed6a3", "c6809b1af5e8449c"),
    ("test", "t.register", 5): ("6114264871ce885d", "b0701faea1ea1b01"),
    ("test", "t.keys", SEED): ("45e4ea48d9f237d8", "cb7df6925628dc23"),
    ("test", "t.keys", 5): ("2dbcce5f1632e2b5", "ca66400c49de0aa8"),
    ("test", "t.keys.mesh4", SEED): ("f2a6c5055eec53ad", "985b2b92385cd3e0"),
    ("test", "t.keys.mesh4", 5): ("b44a7b84c5159cd2", "0612e9a0aafe697b"),
}


def canon(x):
    """``x`` with sets sorted and dict entries listed in key order, so
    that its JSON is one string."""
    if isinstance(x, (set, frozenset)):
        return sorted(canon(v) for v in x)
    if isinstance(x, (list, tuple)):
        return [canon(v) for v in x]
    if isinstance(x, dict):
        return sorted([json.dumps(canon(k)), canon(v)] for k, v in x.items())
    return x


@pytest.mark.parametrize("which,cell,seed", list(PINNED))
def test_pool_and_answers_as_pinned(which, cell, seed):
    bench = harness.load() if which == "full" else bench_testing.bench()
    w = bench.cell(cell)
    mix = bench.mix(w)
    chk = bench.checker(w)
    pool = [chk.history(mix, seed, j) for j in range(mix.pool)]
    h = hashlib.sha256(json.dumps(mix.test, sort_keys=True).encode())
    for p in pool:
        h.update(json.dumps([p.history, canon(p.plants)]).encode())
    want_pool, want_answers = PINNED[which, cell, seed]
    assert h.hexdigest()[:16] == want_pool
    if want_answers is None:
        return
    for kind in ("reference", "control"):
        answers = harness.answers(chk, pool, range(len(pool)), kind)
        h.update(json.dumps(canon(answers)).encode())
    assert h.hexdigest()[:16] == want_answers
