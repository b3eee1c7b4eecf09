"""The process-wide cache of jitted frontier-scan kernels
(doc/performance.md "Kernel caches"): fresh checkers share a kernel of
the same shape and model, distinct models and initial states never
share one, the bound evicts the least recently used entry, and threads
that miss at once build one entry."""
from __future__ import annotations

import sys
import threading
import time
import weakref
from collections import OrderedDict

import pytest

from test_trace_phases import keyed_history, recorded, register_history

from jepsen_tpu import independent
from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.models import (CASRegister, MultiRegister, cas_register_spec,
                               multi_register_spec, register_spec)
from jepsen_tpu.ops import jitlin
from jepsen_tpu.ops.jitlin import JitLinKernel


@pytest.fixture(autouse=True)
def clean_cache(monkeypatch):
    """A clean slate per test, whatever ran before in the process."""
    monkeypatch.setattr(jitlin, "_FRONTIER_CACHE", OrderedDict())
    return jitlin._FRONTIER_CACHE


def txn_history(bad: bool = False) -> list:
    """10 processes at once, the even ones writing 1 to key 0 and the
    odd ones 2 to key 1, then each reading both keys; ``bad`` makes one
    read see 0 in key 0, never written there."""
    h: list = []

    def op(t, p, value):
        h.append({"type": t, "process": p, "f": "txn", "value": value})

    for t in ("invoke", "ok"):
        for p in range(10):
            op(t, p, [["w", p % 2, 1 + p % 2]])
    for p in range(10):
        op("invoke", p, [["r", 0, None], ["r", 1, None]])
    for p in range(10):
        op("ok", p, [["r", 0, 0 if bad and p == 0 else 1], ["r", 1, 2]])
    return h


def dispatch_lowered(fn) -> list[int]:
    """The ``lowered`` stat of every ``dispatch.call`` span ``fn()``
    emits, in order."""
    return [ev["args"]["lowered"] for ev in recorded(fn)
            if ev["name"] == "dispatch.call"]


def test_fresh_checkers_share_one_jitted_scan(clean_cache):
    bad = register_history(3, bad=True)
    results = []

    def run():
        for _ in range(2):
            results.append(LinearizableChecker(accelerator="tpu").check(
                {}, bad, {}))

    assert dispatch_lowered(run) == [1, 0]
    assert [r["valid?"] for r in results] == [False, False]
    assert results[0]["failed-op"] == results[1]["failed-op"]
    assert len(clean_cache) == 1


def test_fresh_independent_checkers_share_one_jitted_scan(clean_cache):
    results = []

    def run():
        for _ in range(2):
            results.append(independent.checker(
                LinearizableChecker(accelerator="tpu")).check(
                    {}, keyed_history(), {}))

    assert dispatch_lowered(run) == [1, 0]
    assert [r["valid?"] for r in results] == [False, False]
    assert len(clean_cache) == 1


def test_specs_hand_out_one_step_function():
    assert cas_register_spec(0).step_ids is cas_register_spec(3).step_ids
    assert register_spec(1).step_ids is cas_register_spec().step_ids
    assert multi_register_spec(2, 3).step_ids is \
        multi_register_spec(2, 3).step_ids
    assert multi_register_spec(2, 3).step_ids is not \
        multi_register_spec(3, 3).step_ids


@pytest.mark.parametrize("model, history, valid", [
    (CASRegister(), register_history(3), True),
    (CASRegister(), register_history(3, bad=True), False),
    (CASRegister(), register_history(3, read_first=3), False),
    (CASRegister(3), register_history(3, read_first=3), True),
    (CASRegister(3), register_history(3, read_first=4), False),
    (MultiRegister(), txn_history(), True),
    (MultiRegister(), txn_history(bad=True), False),
], ids=["cas-valid", "cas-bad", "cas-reads-unset", "cas3-reads-3",
        "cas3-reads-4", "multi-valid", "multi-bad"])
def test_models_match_the_cpu_oracle_through_one_cache(model, history,
                                                       valid):
    """Every model and initial state gets its own kernel: the device
    verdict and failing op equal the CPU oracle's on a cache that the
    other models have filled first."""
    for other, h in ((CASRegister(), register_history(3, read_first=3)),
                     (CASRegister(3), register_history(3, read_first=3)),
                     (MultiRegister(), txn_history())):
        LinearizableChecker(model=other, accelerator="tpu",
                            multi_shape=(2, 3)).check({}, h, {})
    dev = LinearizableChecker(model=model, accelerator="tpu",
                              multi_shape=(2, 3)).check({}, history, {})
    cpu = LinearizableChecker(model=model, accelerator="cpu",
                              multi_shape=(2, 3)).check({}, history, {})
    assert "jitlin-cpu-" in dev["algorithm"]  # the device kernel ran
    assert dev["valid?"] == cpu["valid?"] == valid
    assert dev.get("failed-op") == cpu.get("failed-op")


def test_models_and_initial_states_take_distinct_entries(clean_cache):
    for model, h in ((CASRegister(), register_history(3, read_first=3)),
                     (CASRegister(3), register_history(3, read_first=3)),
                     (MultiRegister(), txn_history())):
        LinearizableChecker(model=model, accelerator="tpu",
                            multi_shape=(2, 3)).check({}, h, {})
    keys = list(clean_cache)
    assert len(keys) == 3
    steps = {(k[5], k[6]) for k in keys}
    cas = cas_register_spec().step_ids
    multi = multi_register_spec(2, 3).step_ids
    assert steps == {(cas, 0), (cas, 1), (multi, 0)}


def test_the_bound_evicts_the_least_recently_used(clean_cache,
                                                  monkeypatch):
    monkeypatch.setattr(jitlin, "FRONTIER_CACHE_SIZE", 2)
    k = JitLinKernel()
    a = k._get(3, 8, batched=False)
    b = k._get(4, 8, batched=False)
    assert k._get(3, 8, batched=False) is a  # a is now the newest
    k._get(5, 8, batched=False)              # evicts b, not a
    assert [key[1] for key in clean_cache] == [3, 5]
    assert k._get(3, 8, batched=False) is a
    assert k._get(4, 8, batched=False) is not b


def test_threads_that_miss_at_once_build_one_entry(clean_cache,
                                                   monkeypatch):
    built = []
    real = jitlin._build_frontier_kernel

    def slow_build(*key):
        built.append(key)
        time.sleep(0.05)  # widen the window in which others miss
        return real(*key)

    monkeypatch.setattr(jitlin, "_build_frontier_kernel", slow_build)
    n = 16
    start = threading.Barrier(n)
    got: list = [None] * n

    def one(i):
        start.wait(timeout=10)
        got[i] = JitLinKernel()._get(10, 256, batched=True, num_states=6)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=one, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(built) == 1 and len(clean_cache) == 1
    assert all(fn is got[0] for fn in got) and got[0] is not None


def test_the_cache_keeps_its_step_function_alive(clean_cache):
    """The key holds the step function itself, so its address cannot be
    reused by another model's step while the entry lives."""

    def step(state, f, a, b):
        return state, f == f

    ref = weakref.ref(step)
    JitLinKernel(step_ids=step)._get(3, 8, batched=False)
    del step
    assert ref() is not None


def test_distinct_step_functions_never_share_a_matrix_kernel(monkeypatch):
    monkeypatch.setattr(jitlin, "_MATRIX_CACHE", {})

    def make_step():
        def step(state, f, a, b):
            return state, f == f
        return step

    first, second = make_step(), make_step()
    ref = weakref.ref(first)
    S, V, T, C = 4, 16, 8, 2
    a = jitlin._matrix_cache(S, V, first, 0, T, C)
    del first
    assert ref() is not None  # kept alive by its key, address not reused
    b = jitlin._matrix_cache(S, V, second, 0, T, C)
    assert a is not b
    assert len(jitlin._MATRIX_CACHE) == 2
    assert jitlin._matrix_cache(S, V, second, 0, T, C) is b
