"""Linearizability checker tests: unit cases + differential testing of the
WGL oracle, the int-encoded CPU search, and the JAX kernel (on the virtual
CPU mesh). Mirrors the reference's knossos-as-oracle strategy
(SURVEY.md §4, BASELINE north_star)."""
import random

import pytest

from jepsen_tpu.checker.linear_cpu import check_stream, wgl
from jepsen_tpu.checker.linear_encode import encode_register_ops
from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.models import CASRegister


def op(typ, process, f, value=None):
    return {"type": typ, "process": process, "f": f, "value": value}


GOOD_SEQ = [
    op("invoke", 0, "write", 1), op("ok", 0, "write", 1),
    op("invoke", 1, "read"), op("ok", 1, "read", 1),
    op("invoke", 0, "cas", [1, 2]), op("ok", 0, "cas", [1, 2]),
    op("invoke", 1, "read"), op("ok", 1, "read", 2),
]

BAD_READ = [
    op("invoke", 0, "write", 1), op("ok", 0, "write", 1),
    op("invoke", 1, "read"), op("ok", 1, "read", 99),
]

# write(1) and read run concurrently: read may see None or 1
CONCURRENT_OK = [
    op("invoke", 0, "write", 1),
    op("invoke", 1, "read"),
    op("ok", 1, "read", 1),
    op("ok", 0, "write", 1),
]

# crashed write may have taken effect
CRASHED_WRITE_SEEN = [
    op("invoke", 0, "write", 7), op("info", 0, "write", 7),
    op("invoke", 1, "read"), op("ok", 1, "read", 7),
]

# failed write must NOT be visible
FAILED_WRITE_SEEN = [
    op("invoke", 0, "write", 7), op("fail", 0, "write", 7),
    op("invoke", 1, "read"), op("ok", 1, "read", 7),
]

# read completed before the write was invoked: must not see it
REAL_TIME_VIOLATION = [
    op("invoke", 1, "read"), op("ok", 1, "read", 7),
    op("invoke", 0, "write", 7), op("ok", 0, "write", 7),
]


CASES = [
    (GOOD_SEQ, True),
    (BAD_READ, False),
    (CONCURRENT_OK, True),
    (CRASHED_WRITE_SEEN, True),
    (FAILED_WRITE_SEEN, False),
    (REAL_TIME_VIOLATION, False),
    ([], True),
]


@pytest.mark.parametrize("history,expected", CASES)
def test_wgl_cases(history, expected):
    assert wgl(history, CASRegister()).valid is expected


@pytest.mark.parametrize("history,expected", CASES)
def test_jitlin_cpu_cases(history, expected):
    assert check_stream(encode_register_ops(history)).valid is expected


@pytest.mark.parametrize("history,expected", CASES)
def test_jitlin_device_cases(history, expected):
    from jepsen_tpu.ops.jitlin import JitLinKernel, verdict
    if not history:
        return
    stream = encode_register_ops(history)
    alive, died, ovf, peak = JitLinKernel().check(stream, capacity=64)
    assert verdict(alive, ovf) is expected


def test_checker_interface():
    chk = LinearizableChecker(accelerator="cpu")
    r = chk.check({}, GOOD_SEQ, {})
    assert r["valid?"] is True
    r = chk.check({}, BAD_READ, {})
    assert r["valid?"] is False
    assert r["failed-op"] is not None


def gen_history(rng: random.Random, n_procs=4, n_ops=40, values=4, corrupt=False):
    """Generates a register history by simulating a real register with
    random overlap; optionally corrupts one read to force non-linearizable
    (usually)."""
    reg = None
    history = []
    pending = {}  # process -> op
    procs = list(range(n_procs))
    ops_left = n_ops
    while ops_left > 0 or pending:
        p = rng.choice(procs)
        if p in pending:
            # complete p's op: apply it now (linearization point at completion)
            o = pending.pop(p)
            f, v = o["f"], o["value"]
            outcome = rng.random()
            if f == "read":
                o2 = op("ok", p, "read", reg)
            elif outcome < 0.1:
                o2 = op("info", p, f, v)   # indeterminate: maybe applied
                if rng.random() < 0.5:
                    reg = v if f == "write" else (v[1] if reg == v[0] else reg)
            elif outcome < 0.2 and f == "cas":
                o2 = op("fail", p, f, v)   # definitely not applied
            else:
                if f == "write":
                    reg = v
                    o2 = op("ok", p, f, v)
                else:  # cas
                    if reg == v[0]:
                        reg = v[1]
                        o2 = op("ok", p, f, v)
                    else:
                        o2 = op("fail", p, f, v)
            history.append(o2)
        elif ops_left > 0:
            ops_left -= 1
            r = rng.random()
            if r < 0.4:
                o = op("invoke", p, "read")
            elif r < 0.7:
                o = op("invoke", p, "write", rng.randrange(values))
            else:
                o = op("invoke", p, "cas", [rng.randrange(values), rng.randrange(values)])
            pending[p] = o
            history.append(o)
    if corrupt:
        reads = [i for i, o in enumerate(history)
                 if o["type"] == "ok" and o["f"] == "read"]
        if reads:
            i = rng.choice(reads)
            history[i] = dict(history[i], value=(history[i]["value"] or 0) + 100)
    return history


@pytest.mark.slow
def test_differential_random_histories():
    """wgl == jitlin-cpu == jax kernel across random valid/corrupted
    histories."""
    from jepsen_tpu.ops.jitlin import JitLinKernel, verdict
    kernel = JitLinKernel()
    rng = random.Random(7)
    n_disagreements = []
    for trial in range(60):
        corrupt = trial % 3 == 0
        h = gen_history(rng, n_procs=4, n_ops=30, corrupt=corrupt)
        r_wgl = wgl(h, CASRegister()).valid
        stream = encode_register_ops(h)
        r_jit = check_stream(stream).valid
        alive, _, ovf, _ = kernel.check(stream, capacity=128)
        r_dev = verdict(alive, ovf)
        assert r_wgl == r_jit, f"trial {trial}: wgl={r_wgl} jit={r_jit}\n{h}"
        assert r_jit == r_dev, f"trial {trial}: jit={r_jit} dev={r_dev}\n{h}"
        if not corrupt:
            assert r_wgl is True, f"trial {trial}: valid history judged {r_wgl}\n{h}"
        n_disagreements.append((r_wgl, corrupt))
    # corrupted histories should usually be invalid (sanity that the test
    # exercises both verdicts)
    assert any(v is False for v, _ in n_disagreements)
    assert any(v is True for v, _ in n_disagreements)


def test_wgl_handles_uncompleted_ops():
    h = [
        op("invoke", 0, "write", 1),   # never completes
        op("invoke", 1, "read"), op("ok", 1, "read", 1),
    ]
    assert wgl(h, CASRegister()).valid is True
    assert check_stream(encode_register_ops(h)).valid is True


def test_nemesis_ops_ignored():
    h = [
        {"type": "info", "process": "nemesis", "f": "start", "value": None},
        op("invoke", 0, "write", 1), op("ok", 0, "write", 1),
        {"type": "info", "process": "nemesis", "f": "stop", "value": None},
    ]
    assert wgl(h, CASRegister()).valid is True
    assert check_stream(encode_register_ops(h)).valid is True


def test_dense_and_sparse_kernels_agree():
    """The exact dense-table kernel (small 2^S x V config spaces) and the
    capacity-K sort-based frontier must return identical verdicts; the
    batch path auto-selects dense, so pin each explicitly here."""
    import jax
    from jepsen_tpu.ops.jitlin import (JitLinKernel, _bucket, scan_inputs,
                                       verdict)
    from jepsen_tpu.checker.linear_encode import pad_streams

    kernel = JitLinKernel()
    rng = random.Random(13)
    for trial in range(20):
        h = gen_history(rng, n_procs=3, n_ops=24, corrupt=trial % 3 == 0)
        if not h:
            continue
        stream = encode_register_ops(h)
        batch = pad_streams([stream], length=_bucket(len(stream)))
        S = max(1, batch["n_slots"])
        events = tuple(batch[k] for k in ("kind", "slot", "f", "a", "b"))
        dense = kernel._get(S, 128, batched=False,
                            num_states=len(stream.intern))
        sparse = kernel._get(S, 128, batched=False, num_states=None)
        d_args, _ = scan_inputs(*events, S, len(stream.intern))
        s_args, _ = scan_inputs(*events, S, None)
        da, _, dovf, _ = map(jax.device_get, dense(*(x[0] for x in d_args)))
        sa, _, sovf, _ = map(jax.device_get, sparse(*(x[0] for x in s_args)))
        assert not bool(dovf)  # dense is exact, never overflows
        assert verdict(bool(da), bool(dovf)) == verdict(bool(sa), bool(sovf)), \
            f"trial {trial}: dense={bool(da)} sparse={bool(sa)}\n{h}"


# ---------------------------------------------------------------------------
# block-composed transfer-matrix kernel (ops/jitlin.matrix_check)
# ---------------------------------------------------------------------------

def _scan_alive(history):
    """The event-scan kernel's aliveness for differential comparison."""
    import jax
    from jepsen_tpu.checker.linear_encode import (encode_register_ops,
                                                  pad_streams)
    from jepsen_tpu.ops.jitlin import JitLinKernel, _bucket, scan_inputs
    stream = encode_register_ops(history)
    batch = pad_streams([stream], length=_bucket(len(stream)))
    S = max(1, batch["n_slots"])
    run = JitLinKernel()._get(S, 256, batched=False,
                              num_states=len(stream.intern))
    args, _ = scan_inputs(*(batch[k] for k in ("kind", "slot", "f", "a", "b")),
                          S, len(stream.intern))
    alive, _, _, _ = run(*(jax.numpy.asarray(x[0]) for x in args))
    return bool(alive)


@pytest.mark.slow
def test_matrix_kernel_differential_valid():
    from __graft_entry__ import _register_history  # conftest adds the root
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import matrix_check
    for n, seed in ((60, 0), (60, 1), (300, 2), (300, 3)):
        h = _register_history(n, n_procs=4, seed=seed)
        m = matrix_check(encode_register_ops(h), force=True)
        assert m is not None
        assert m[0] == _scan_alive(h) is True, (n, seed)


@pytest.mark.slow
def test_matrix_kernel_differential_invalid():
    import random
    from __graft_entry__ import _register_history  # conftest adds the root
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import matrix_check
    for seed in range(4):
        h = _register_history(200, n_procs=4, seed=100 + seed)
        rng = random.Random(seed)
        reads = [op for op in h
                 if op.get("f") == "read" and op.get("type") == "ok"]
        for op in rng.sample(reads, min(2, len(reads))):
            op["value"] = 999  # a value never written
        m = matrix_check(encode_register_ops(h), force=True)
        assert m is not None
        assert m[0] == _scan_alive(h) is False, seed


def test_matrix_kernel_gating():
    """The matrix path must decline outside its regime: large value
    domains (quadratic blowup) and short histories."""
    from jepsen_tpu.ops.jitlin import matrix_ok
    assert matrix_ok(5, 8, 5000)
    assert not matrix_ok(5, 101, 5000)   # 10k-op bench history: 101 values
    assert not matrix_ok(5, 8, 100)      # short history: scan is cheaper
    assert not matrix_ok(12, 8, 5000)    # too many slots


def test_matrix_kernel_shape_bucketing():
    """Nearby return counts must map to the same (T, G) chunk shape so
    the compiled program is reused, and G stays within the element cap."""
    from jepsen_tpu.ops.jitlin import (MATRIX_MAX_ELEMS, _bucket)
    import numpy as np
    shapes = set()
    for R in (2000, 2040, 2500, 3000):
        MV = 32 * 8
        rb = _bucket(R, floor=64)
        G = int(np.clip(rb // 120, 8, 256))
        G = max(1, min(G, MATRIX_MAX_ELEMS // (MV * MV)))
        T = -(-rb // G)
        shapes.add((T, G))
    assert len(shapes) <= 2  # 2048 and 4096 buckets
    # the memory cap engages for big MV
    MV = 4096
    G = max(1, min(256, MATRIX_MAX_ELEMS // (MV * MV)))
    assert G * MV * MV <= MATRIX_MAX_ELEMS


def test_returns_prepass_vectorized_differential():
    """The vectorized matrix-kernel prepass must agree event-for-event
    with the straightforward per-event walk it replaced."""
    import numpy as np
    from jepsen_tpu.ops.jitlin import EV_INVOKE, EV_RETURN, _returns_prepass

    def walk(kind, slot, f, a, b):
        fabs = np.stack([f, a, b], axis=1)
        S = int(slot.max(initial=0)) + 1
        cur = np.zeros((S, 3), np.int64)
        pend = np.zeros((S,), bool)
        r_slot, r_pend, r_ops = [], [], []
        for i in range(kind.shape[0]):
            k, s = int(kind[i]), int(slot[i])
            if k == EV_INVOKE:
                cur[s] = fabs[i]
                pend[s] = True
            elif k == EV_RETURN:
                r_slot.append(s)
                r_pend.append(pend.copy())
                r_ops.append(cur.copy())
                pend[s] = False
        if not r_slot:
            return (np.zeros((0,), np.int32), np.zeros((0, S), bool),
                    np.zeros((0, S, 3), np.int64), S)
        return (np.asarray(r_slot, np.int32), np.stack(r_pend),
                np.stack(r_ops), S)

    rng = np.random.default_rng(7)
    for trial in range(100):
        E, S = int(rng.integers(1, 80)), int(rng.integers(1, 6))
        kind, slot, pend = [], [], set()
        for _ in range(E):
            r = rng.random()
            if (r < 0.25 and pend) or (r < 0.85 and len(pend) == S):
                s = int(rng.choice(sorted(pend)))
                pend.discard(s)
                kind.append(EV_RETURN)
            elif r < 0.85:
                s = int(rng.choice([x for x in range(S) if x not in pend]))
                pend.add(s)
                kind.append(EV_INVOKE)
            else:
                s = 0
                kind.append(2)  # noop
            slot.append(s)
        kind, slot = np.array(kind), np.array(slot)
        f = rng.integers(0, 3, E)
        a = rng.integers(0, 9, E)
        b = rng.integers(0, 9, E)
        got = _returns_prepass(kind, slot, f, a, b)
        want = walk(kind, slot, f, a, b)
        assert got[3] == want[3], trial
        for g, w in zip(got[:3], want[:3]):
            assert np.array_equal(g, w), trial


def test_matrix_check_batch_differential_and_dispatch(monkeypatch):
    """batch_check must route in-regime batches through the key-batched
    transfer-matrix kernel and still agree per-key with the CPU oracle —
    including invalid keys, which fall back to the event scan for
    diagnostics."""
    import jepsen_tpu.ops.jitlin as jitlin
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.parallel import batch_check

    histories = []
    for k in range(8):
        h = _register_history(500, n_procs=4, seed=500 + k, n_values=5)
        if k % 3 == 2:  # corrupt: read a value never written
            reads = [op for op in h
                     if op.get("f") == "read" and op.get("type") == "ok"]
            reads[len(reads) // 2]["value"] = 999
        histories.append(h)
    streams = [encode_register_ops(h) for h in histories]

    calls = []
    real = jitlin.matrix_check_batch

    def spy(*a, **kw):
        calls.append(len(a[0]))
        return real(*a, **kw)

    monkeypatch.setattr(jitlin, "matrix_check_batch", spy)
    results = batch_check(streams, capacity=256)
    assert calls == [8], "in-regime batch must take the matrix path"
    for i, (s, r) in enumerate(zip(streams, results)):
        want = check_stream(s).valid
        assert (r[0] and not r[2]) == (want is True), (i, r, want)


def test_linearizable_checker_selects_matrix_path():
    """The device dispatch must pick the transfer-matrix kernel for long
    small-value-domain histories (its home regime)."""
    import jax
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    if not jax.devices():
        return
    h = _register_history(3000, n_procs=4, seed=11, n_values=5)
    res = LinearizableChecker(accelerator="tpu").check({}, h, {})
    assert res["valid?"] is True
    assert res["algorithm"] == "jitlin-cpu-matrix", res["algorithm"]


# ---------------------------------------------------------------------------
# failure rendering (reference: linear.svg, checker.clj:205-212)
# ---------------------------------------------------------------------------

def _failing_history():
    return [
        {"type": "invoke", "process": 0, "f": "write", "value": 1},
        {"type": "ok", "process": 0, "f": "write", "value": 1},
        {"type": "invoke", "process": 1, "f": "write", "value": 2},
        {"type": "ok", "process": 1, "f": "write", "value": 2},
        {"type": "invoke", "process": 0, "f": "read", "value": None},
        {"type": "ok", "process": 0, "f": "read", "value": 1},  # stale!
    ]


def test_check_stream_captures_final_configs():
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops

    res = check_stream(encode_register_ops(_failing_history()))
    assert res.valid is False
    assert res.final_configs, "dying frontier must be captured"
    for c in res.final_configs:
        assert set(c) == {"state", "linearized", "pending"}
    # just before the fatal read returns, the register held 2
    assert any(c["state"] == 2 for c in res.final_configs)


def test_linear_png_written_on_failure(tmp_path):
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    test = {"name": "lin-fail", "start_time": "20260730T000000",
            "store_dir": str(tmp_path)}
    out = LinearizableChecker(accelerator="cpu").check(
        test, _failing_history(), {})
    assert out["valid?"] is False
    assert out["final-configs"]
    plot = out.get("plot")
    assert plot and plot.endswith("linear.png")
    import os
    assert os.path.getsize(plot) > 0


def test_linear_png_device_path_recovers_configs(tmp_path):
    """A device verdict has no frontier detail; the report path re-runs
    the CPU twin to recover the dying configurations."""
    import jax
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    if not jax.devices():
        return
    h = _register_history(800, n_procs=4, seed=77, n_values=5)
    reads = [op for op in h
             if op.get("f") == "read" and op.get("type") == "ok"]
    reads[-1]["value"] = 999  # a value never written
    test = {"name": "lin-fail-tpu", "start_time": "20260730T000001",
            "store_dir": str(tmp_path)}
    out = LinearizableChecker(accelerator="tpu").check(test, h, {})
    assert out["valid?"] is False
    assert out["final-configs"]
    assert out.get("plot", "").endswith("linear.png")


def test_matrix_batch_mesh_divisible_chunks():
    """Odd key counts on a mesh must still shard: the chunk heuristic
    rounds G = B*C to a device-count multiple."""
    import jax
    from jax.sharding import Mesh
    import numpy as np
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import matrix_check_batch

    devs = jax.devices()
    if len(devs) < 2:
        return
    mesh = Mesh(np.array(devs), ("keys",))
    # B=3: 256//3 = 85, 3*85 = 255 not divisible by common device counts
    streams = [encode_register_ops(
        _register_history(800, n_procs=4, seed=900 + k, n_values=5))
        for k in range(3)]
    results = matrix_check_batch(streams, mesh=mesh)
    for s, r in zip(streams, results):
        want = check_stream(s).valid
        assert (r[0] and not r[2]) == (want is True)


# ---------------------------------------------------------------------------
# segmented (resumable-frontier) verification
# ---------------------------------------------------------------------------

def test_quiescent_cuts_never_split_pending_ops():
    from jepsen_tpu.ops.jitlin import EV_INVOKE, EV_NOOP, EV_RETURN, quiescent_cuts
    import numpy as np

    # invoke,invoke,return,return | invoke,return | noop
    kind = np.asarray([EV_INVOKE, EV_INVOKE, EV_RETURN, EV_RETURN,
                       EV_INVOKE, EV_RETURN, EV_NOOP])
    cuts = quiescent_cuts(kind, max_segment=2)
    # window of 2 has no quiescent point at 2 (one op pending): must
    # extend to 4, then 6, then end
    assert cuts[0] == 4
    assert cuts[-1] == len(kind)
    # verify every cut is genuinely quiescent (or the end)
    delta = np.where(kind == EV_INVOKE, 1,
                     np.where(kind == EV_RETURN, -1, 0))
    pending = np.cumsum(delta)
    for c in cuts[:-1]:
        assert pending[c - 1] == 0


def _seg_stream(n_ops, seed=0, n_values=5):
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    return encode_register_ops(
        _register_history(n_ops, n_procs=4, seed=seed, n_values=n_values))


@pytest.mark.parametrize("max_segment", [64, 256])
def test_segmented_check_matches_whole_run_valid(max_segment):
    from jepsen_tpu.ops.jitlin import JitLinKernel, segmented_check

    stream = _seg_stream(600, seed=7)
    k = JitLinKernel()
    whole = k.check(stream)
    seg = segmented_check(stream, max_segment=max_segment, kernel=k)
    assert seg[0] == whole[0] is True
    assert seg[2] == whole[2]


def test_segmented_check_matches_whole_run_invalid():
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import JitLinKernel, segmented_check

    # a read that observes a never-written value after a quiescent point
    h = []
    for i, v in enumerate([1, 2, 3]):
        h.append({"type": "invoke", "process": 0, "f": "write", "value": v})
        h.append({"type": "ok", "process": 0, "f": "write", "value": v})
    h.append({"type": "invoke", "process": 1, "f": "read", "value": None})
    h.append({"type": "ok", "process": 1, "f": "read", "value": 99})
    stream = encode_register_ops(h)
    k = JitLinKernel()
    whole = k.check(stream)
    seg = segmented_check(stream, max_segment=4, kernel=k)
    assert whole[0] is False or whole[0] == False  # noqa: E712
    assert bool(seg[0]) is False
    assert seg[1] >= 0  # died index reported (global)


@pytest.mark.slow
def test_segmented_check_sparse_kernel_path():
    """Force the sparse (capacity-K) kernel by exceeding the dense
    state-count regime, exercising the mask/state resume carry."""
    from jepsen_tpu.ops.jitlin import JitLinKernel, segmented_check

    stream = _seg_stream(400, seed=3, n_values=800)  # V too big for dense
    k = JitLinKernel()
    whole = k.check(stream)
    seg = segmented_check(stream, max_segment=128, kernel=k,
                          num_states=801)
    assert bool(seg[0]) == bool(whole[0])


@pytest.mark.slow
def test_matrix_resume_matches_monolithic():
    """Chaining segment operator products equals one monolithic matrix
    run (block composition is associative), valid and invalid alike."""
    import numpy as np

    from jepsen_tpu.ops.jitlin import (JitLinKernel, _slice_stream,
                                       matrix_check, matrix_check_resume,
                                       quiescent_cuts)

    for seed, corrupt in ((11, False), (12, True)):
        stream = _seg_stream(800, seed=seed, n_values=5)
        if corrupt:
            from dataclasses import replace
            a_bad = np.asarray(stream.a).copy()
            reads = np.nonzero((np.asarray(stream.kind) == 0)
                               & (np.asarray(stream.f) == 0))[0]
            # scramble several mid-stream reads so at least one is
            # genuinely impossible (asserted below, deterministic seed)
            for i, r in enumerate(reads[40:55]):
                a_bad[r] = (a_bad[r] % 5) + 1 if i % 2 else 5
            stream = replace(stream, a=a_bad)
        whole = matrix_check(stream, force=True)
        assert bool(whole[0]) == (not corrupt), (seed, corrupt, whole)
        cuts = quiescent_cuts(np.asarray(stream.kind), 256)
        tot = None
        alive = True
        base = 0
        S = stream.n_slots
        for end in cuts:
            seg = _slice_stream(stream, base, end)
            a, inexact, tot = matrix_check_resume(seg, tot, n_slots=S)
            assert not bool(np.asarray(inexact).any())
            alive = bool(np.asarray(a).all())
            if not alive:
                break
            base = end
        assert alive == bool(whole[0]), (seed, corrupt, alive, whole)


# ---------------------------------------------------------------------------
# stored-column re-check (lin_* sidecar)
# ---------------------------------------------------------------------------

def test_stream_columns_roundtrip():
    import numpy as np

    from jepsen_tpu.checker.linear_encode import (
        encode_register_ops, stream_from_columns, stream_to_columns)

    h = []
    for i in range(30):
        p = i % 3
        h.append({"type": "invoke", "process": p, "f": "write", "value": i})
        h.append({"type": "ok", "process": p, "f": "write", "value": i})
        h.append({"type": "invoke", "process": p, "f": "read",
                  "value": None})
        h.append({"type": "ok", "process": p, "f": "read", "value": i})
    s0 = encode_register_ops(h)
    cols = stream_to_columns(s0)
    assert cols is not None
    s1 = stream_from_columns(cols)
    assert np.array_equal(s0.kind, s1.kind)
    assert np.array_equal(s0.f, s1.f)
    assert np.array_equal(s0.a, s1.a)
    assert s0.n_slots == s1.n_slots
    assert list(s0.intern.table) == list(s1.intern.table)


def test_stream_columns_reject_non_int_values():
    from jepsen_tpu.checker.linear_encode import (
        encode_register_ops, stream_to_columns)

    h = [{"type": "invoke", "process": 0, "f": "write", "value": "x"},
         {"type": "ok", "process": 0, "f": "write", "value": "x"}]
    assert stream_to_columns(encode_register_ops(h)) is None


def test_linear_check_stored_roundtrip(tmp_path):
    from jepsen_tpu import store
    from jepsen_tpu.checker import linearizable as lin_mod

    h = []
    for i in range(40):
        p = i % 3
        h.append({"type": "invoke", "process": p, "f": "write",
                  "value": i % 5, "time": 2 * i})
        h.append({"type": "ok", "process": p, "f": "write",
                  "value": i % 5, "time": 2 * i + 1})
    test = {"name": "lin-store-t", "start_time": "20260731T000001",
            "store_dir": str(tmp_path), "history": h}
    store.write_history(test)
    store.write_columnar(test)
    cols = store.load_linear_columns("lin-store-t", "20260731T000001",
                                     str(tmp_path))
    assert cols is not None, "register run must persist lin_* columns"
    out = lin_mod.check_stored("lin-store-t", "20260731T000001",
                               str(tmp_path), accelerator="cpu")
    assert out["valid?"] is True
    assert out["algorithm"].endswith("(stored)")


def test_linear_check_stored_invalid_falls_back(tmp_path):
    """An invalid verdict needs op context: the stored lane must defer
    to the jsonl path, which renders the full failure report."""
    from jepsen_tpu import store
    from jepsen_tpu.checker import linearizable as lin_mod

    h = [
        {"type": "invoke", "process": 0, "f": "write", "value": 1},
        {"type": "ok", "process": 0, "f": "write", "value": 1},
        {"type": "invoke", "process": 1, "f": "read", "value": None},
        {"type": "ok", "process": 1, "f": "read", "value": 2},  # impossible
    ]
    test = {"name": "lin-store-bad", "start_time": "20260731T000002",
            "store_dir": str(tmp_path), "history": h}
    store.write_history(test)
    store.write_columnar(test)
    out = lin_mod.check_stored("lin-store-bad", "20260731T000002",
                               str(tmp_path), accelerator="cpu")
    assert out["valid?"] is False
    assert not out["algorithm"].endswith("(stored)")
    assert out.get("failed-op") is not None     # full object report


def test_lin_sidecar_survives_leading_nemesis_op(tmp_path):
    """A nemesis op before the first client op must not mask a register
    run from the lin_* sidecar probe."""
    from jepsen_tpu import store

    h = [{"type": "info", "process": "nemesis", "f": "start-partition",
          "value": None}]
    for i in range(10):
        h.append({"type": "invoke", "process": 0, "f": "write",
                  "value": i})
        h.append({"type": "ok", "process": 0, "f": "write", "value": i})
    test = {"name": "lin-nem-t", "start_time": "20260801T000003",
            "store_dir": str(tmp_path), "history": h}
    store.write_history(test)
    store.write_columnar(test)
    assert store.load_linear_columns(
        "lin-nem-t", "20260801T000003", str(tmp_path)) is not None
