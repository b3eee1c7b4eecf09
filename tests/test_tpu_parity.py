"""Real-TPU parity tier (VERDICT r3 item 5): device-vs-CPU verdict
parity for the hot kernels on ONE real chip. The CPU-backend fuzz
cannot catch backend-specific breakage (layout, bf16, Mosaic lowering)
— this tier runs the same checks on the actual device.

Opt-in: ``JEPSEN_TPU_TESTS=1 python -m pytest -m tpu tests/`` on the
chip machine (conftest leaves the platform list alone when the env var
is set). Without the env var every test here skips instantly and the
normal suite never touches the chip.

First compiles are slow (~20-40s each) — the module warms shared
shape-buckets so later tests reuse compiled kernels.
"""
import os

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

if not os.environ.get("JEPSEN_TPU_TESTS"):
    pytest.skip("JEPSEN_TPU_TESTS not set (real-chip tier is opt-in)",
                allow_module_level=True)


@pytest.fixture(scope="module")
def tpu_device():
    import jax
    devices = [d for d in jax.devices() if d.platform != "cpu"]
    if not devices:
        pytest.skip("no non-CPU jax device present")
    return devices[0]


def _histories():
    from __graft_entry__ import _register_history
    good = _register_history(2_000, n_procs=5, seed=7, n_values=5)
    bad = [dict(op) for op in good]
    # corrupt one mid-history read completion to a value NOBODY ever
    # writes (outside the 5-value domain) — unconditionally
    # non-linearizable regardless of concurrency structure
    for i in reversed(range(len(bad) // 2, len(bad))):
        op = bad[i]
        if op["type"] == "ok" and op["f"] == "read":
            bad[i] = {**op, "value": 97}
            break
    return good, bad


@pytest.fixture(scope="module")
def streams():
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    good, bad = _histories()
    return encode_register_ops(good), encode_register_ops(bad)


def test_matrix_kernel_verdict_parity(tpu_device, streams):
    """Block-composed transfer-matrix kernel vs the CPU WGL oracle."""
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.ops.jitlin import matrix_check

    good, bad = streams
    # force=True skips the min-size gate (the differential-test seam) so
    # the tier stays fast; the kernel itself is the production one
    m = matrix_check(good, force=True)
    assert m is not None and bool(m[0]) and not bool(m[2])
    assert check_stream(good).valid is True
    mb = matrix_check(bad, force=True)
    assert mb is not None and not bool(mb[0])
    assert check_stream(bad).valid is False


def test_event_scan_verdict_parity(tpu_device, streams):
    """Dense-table event-scan kernel vs the CPU oracle, both verdicts."""
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import pad_streams
    from jepsen_tpu.ops.jitlin import (JitLinKernel, _bucket, scan_inputs,
                                       verdict)

    good, bad = streams
    for stream, want in ((good, True), (bad, False)):
        batch = pad_streams([stream], length=_bucket(len(stream)))
        run = JitLinKernel()._get(stream.n_slots, 256, batched=False,
                                  num_states=len(stream.intern))
        import jax.numpy as jnp
        args, _ = scan_inputs(
            *(batch[k] for k in ("kind", "slot", "f", "a", "b")),
            stream.n_slots, len(stream.intern))
        alive, died, ovf, _peak = [
            np.asarray(x) for x in run(*(jnp.asarray(a[0]) for a in args))]
        assert verdict(bool(alive), bool(ovf)) is want
        assert check_stream(stream).valid is want


def test_batch_check_multikey_parity(tpu_device):
    """The vmapped multi-key dispatch agrees with the CPU oracle
    per key, including a planted failure."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.parallel import batch_check

    good, bad = _histories()
    streams = [encode_register_ops(
        _register_history(500, n_procs=5, seed=100 + k, n_values=5))
        for k in range(7)] + [encode_register_ops(bad)]
    results = batch_check(streams, capacity=256)
    cpu = [check_stream(s).valid for s in streams]
    dev = [bool(r[0]) and not bool(r[2]) for r in results]
    assert dev == cpu
    assert dev[-1] is False and all(dev[:-1])


def test_set_full_membership_parity(tpu_device):
    """Device membership-matrix set-full path vs the CPU walk."""
    from jepsen_tpu.checker import SetFullChecker

    history, present = [], []
    t = 0
    for v in range(800):
        history.append({"type": "invoke", "process": v % 5, "f": "add",
                        "value": v, "time": t})
        history.append({"type": "ok", "process": v % 5, "f": "add",
                        "value": v, "time": t + 1})
        present.append(v)
        t += 2
        if (v + 1) % 40 == 0:
            history.append({"type": "invoke", "process": 5, "f": "read",
                            "value": None, "time": t})
            history.append({"type": "ok", "process": 5, "f": "read",
                            "value": list(present), "time": t + 1})
            t += 2
    # plant a LOST element: 100 is visible in early reads (known), then
    # vanishes from every read past element 400 — known-then-absent is
    # the set-full "lost" verdict regardless of add acknowledgment
    lost_history = [dict(op) for op in history]
    for op in lost_history:
        if op.get("f") == "read" and op.get("type") == "ok" \
                and max(op["value"]) >= 400:
            op["value"] = [x for x in op["value"] if x != 100]
    for h, want in ((history, True), (lost_history, False)):
        r_dev = SetFullChecker(accelerator="tpu").check({}, h, {})
        r_cpu = SetFullChecker(accelerator="cpu").check({}, h, {})
        assert bool(r_dev["valid?"]) is want, r_dev
        assert r_dev["valid?"] == r_cpu["valid?"]
        assert r_dev["stable-count"] == r_cpu["stable-count"]
        assert r_dev.get("lost-count") == r_cpu.get("lost-count")


def test_scc_screen_parity(tpu_device):
    """Device SCC trim vs CPU Tarjan on cyclic and acyclic graphs."""
    from jepsen_tpu.ops.scc import has_cycle, tarjan_scc

    rng = np.random.default_rng(3)
    n = 500
    # random DAG: edges only forward
    src = rng.integers(0, n - 1, 2000)
    off = rng.integers(1, 50, 2000)
    dst = np.minimum(src + off, n - 1)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    assert has_cycle(n, src, dst) is False
    assert all(len(c) == 1 for c in tarjan_scc(
        n, list(zip(src.tolist(), dst.tolist()))))
    # close one long cycle
    src2 = np.concatenate([src, [n - 1]])
    dst2 = np.concatenate([dst, [0]])
    dev = has_cycle(n, src2, dst2)
    cpu_sccs = tarjan_scc(n, list(zip(src2.tolist(), dst2.tolist())))
    assert dev is (max(len(c) for c in cpu_sccs) > 1)


def test_elle_device_parity(tpu_device):
    """The list-append check's device screen agrees with the CPU path on
    a valid and an anomalous history."""
    from chip_smoke import _elle_history
    from jepsen_tpu.elle import list_append

    good = _elle_history(2_000)
    bad = _elle_history(2_000, crossed_pairs=10)
    for h, want in ((good, True), (bad, False)):
        r_dev = list_append.check(h, accelerator="tpu")
        r_cpu = list_append.check(h, accelerator="cpu")
        assert r_dev["valid?"] is want and r_cpu["valid?"] is want
        if not want:
            assert set(r_dev["anomaly-types"]) == set(r_cpu["anomaly-types"])


def test_pallas_chunk_product_parity(tpu_device, streams):
    """The pallas fused chunk product (ops/pallas_matrix.py) against
    the XLA scan path on the REAL chip, both verdict polarities. Also
    asserts the self-verifying probe actually admitted the pallas path
    on this backend (if Mosaic regressed, the probe must say so rather
    than this test silently exercising the fallback twice)."""
    import jepsen_tpu.ops.pallas_matrix as pm
    from jepsen_tpu.ops.jitlin import matrix_check

    good, bad = streams
    if not pm.enabled(5, 8):
        pytest.fail("pallas probe rejected the kernel on the real chip "
                    "(lowering failure or miscompile — see the log); "
                    f"_DISABLED={pm._DISABLED} _PROBED={pm._PROBED}")
    for stream, expect in ((good, True), (bad, False)):
        pal = matrix_check(stream, force=True)
        os.environ["JEPSEN_TPU_NO_PALLAS"] = "1"
        try:
            scan = matrix_check(stream, force=True)
        finally:
            del os.environ["JEPSEN_TPU_NO_PALLAS"]
        assert pal is not None and scan is not None
        assert bool(pal[0]) == bool(scan[0]) == expect
