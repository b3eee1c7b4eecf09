"""Pallas transfer-matrix kernels (ops/pallas_matrix.py).

CPU tier: every kernel variant (f32 / int8-MXU) at two (S, V) shapes and
every L-build mode (in-kernel dots / VMEM pretile / HBM-streamed
pretile), plus the fused streaming combine, run in pallas interpret
mode and are differentially pinned against (a) an independent numpy
oracle of the factored math and (b) the XLA scan path through the
PRODUCTION matrix_check dispatch. Probe sidecar caching and the
demote-not-fail variant ladder are unit-tested with fake probes.
Real-chip verdict parity lives in tests/test_tpu_parity.py (-m tpu).
"""
from __future__ import annotations

import numpy as np
import pytest

VARIANTS = ("f32", "int8")
MODES = ("none", "vmem", "hbm")
# (variant, S, V): every variant at MV=64, plus int8 at a second MV=64
# factorization (fewer slots, wider value domain)
CASES = (("f32", 3, 8), ("int8", 3, 8), ("int8", 2, 16))


def _oracle(S, V, pend, ids, mtT, slots, valid):
    """The shared numpy replay (also the enabled() probe's reference)."""
    from jepsen_tpu.ops.pallas_matrix import _oracle_product

    return _oracle_product(S, V, pend, ids, mtT, slots, valid)


def _inputs(S, V, T, U, G, seed=0):
    rng = np.random.default_rng(seed)
    return ((rng.random((T, G, S)) < 0.5).astype(np.float32),
            rng.integers(0, U, (T, G, S)).astype(np.int32),
            (rng.random((U, V, V)) < 0.3).astype(np.float32),
            rng.integers(0, S, (T, G)).astype(np.int32),
            (rng.random((T, G)) < 0.8).astype(np.float32))


def test_static_tables_express_kron_and_kill():
    """Rexp * tile(X) == R (kron) X^T, and Kexp @ B == the row
    gather+mask the XLA path performs — the two identities the
    factored kernel rests on."""
    from jepsen_tpu.ops.pallas_matrix import _static_tables

    S, V = 3, 4
    M = 1 << S
    MV = M * V
    Rexp, Kexp, U1, U2 = _static_tables(S, V)
    rng = np.random.default_rng(7)
    X = (rng.random((V, V)) < 0.4).astype(np.float32)
    rows = np.arange(MV)
    a, w = rows // V, rows % V
    for s in range(S):
        R = np.zeros((M, M), np.float32)
        src = np.arange(M)[((np.arange(M) >> s) & 1) == 0]
        R[src | (1 << s), src] = 1.0
        kron = R[a][:, a] * X.T[w][:, w]  # [(a,w),(b,v)] = R[a,b] X[v,w]
        got = Rexp[s] * (U1 @ X.T @ U2)
        assert np.array_equal(kron, got), s

    B = (rng.random((MV, MV)) < 0.3).astype(np.float32)
    for s in range(S):
        ok = ((a >> s) & 1) == 0
        kill_idx = np.where(ok, ((a | (1 << s)) * V + w), 0)
        ref = B[kill_idx] * ok[:, None]
        assert np.array_equal((Kexp[s] @ B > 0) * 1.0, (ref > 0) * 1.0), s


@pytest.mark.parametrize("variant,S,V", CASES)
def test_kernel_matches_numpy_oracle_interpret(variant, S, V):
    """Every representation variant is bit-identical to the numpy
    oracle on a random run — the identity the auto-probe re-verifies
    per (S, V, variant) before a production dispatch."""
    from jepsen_tpu.ops.pallas_matrix import _build

    T, U, G = 5, 16, 4
    pend, ids, mtT, slots, valid = _inputs(S, V, T, U, G)
    ref = _oracle(S, V, pend, ids, mtT, slots, valid)
    fn = _build(S, V, T, U, interpret=True, variant=variant)
    got = np.asarray(fn(pend, ids, mtT, slots, valid)).astype(np.float32)
    assert np.array_equal(ref, got), variant


@pytest.mark.parametrize("variant,S,V", CASES)
@pytest.mark.parametrize("mode", MODES)
def test_lbuild_modes_match_oracle_interpret(variant, S, V, mode):
    """All three L-build data paths — in-kernel tiling dots, the VMEM
    pre-tiled table, and the HBM-streamed double-buffered table — are
    bit-identical to the oracle for every variant (the hbm mode is what
    lets value domains past PALLAS_PRETILE_BYTES keep the fast
    L-build)."""
    from jepsen_tpu.ops.pallas_matrix import _build

    T, U, G = 5, 16, 4
    pend, ids, mtT, slots, valid = _inputs(S, V, T, U, G, seed=3)
    ref = _oracle(S, V, pend, ids, mtT, slots, valid)
    fn = _build(S, V, T, U, interpret=True, pretile=mode, variant=variant)
    got = np.asarray(fn(pend, ids, mtT, slots, valid)).astype(np.float32)
    assert np.array_equal(ref, got), (variant, mode)


def test_pretile_mode_selection(monkeypatch):
    """Mode thresholds: VMEM under the budget, HBM streaming past it,
    in-kernel dots past the HBM cap; integer variants' 1-byte tables
    extend the VMEM budget 4x over f32."""
    import jepsen_tpu.ops.pallas_matrix as pm

    S, V = 3, 16           # MV=128 -> one f32 tile = 64 KiB
    assert pm._pretile_mode(S, V, 16, "f32") == "vmem"
    monkeypatch.setattr(pm, "PALLAS_PRETILE_BYTES", 16 * 128 * 128)
    # f32 tables now blow the VMEM budget at U=16; the int8 table is
    # 4x smaller and still fits
    assert pm._pretile_mode(S, V, 16, "f32") == "hbm"
    assert pm._pretile_mode(S, V, 16, "int8") == "vmem"
    monkeypatch.setattr(pm, "PALLAS_PRETILE_HBM_BYTES", 16 * 128 * 128)
    assert pm._pretile_mode(S, V, 64, "f32") == "none"


def test_fused_combine_matches_tree_and_oracle():
    """The fused streaming combine == the sequential numpy chain == the
    jitlin tree combine, bit for bit (boolean products are exact under
    any association — the identity that makes the fusion safe)."""
    import jax.numpy as jnp
    from jepsen_tpu.ops.jitlin import _kernel_math
    from jepsen_tpu.ops.pallas_matrix import _build_combine, _combine_oracle

    B, C, MV = 2, 7, 32
    S, V = 2, 8            # MV = (1<<2)*8 = 32
    rng = np.random.default_rng(4)
    P = (rng.random((B, C, MV, MV)) < 0.15).astype(np.float32)
    tot0 = np.broadcast_to(np.eye(MV, dtype=np.float32),
                           (B, MV, MV)).copy()
    ref = _combine_oracle(P, tot0)
    fn = _build_combine(B, C, MV, interpret=True)
    got = np.asarray(fn(jnp.asarray(P, jnp.bfloat16),
                        jnp.asarray(tot0, jnp.bfloat16))
                     ).astype(np.float32)
    assert np.array_equal(got, ref)

    def step_ids(st, f, a, b):   # unused by the combine; shape only
        return st, jnp.ones_like(st, dtype=bool)

    math = _kernel_math(S, V, step_ids, B * C)
    tree = math.make_combine(B, C, init_state=0)
    alive, _, total = tree(
        jnp.asarray(P.reshape(B * C, MV, MV), jnp.bfloat16),
        jnp.zeros((B * C,), bool), jnp.asarray(tot0, jnp.bfloat16))
    assert np.array_equal(np.asarray(total, dtype=np.float32), ref)
    assert np.array_equal(np.asarray(alive),
                          (ref[:, :, 0] > 0).any(axis=1))


def test_production_dispatch_variant_parity(monkeypatch):
    """matrix_check through every pallas variant (interpret mode,
    forced) agrees with the XLA scan path on valid AND corrupted
    histories, and the fused combine rides the same dispatches — the
    same cross-checks the chip parity tier runs for real. Quick-lane
    shapes: 60-op small-domain histories."""
    from __graft_entry__ import _register_history  # conftest adds the root
    import jepsen_tpu.ops.pallas_matrix as pm
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import last_dispatch_info, matrix_check

    def verdicts(h, variant):
        monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
        scan = matrix_check(encode_register_ops(h), force=True,
                            combine_fused=False)
        assert last_dispatch_info()["variant"] == "scan"
        monkeypatch.setattr(pm, "FORCE_INTERPRET", True)
        try:
            pallas = matrix_check(encode_register_ops(h), force=True,
                                  variant=variant)
            info = last_dispatch_info()
        finally:
            monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
        assert info["variant"] == variant, info
        assert info["combine"] == "fused", info
        return scan, pallas

    h_ok = _register_history(60, n_procs=3, seed=5, n_values=4)
    h_bad = _register_history(60, n_procs=3, seed=6, n_values=4)
    import random
    reads = [op for op in h_bad
             if op.get("f") == "read" and op.get("type") == "ok"]
    for op in random.Random(0).sample(reads, min(2, len(reads))):
        op["value"] = 999

    for variant in VARIANTS:
        scan, pallas = verdicts(h_ok, variant)
        assert scan is not None and pallas is not None
        assert pallas[0] == scan[0] is True, variant
        scan, pallas = verdicts(h_bad, variant)
        assert pallas[0] == scan[0] is False, variant


@pytest.mark.explain
@pytest.mark.parametrize("variant,n_values", [("int8", 4), ("int8", 5),
                                              ("f32", 4)])
def test_variant_verdict_localizes_to_frontier(variant, n_values,
                                                monkeypatch):
    """ISSUE 12 (explain tier): an INVALID verdict from each pallas
    kernel variant (interpret mode) localizes to the same
    first-return/event as the exact CPU frontier — the representation
    changes how the boolean products are computed, never which return
    first kills the frontier. (Lives here rather than test_explain.py
    so its interpret-mode compiles don't land right before the
    timing-sensitive live-daemon tests in tier-1 file order.)"""
    from __graft_entry__ import _register_history
    import jepsen_tpu.ops.pallas_matrix as pm
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import matrix_check, matrix_localize

    h = _register_history(160, n_procs=3, seed=6, n_values=n_values)
    import random
    reads = [op for op in h
             if op.get("f") == "read" and op.get("type") == "ok"]
    for op in random.Random(1).sample(reads, 2):
        op["value"] = 999
    s = encode_register_ops(h)
    cpu = check_stream(s)
    assert cpu.valid is False
    monkeypatch.setattr(pm, "FORCE_INTERPRET", True)
    try:
        m = matrix_check(s, force=True, variant=variant)
    finally:
        monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
    assert m is not None and m[0] is False and not m[2], variant
    loc = matrix_localize(s)
    assert loc is not None
    assert loc.failed_event == cpu.failed_event, variant
    assert loc.failed_op_index == cpu.failed_op_index, variant


def test_checker_knobs_route_variant(monkeypatch):
    """The test-map knobs reach the ladder's matrix rung: a pinned
    matrix_variant/combine_fused routes the dispatch (visible in the
    re-published phase split's routing labels), and the verdict settles
    at the matrix rung as before."""
    from __graft_entry__ import _register_history
    import jepsen_tpu.ops.pallas_matrix as pm
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.ops import jitlin

    monkeypatch.setattr(jitlin, "MATRIX_MIN_RETURNS", 10)
    monkeypatch.setattr(pm, "FORCE_INTERPRET", True)
    chk = LinearizableChecker(accelerator="tpu")
    out = chk.check({"matrix_variant": "int8", "combine_fused": True,
                     "checker_sharded": False},
                    _register_history(240, n_procs=3, seed=3, n_values=5),
                    {})
    assert out["valid?"] is True
    assert out["algorithm"] == "jitlin-cpu-matrix"
    split = jitlin.last_phase_seconds()
    assert split.get("variant") == "int8", split
    assert split.get("combine") == "fused", split


def test_variant_runtime_failure_demotes(monkeypatch):
    """A variant that blows up at dispatch time is disabled and the
    dispatch demotes to the next representation — same verdict, no
    error (PR-3 ladder semantics inside the rung)."""
    from __graft_entry__ import _register_history
    import jepsen_tpu.ops.pallas_matrix as pm
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import last_dispatch_info, matrix_check

    monkeypatch.setattr(pm, "FORCE_INTERPRET", True)
    monkeypatch.setattr(pm, "_DISABLED", set())
    real_build = pm._build.__wrapped__

    def bomb(S, V, T, U, interpret=False, pretile="none", variant="f32"):
        if variant == "int8":
            raise RuntimeError("synthetic int8 lowering failure")
        return real_build(S, V, T, U, interpret, pretile, variant)

    bomb.__wrapped__ = bomb
    import functools
    monkeypatch.setattr(pm, "_build", functools.lru_cache(maxsize=32)(bomb))
    h = _register_history(60, n_procs=3, seed=5, n_values=4)
    m = matrix_check(encode_register_ops(h), force=True, variant="int8")
    assert m is not None and m[0] is True
    info = last_dispatch_info()
    assert info["variant"] == "f32", info      # demoted one rung down
    assert (3, 8, "int8") in pm._DISABLED


def test_gates(monkeypatch):
    import jepsen_tpu.ops.pallas_matrix as pm

    # VMEM caps: decline huge operator dimensions
    assert pm.chunk_product(9, 8, 4, 16) is None        # S over cap
    assert pm.chunk_product(8, 16, 4, 16) is None       # MV = 4096 over cap
    assert pm.variant_ok("packed", 3, 8) is False       # removed variant
    assert pm.variant_ok("int8", 1, 8) is True          # MV=16
    assert pm.variant_ok("int8", 5, 16) is True         # MV=512 at the cap
    # hbm DMA tiles need a 128-lane-aligned MV: below it the table
    # keeps the in-kernel dots however big it grows
    assert pm._pretile_mode(3, 8, 2048, "f32") == "none"    # MV=64
    assert pm._pretile_mode(4, 16, 512, "f32") == "hbm"     # MV=256
    assert pm.variant_ok("bf16", 3, 8) is False         # unknown name
    # env kill-switch (monkeypatch restores any externally-set value)
    monkeypatch.setenv("JEPSEN_TPU_NO_PALLAS", "1")
    assert not pm.available()
    assert not pm.enabled(3, 8)
    assert not pm.combine_enabled(64)
    assert pm.best_variant(3, 8) is None
    assert pm.chunk_product(3, 8, 4, 16) is None
    monkeypatch.delenv("JEPSEN_TPU_NO_PALLAS")
    assert pm.available()


def test_env_and_knob_coercion(monkeypatch):
    """Tolerant routing knobs: garbage warns and reads as unset/auto,
    never raises (the sweep-variable discipline every env knob here
    follows)."""
    import jepsen_tpu.ops.pallas_matrix as pm

    monkeypatch.setenv("JEPSEN_TPU_MATRIX_VARIANT", "Int8")
    assert pm.matrix_variant() == "int8"
    monkeypatch.setenv("JEPSEN_TPU_MATRIX_VARIANT", "packed")
    assert pm.matrix_variant() == "auto"
    monkeypatch.setenv("JEPSEN_TPU_MATRIX_VARIANT", "bf16")
    assert pm.matrix_variant() == "auto"
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "FORCE")
    assert pm.probe_mode() == "force"
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "never")
    assert pm.probe_mode() == "auto"
    monkeypatch.setenv("JEPSEN_TPU_FUSE_COMBINE", "no")
    assert pm.fuse_combine_mode() is False
    monkeypatch.setenv("JEPSEN_TPU_FUSE_COMBINE", "1")
    assert pm.fuse_combine_mode() is True
    monkeypatch.delenv("JEPSEN_TPU_FUSE_COMBINE")
    assert pm.fuse_combine_mode() is None
    assert pm.coerce_variant("int8") == "int8"
    assert pm.coerce_variant("auto") is None
    assert pm.coerce_variant("") is None
    assert pm.coerce_variant(7) is None


def test_probe_sidecar_cache(monkeypatch, tmp_path):
    """Probe verdicts persist per (backend, jax version, S, V, variant)
    in the fs_cache sidecar: a fresh process (fresh _PROBED) reuses the
    stored verdict instead of re-probing; JEPSEN_TPU_PALLAS_PROBE=force
    re-probes and refreshes; =skip trusts the gates without probing.
    probe_seconds() accumulates only for real probe runs."""
    import jepsen_tpu.ops.pallas_matrix as pm

    monkeypatch.setenv("JEPSEN_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_DISABLED", set())
    calls = []
    monkeypatch.setattr(pm, "_run_probe",
                        lambda S, V, variant, mode: calls.append(variant)
                        or True)
    t0 = pm.probe_seconds()
    assert pm.enabled(3, 8, "int8") is True
    assert calls == ["int8"]
    assert pm.probe_seconds() >= t0

    # fresh process: in-memory memo cleared, sidecar answers
    monkeypatch.setattr(pm, "_PROBED", {})
    assert pm.enabled(3, 8, "int8") is True
    assert calls == ["int8"]                   # no second probe

    # force: re-probe and refresh the sidecar
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "force")
    monkeypatch.setattr(pm, "_PROBED", {})
    assert pm.enabled(3, 8, "int8") is True
    assert calls == ["int8", "int8"]

    # skip: gates only, no probe, nothing persisted for this key
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "skip")
    monkeypatch.setattr(pm, "_PROBED", {})
    assert pm.enabled(3, 8, "f32") is True
    assert "f32" not in calls

    # a persisted MISS also sticks across processes
    monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "auto")
    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_run_probe",
                        lambda S, V, variant, mode: False)
    assert pm.enabled(4, 8, "f32") is False
    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_run_probe",
                        lambda S, V, variant, mode: True)
    assert pm.enabled(4, 8, "f32") is False    # sidecar's verdict wins


def test_transient_probe_failure_not_persisted(monkeypatch, tmp_path):
    """A transient probe failure (device busy, co-tenant OOM) must not
    write a permanent ok=false verdict into the cross-process sidecar —
    the next process re-probes and self-heals. Deterministic failures
    (lowering errors, mismatches) do persist."""
    import jepsen_tpu.ops.pallas_matrix as pm

    monkeypatch.setenv("JEPSEN_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_DISABLED", set())

    def busy(S, V, variant, mode):
        raise RuntimeError("RESOURCE_EXHAUSTED: co-tenant ate the HBM")

    monkeypatch.setattr(pm, "_run_probe", busy)
    assert pm.enabled(3, 8, "int8") is False       # this process: off
    monkeypatch.setattr(pm, "_PROBED", {})         # "next process"
    monkeypatch.setattr(pm, "_run_probe",
                        lambda S, V, variant, mode: True)
    assert pm.enabled(3, 8, "int8") is True        # re-probed, healed

    def lower_fail(S, V, variant, mode):
        raise RuntimeError("Only interpret mode is supported on CPU")

    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_run_probe", lower_fail)
    assert pm.enabled(4, 8, "int8") is False
    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_run_probe",
                        lambda S, V, variant, mode: True)
    assert pm.enabled(4, 8, "int8") is False       # persisted verdict wins


def test_best_variant_order_and_demotion(monkeypatch):
    """Auto order prefers the densest probed-good representation; a
    pinned variant that fails its probe demotes down the order instead
    of erroring; runtime disable() beats every probe."""
    import jepsen_tpu.ops.pallas_matrix as pm

    monkeypatch.setattr(pm, "FORCE_INTERPRET", False)
    monkeypatch.setattr(pm, "_PROBED", {})
    monkeypatch.setattr(pm, "_DISABLED", set())
    monkeypatch.delenv("JEPSEN_TPU_MATRIX_VARIANT", raising=False)
    verdicts = {"int8": False, "f32": True}
    monkeypatch.setattr(
        pm, "enabled",
        lambda S, V, variant="f32": verdicts.get(variant, False))
    assert pm.best_variant(3, 8) == "f32"
    assert pm.best_variant(3, 8, force="int8") == "f32"   # demoted
    assert pm.best_variant(3, 8, force="packed") == "f32"  # unknown name
    verdicts.update({"int8": True})
    assert pm.best_variant(3, 8) == "int8"
    monkeypatch.setenv("JEPSEN_TPU_MATRIX_VARIANT", "f32")
    assert pm.best_variant(3, 8) == "f32"
