"""Ahead-of-time compiles of the checker's device programs for a
described TPU v5e, with no chip attached.

Interpret mode (tests/test_pallas_matrix.py) cannot see what Mosaic
refuses: an unsigned reduction, a DMA slice not aligned to the 128-lane
tile, a kernel past the scoped-VMEM limit. These compiles can, at about
two seconds each. The topology is described inside a module fixture,
never at import: only one process at a time may load the TPU library,
and every xdist worker imports this file.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# (S, V) at the matrix regime's edges: MV = 64, 256, 512
SHAPES = ((3, 8), (4, 16), (5, 16))
UOPS = (16, 512)
T, G = 256, 4


def _kernel_cases():
    from jepsen_tpu.ops import pallas_matrix as pm

    cases = []
    for S, V in SHAPES:
        for U in UOPS:
            for variant in pm.VARIANTS:
                if not pm.variant_ok(variant, S, V):
                    continue
                for mode in sorted({pm._pretile_mode(S, V, U, variant),
                                    "none"}):
                    cases.append(pytest.param(
                        S, V, U, variant, mode,
                        id=f"S{S}V{V}-U{U}-{variant}-{mode}"))
    return cases


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile cannot be read back from the
    # persistent cache without a chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("S,V,U,variant,mode", _kernel_cases())
def test_chunk_product_compiles(one_chip, S, V, U, variant, mode):
    import jax.numpy as jnp

    from jepsen_tpu.ops import pallas_matrix as pm

    fn = pm._build(S, V, T, U, False, mode, variant)
    hlo = _compile(fn, _sds(one_chip, (T, G, S), jnp.float32),
                   _sds(one_chip, (T, G, S), jnp.int32),
                   _sds(one_chip, (U, V, V), jnp.float32),
                   _sds(one_chip, (T, G), jnp.int32),
                   _sds(one_chip, (T, G), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_hbm_pretile_refused_below_128_lanes():
    """MV = 64 cannot DMA lane-aligned [MV, MV] tiles: a table past the
    VMEM budget keeps the in-kernel dots (compiled above) instead."""
    from jepsen_tpu.ops import pallas_matrix as pm

    assert pm._pretile_mode(3, 8, 512, "f32") == "none"
    assert pm._pretile_mode(4, 16, 512, "f32") == "hbm"
    assert "packed" not in pm.VARIANTS


@pytest.mark.parametrize("B,C,MV", [(4, 16, 128), (1, 8, 512)])
def test_fused_combine_compiles(one_chip, B, C, MV):
    import jax.numpy as jnp

    from jepsen_tpu.ops import pallas_matrix as pm

    fn = pm._build_combine(B, C, MV, False)
    hlo = _compile(fn, _sds(one_chip, (B, C, MV, MV), jnp.bfloat16),
                   _sds(one_chip, (B, MV, MV), jnp.bfloat16))
    assert "tpu_custom_call" in hlo


def test_graft_entry_scan_step_compiles(one_chip):
    """The event-scan step (no pallas kernel: plain XLA)."""
    import jax
    from __graft_entry__ import entry

    run, args = entry()
    hlo = _compile(jax.jit(run), *(
        _sds(one_chip, np.shape(a), np.asarray(a).dtype) for a in args))
    assert "tpu_custom_call" not in hlo
    assert jax.default_backend() == "cpu"   # the described chip ran nothing


@pytest.mark.parametrize("B,R", [(1, 8192), (512, 32)])
def test_dense_return_scan_compiles(one_chip, B, R):
    """The batched dense frontier scan at the register cells' shapes:
    S = 10 slots, 16 states, one 10k-op history (8,192 padded return
    steps) or 512 keys of at most 20 ops (32 steps each)."""
    import jax.numpy as jnp

    from jepsen_tpu.ops.jitlin import JitLinKernel

    S = 10
    fn = JitLinKernel()._get(S, 256, batched=True, num_states=16)
    hlo = _compile(fn, _sds(one_chip, (B, R), jnp.int32),
                   _sds(one_chip, (B, R, S), jnp.bool_),
                   _sds(one_chip, (B, R, S, 3), jnp.int32),
                   _sds(one_chip, (B, S), jnp.bool_),
                   _sds(one_chip, (B, S, 3), jnp.int32))
    assert hlo.startswith("HloModule jit_run")
    assert "tpu_custom_call" not in hlo


@pytest.fixture(scope="module")
def batch_64x1k():
    """The matrix kernel and one sub-batch of grids at the 64-key x 1k-op
    shape (BASELINE config 3): 5 processes, rand-int-5 values."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops import jitlin

    streams = [encode_register_ops(_register_history(
        1000, n_procs=5, seed=1000 + k, n_values=5)) for k in range(64)]
    sub = jitlin.MATRIX_PIPELINE_KEYS
    S = max(s.n_slots for s in streams)
    V = jitlin._bucket(max(len(s.intern) for s in streams), floor=8)
    preps = [jitlin._returns_prepass(*(np.asarray(getattr(s, f)) for f in
                                       ("kind", "slot", "f", "a", "b")))
             for s in streams[:sub]]
    R_max = max(p[0].shape[0] for p in preps)
    C, Tk = jitlin._matrix_plan(sub, S, R_max, V, None)
    run = jitlin._matrix_cache(S, V, jitlin.cas_register_step_ids, 0, Tk, C,
                               sub)
    grids, uops = jitlin._matrix_grids(preps, S, V, sub, C, Tk, None)
    pend, ids, slots, valid = (np.asarray(g) for g in grids)
    return run, (pend, ids, np.asarray(uops), slots, valid), sub, C, \
        (1 << S) * V


@pytest.mark.parametrize("stage", ["products-int8", "products-f32",
                                   "scan_total", "combine_fused"])
def test_matrix_batch_stages_compile(one_chip, batch_64x1k, stage):
    import jax.numpy as jnp

    run, arrays, B, C, MV = batch_64x1k
    args = [_sds(one_chip, a.shape, a.dtype) for a in arrays]
    tot0 = _sds(one_chip, (B, MV, MV), jnp.bfloat16)
    if stage.startswith("products"):
        fn = run.stages["products"](stage.split("-")[1])
        hlo = _compile(fn, *args)
    elif stage == "scan_total":
        hlo = _compile(run.stages["scan_total"], *args, tot0)
    else:
        hlo = _compile(run.stages["combine_fused"],
                       _sds(one_chip, (B * C, MV, MV), jnp.bfloat16),
                       _sds(one_chip, (B * C,), jnp.bool_), tot0)
    assert ("tpu_custom_call" in hlo) is (stage != "scan_total")
