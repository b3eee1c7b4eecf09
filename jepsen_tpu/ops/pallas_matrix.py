"""Pallas TPU kernels for the transfer-matrix chunk product.

The block-composed matrix kernel (ops/jitlin.py _build_matrix_kernel,
the TPU analog of knossos's wgl search — checker.clj:185-216) advances
every chunk's composed operator by one return per ``lax.scan`` step.
Under XLA each step materializes ~6 [G, MV, MV] intermediates in HBM
(L build, I+L, the closure squarings, the kill product, the compose),
and on long histories (the scale path's ~2k-step segments) that HBM
round-trip traffic — not the matmul FLOPs — bounds the step.

This kernel fuses the ENTIRE T-step product per chunk: one pallas
program per chunk g keeps its running product P in a VMEM scratch
buffer across all T returns and only writes the final [MV, MV] chunk
product to HBM. Per-step HBM traffic drops from ~6 full [G, MV, MV]
arrays to zero.

Matrix representation VARIANTS
------------------------------
Every matrix in this algebra is a boolean reachability operator — all
entries are exactly 0 or 1 and every product is thresholded back to
0/1. Doing that work as f32 matmuls wastes the hardware: the MXU
multiplies 32-bit mantissas to compute what is semantically AND/OR.
Two probe-selected representations:

* ``f32``    — the compatibility baseline: f32 0/1 operands, f32
  accumulation, ``> 0`` threshold. Bit-exact and universally lowerable;
  the terminal fallback when the integer paths miscompile. (Naive bf16
  was measured ~25 % SLOWER here — the (16, 128) bf16 tile shape slows
  the per-step thresholds more than the MXU rate buys at MV = 256 — so
  the win has to come from operand density, not a float dtype swap.)
* ``int8``   — int8 0/1 operands through the MXU with
  ``preferred_element_type=jnp.int32`` (counts ≤ MV ≤ 2^12 are exact in
  int32), saturating ``> 0`` threshold back to int8. 4× the effective
  operand density of f32 on MXU generations with int8 feeds.

(A bit-packed uint32 variant was removed: Mosaic refuses both its
unsigned word reduction and the [MV, MV/32, 32] reshape that packs the
words, so it never compiled for the chip.)

All variants compute the same thresholded 0/1 matrices, so results are
bit-identical to the numpy oracle and the XLA scan path — each
(S, V, variant) admits itself through the same end-to-end probe, and a
variant that fails to lower or miscompiles demotes to the next one
(PR-3 ladder semantics), never to a wrong verdict.

The L build is re-formulated to be layout-friendly (no [M, V, M, V]
reshapes, which relayout badly on TPU tiles):

    L = sum_s pend_s * (R_s (kron) Mt_s^T)
      = sum_s pend_s * Rexp_s * (U1 @ Mt_s^T @ U2)

where ``Rexp_s[(a,w),(b,v)] = R_s[a,b]`` is a STATIC [MV, MV]
block-expansion of the slot-s receiver map, and ``U1 @ X @ U2`` tiles a
[V, V] matrix over every (a, b) block — two tiny matmuls plus one VPU
elementwise multiply, instead of a Kronecker construction. The kill
gather becomes a matmul with a static per-slot kill matrix
``Kexp_s[r, kill_idx_s[r]] = kill_mask_s[r]``.

Pre-tiled L-build modes (``_pretile_mode``): with ``vmem`` the
[U, MV, MV] tiled uop table U1 @ Mt_u^T @ U2 is precomputed ONCE in XLA
and resides in VMEM (gather + VPU multiply per step, no in-kernel
dots); with ``hbm`` the same table is too big for VMEM but lives in
HBM and the per-step tiles stream in through a double-buffered DMA
pipeline (step t's closure compute overlaps step t+1's tile fetches) —
large value domains no longer fall back to the slow in-kernel L
construction. The integer variants store the table at 1 byte/entry,
which by itself extends the VMEM budget 4× over f32.

``chunk_product`` returns a jitted callable or None when the regime
doesn't fit (VMEM budget, dtype caps) or pallas lowering fails on this
backend — callers fall back to the XLA scan path.

Probe caching: the per-(S, V, variant) self-test verdicts persist in a
store-side sidecar (fs_cache) keyed by backend + jax version, so fresh
processes stop re-paying probe compiles; ``JEPSEN_TPU_PALLAS_PROBE=
force`` re-probes (and re-writes the sidecar), ``skip`` trusts the
shape gates without probing. ``probe_seconds()`` exposes this process's
cumulative probe wall (also the ``pallas_probe_seconds_total``
counter), so probe time stops hiding inside first-check compile time.
"""
from __future__ import annotations

import functools
import logging
import os
import time

import numpy as np

logger = logging.getLogger("jepsen.pallas")

# VMEM budget gate: the two static [S, MV, MV] tables plus ~4 [MV, MV]
# scratch/working buffers; at MV = 512 the f32 tables alone pass the
# compiler's 16 MiB default scoped-VMEM limit, hence PALLAS_VMEM_LIMIT
PALLAS_MAX_MV = 512
PALLAS_MAX_SLOTS = 8
# scoped VMEM each kernel may claim (v5e has 128 MiB of VMEM per core)
PALLAS_VMEM_LIMIT = 64 << 20
# the hbm mode DMAs [MV, MV] tiles; Mosaic needs the lane dimension of
# a DMA slice aligned to the 128-lane tile
PALLAS_HBM_MIN_MV = 128

# L-build pre-tiling budget: when the whole [U, MV, MV] pre-tiled uop
# table fits this many bytes of VMEM alongside the static tables, the
# per-step U1 @ Mt^T @ U2 tiling dots move OFF the critical path — they
# run once in XLA before the pallas program instead of 2*S heavily
# padded [MV, V] x [V, V] MXU dots per step (V is ~8-16 in the matrix
# regime: those dots under-tile the 128-lane MXU badly, so their cost
# is far above their FLOP share). Integer variants count 1 byte/entry.
PALLAS_PRETILE_BYTES = 4 << 20
# ... and past the VMEM budget the table stays in HBM and the per-step
# tiles stream in via double-buffered DMA (mode "hbm") up to this cap
PALLAS_PRETILE_HBM_BYTES = 128 << 20

#: auto-probe preference order: densest representation first; each
#: candidate must pass its (S, V, variant) differential probe before
#: taking a production dispatch, and a runtime failure demotes to the
#: next (jitlin._dispatch_total's variant loop)
VARIANTS = ("int8", "f32")


def available() -> bool:
    """Pallas path enabled? (env kill-switch for triage)."""
    return not os.environ.get("JEPSEN_TPU_NO_PALLAS")


_ENV_WARNED: set = set()


def _env_choice(name: str, choices: tuple, default: str) -> str:
    """Tolerant env enum knob: unset/empty -> default, a valid choice
    passes, garbage warns ONCE per distinct value and degrades to the
    default (these knobs are re-read on every matrix dispatch — a bad
    sweep variable must neither make the module unusable nor flood the
    log of a segmented run)."""
    raw = os.environ.get(name, "")
    if not raw:
        return default
    v = raw.strip().lower()
    if v in choices:
        return v
    if (name, raw) not in _ENV_WARNED:
        _ENV_WARNED.add((name, raw))
        logger.warning("ignoring malformed %s=%r (want one of %s)",
                       name, raw, "|".join(choices))
    return default


def matrix_variant() -> str:
    """The operator's variant preference: ``auto`` (probe order) or a
    forced member of VARIANTS (still probe-gated — a forced variant
    that fails its probe demotes down the auto order, never errors)."""
    return _env_choice("JEPSEN_TPU_MATRIX_VARIANT",
                       ("auto",) + VARIANTS, "auto")


def probe_mode() -> str:
    """``auto`` — sidecar-cached probes; ``force`` — re-probe (and
    refresh the sidecar); ``skip`` — trust the shape gates, no probe."""
    return _env_choice("JEPSEN_TPU_PALLAS_PROBE",
                       ("auto", "force", "skip"), "auto")


def fuse_combine_mode() -> bool | None:
    """JEPSEN_TPU_FUSE_COMBINE: True/False force the fused/tree chunk
    combine; None (default) = probe decides (jepsen_tpu.parallel
    coerce_flag semantics for the string forms; a malformed value warns
    once, not per dispatch)."""
    raw = os.environ.get("JEPSEN_TPU_FUSE_COMBINE")
    if raw is None or raw == "":
        return None
    from jepsen_tpu.parallel import coerce_flag
    key = ("JEPSEN_TPU_FUSE_COMBINE", raw)
    if key in _ENV_WARNED:
        return None
    out = coerce_flag(raw, knob="JEPSEN_TPU_FUSE_COMBINE")
    if out is None:
        _ENV_WARNED.add(key)
    return out


def coerce_variant(value, knob: str = "matrix_variant") -> str | None:
    """Tolerant test-map/opts variant knob: None/'' unset; a VARIANTS
    member (or 'auto') passes; garbage warns and reads as unset."""
    if value is None or value == "":
        return None
    if isinstance(value, str):
        v = value.strip().lower()
        if v == "auto":
            return None
        if v in VARIANTS:
            return v
    logger.warning("ignoring malformed %s=%r (want one of auto|%s)",
                   knob, value, "|".join(VARIANTS))
    return None


def _static_tables(S: int, V: int):
    """Host-side static operator tables for (S, V), expanded from the
    SAME receiver/kill constructor the XLA scan path uses
    (jitlin.receiver_kill_tables — one source of truth, so the two
    kernels' bit-identical-verdict guarantee can't drift):

    - Rexp [S, MV, MV]: receiver map R_s block-expanded (R_s[a,b]
      broadcast over the V*V cells of each (a,b) block)
    - Kexp [S, MV, MV]: the closure-then-kill row gather+mask as a
      matrix (A = Kexp_s @ B  ==  B rows gathered at kill_idx_s, masked)
    - U1 [MV, V], U2 [V, MV]: the tiling maps (U1 @ X @ U2 repeats a
      [V, V] X over every block)
    """
    from jepsen_tpu.ops.jitlin import receiver_kill_tables

    M = 1 << S
    MV = M * V
    rows = np.arange(MV)
    ww = rows % V
    receiver, kill_idx, kill_mask = receiver_kill_tables(S, V)

    Rexp = np.stack([receiver[t][rows // V][:, rows // V]
                     for t in range(S)]).astype(np.float32)
    Kexp = np.zeros((S, MV, MV), np.float32)
    for s in range(S):
        Kexp[s, rows, kill_idx[s]] = kill_mask[s]

    U1 = np.zeros((MV, V), np.float32)
    U1[rows, ww] = 1.0
    U2 = np.zeros((V, MV), np.float32)
    U2[ww, rows] = 1.0
    return Rexp, Kexp, U1, U2


def _pretile_mode(S: int, V: int, U: int, variant: str = "f32") -> str:
    """Where the pre-tiled [U, MV, MV] uop table lives: ``vmem``
    (gather + VPU multiply, zero per-step fetch), ``hbm`` (DMA-streamed
    tiles, double-buffered), or ``none`` (in-kernel tiling dots).
    Integer variants store 1 byte/entry — a 4× VMEM budget extension
    over f32 before HBM streaming even starts. Below
    PALLAS_HBM_MIN_MV the DMA tiles cannot be lane-aligned, so a table
    past the VMEM budget keeps the in-kernel tiling dots."""
    MV = (1 << S) * V
    itemsize = 4 if variant == "f32" else 1
    nbytes = U * MV ** 2 * itemsize
    if nbytes <= PALLAS_PRETILE_BYTES:
        return "vmem"
    if nbytes <= PALLAS_PRETILE_HBM_BYTES and MV >= PALLAS_HBM_MIN_MV:
        return "hbm"
    return "none"


@functools.lru_cache(maxsize=32)
def _build(S: int, V: int, T: int, U: int, interpret: bool = False,
           pretile: str = "none", variant: str = "f32"):
    """Compile-cached pallas chunk-product for static shapes.

    Returns fn(pend [T,G,S] f32, ids [T,G,S] i32, mtT [U,V,V] f32,
    slots [T,G] i32, valid [T,G] f32) -> P [G, MV, MV] bf16 — the
    per-chunk composed operator product over its T returns.

    ``pretile``: "vmem" precomputes the [U, MV, MV] tiled uop table
    U1 @ Mt_u^T @ U2 ONCE in XLA (exact: tiling repeats Mt's cells, no
    accumulation) and the kernel's L build becomes a gather + VPU
    multiply; "hbm" keeps that table in HBM and streams the per-step
    tiles through a 2-deep DMA pipeline; "none" keeps the under-tiled
    per-step dots. ``variant`` picks the boolean-product representation
    (module docstring): f32 or int8-MXU.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M = 1 << S
    MV = M * V
    n_sq = 0
    while (1 << n_sq) < S:
        n_sq += 1
    # matrix dtype of the boolean operands per variant; the L build
    # stays f32 (≤ S non-negative addends — exact) and thresholds into
    # the variant dtype, products threshold back into it, and the final
    # P leaves as bf16 for the combine stage in every variant.
    vdtype = jnp.float32 if variant == "f32" else jnp.int8
    tdtype = jnp.float32 if variant == "f32" else jnp.int8
    # The tables stay NUMPY here: _build is lru_cached and its first
    # call may run inside an active jit trace (chunk_product is invoked
    # while the products wrapper traces), where jnp.asarray would yield
    # that trace's tracers — cached into the closure, they leak into
    # every later trace sharing the (S, V, T, U) key and kill the
    # pallas path with UnexpectedTracerError (surfaced by the real-TPU
    # parity tier once the chunk retune multiplied the shape keys).
    # grid_fn stages them per trace instead.
    Rexp, Kexp, U1, U2 = _static_tables(S, V)

    if variant == "int8":
        def bool_mm(x, y):
            # int8 0/1 feeds through the MXU at 4x f32 operand density;
            # int32 accumulation is exact (counts <= MV <= 2^12) and the
            # > 0 threshold saturates back to the 0/1 semiring
            return (jnp.dot(x, y, preferred_element_type=jnp.int32)
                    > 0).astype(jnp.int8)
    else:
        def bool_mm(x, y):
            # f32 0/1 inputs and accumulation: exact (a positive count
            # can't round to zero). Load-bearing f32: this is the
            # probe-verified terminal variant every backend can lower —
            # the integer representations demote HERE, so it must stay.
            return (
                jnp.dot(x, y,  # lint: ignore[threshold-dtype]
                        preferred_element_type=jnp.float32) > 0
            ).astype(jnp.float32)

    def tile_dots(u1, mtT, u2):
        """U1 @ Mt^T @ U2 in-kernel (pretile 'none'): each output cell
        copies ONE Mt cell — exact in either dot dtype."""
        if variant == "f32":
            return jnp.dot(
                jnp.dot(u1, mtT, preferred_element_type=jnp.float32),
                u2, preferred_element_type=jnp.float32)
        inner = jnp.dot(u1, mtT,
                        preferred_element_type=jnp.int32).astype(jnp.int8)
        return jnp.dot(inner, u2, preferred_element_type=jnp.int32)

    def make_step(pend_ref, ids_ref, slot_ref, val_ref, rexp_ref,
                  kexp_ref, fetch_tile):
        """The shared per-return composition, parameterized over how a
        step's per-slot [MV, MV] uop tile is obtained (the three
        L-build modes). Returns (step(t, buf, P), P0)."""
        eye = (lax.broadcasted_iota(jnp.int32, (MV, MV), 0)
               == lax.broadcasted_iota(jnp.int32, (MV, MV), 1)
               ).astype(jnp.float32)

        def _live_step(t, buf, P):
            # L = sum_s pend[t,s] * Rexp_s * tile(Mt_s^T), f32 (<= S
            # non-negative 0/1 addends — exact), thresholded into the
            # variant dtype
            L = jnp.zeros((MV, MV), jnp.float32)
            for s in range(S):
                tile = fetch_tile(t, s, buf)
                L = L + (pend_ref[0, t, s] * rexp_ref[s]
                         * tile.astype(jnp.float32))
            Bm = ((L + eye) > 0).astype(vdtype)
            # closure saturates once the exponent reaches the number of
            # pending ops (each linearization consumes one), so skip
            # squarings a sparse step can't use
            npend = jnp.sum(pend_ref[0, t, :])
            for _i in range(n_sq):
                Bm = lax.cond(npend > (1 << _i),
                              lambda B: bool_mm(B, B),
                              lambda B: B, Bm)   # (I+L)^(2^k) -> closure
            ks = kexp_ref[pl.dslice(slot_ref[0, t, 0], 1), :, :][0]
            A = bool_mm(ks, Bm)                  # closure-then-kill
            return bool_mm(A, P)

        def step(t, buf, P):
            # padding rows (valid=0) compose the identity: skip outright
            return lax.cond(val_ref[0, t, 0] > 0, _live_step,
                            lambda tt, bb, PP: PP, t, buf, P)

        return step, eye.astype(vdtype)

    def kernel_resident(pend_ref, ids_ref, mtT_ref, slot_ref, val_ref,
                        rexp_ref, kexp_ref, u1_ref, u2_ref, out_ref):
        """pretile 'vmem' / 'none': every operand VMEM-resident."""
        def fetch_tile(t, s, _buf):
            idx = ids_ref[0, t, s]
            if pretile == "vmem":
                # mtT_ref holds the pre-tiled [U, MV, MV] table:
                # pure gather + VPU multiply, no per-step dots
                return mtT_ref[pl.dslice(idx, 1), :, :][0]
            mtT = mtT_ref[pl.dslice(idx, 1), :, :][0]       # [V, V]
            return tile_dots(u1_ref[...], mtT, u2_ref[...])

        step, P0 = make_step(pend_ref, ids_ref, slot_ref, val_ref,
                             rexp_ref, kexp_ref, fetch_tile)
        P = lax.fori_loop(0, T, lambda t, P: step(t, jnp.int32(0), P), P0)
        out_ref[0] = P.astype(jnp.bfloat16)

    def kernel_hbm(pend_ref, ids_ref, mtT_ref, slot_ref, val_ref,
                   rexp_ref, kexp_ref, u1_ref, u2_ref, out_ref):
        """pretile 'hbm': the [U, MV, MV] table stays in HBM; step t's
        S tiles were DMA'd into double-buffer slot t%2 while step t-1
        computed, and step t+1's fetches start before t's closure —
        the per-step L build costs a VMEM read instead of either an
        in-kernel dot chain or a VMEM-impossible resident table."""
        def scoped(scratch, sems):
            def dma(t, slot, s):
                return pltpu.make_async_copy(
                    mtT_ref.at[ids_ref[0, t, s]], scratch.at[slot, s],
                    sems.at[slot, s])

            def start(t, slot):
                for s in range(S):
                    dma(t, slot, s).start()

            def fetch_tile(t, s, slot):
                return scratch[slot, s]

            step, P0 = make_step(pend_ref, ids_ref, slot_ref, val_ref,
                                 rexp_ref, kexp_ref, fetch_tile)

            def pipelined(t, P):
                slot = t % 2

                @pl.when(t + 1 < T)
                def _():
                    # prefetch t+1's tiles while t's closure computes
                    start(t + 1, (t + 1) % 2)
                for s in range(S):
                    # near-free once the copy landed during step t-1
                    dma(t, slot, s).wait()
                return step(t, slot, P)

            start(jnp.int32(0), jnp.int32(0))
            P = lax.fori_loop(0, T, pipelined, P0)
            out_ref[0] = P.astype(jnp.bfloat16)

        pl.run_scoped(scoped,
                      scratch=pltpu.VMEM((2, S, MV, MV), tdtype),
                      sems=pltpu.SemaphoreType.DMA((2, S)))

    def grid_fn(pend, ids, mtT, slots, valid):
        # grids arrive [G, T, S] / [G, T, 1]: blocking only on the
        # leading grid axis keeps every block's trailing dims equal to
        # the array's — the Mosaic block-shape rule (trailing two dims
        # divisible by (8, 128) or equal to the array's)
        G = pend.shape[0]
        full = lambda shape: pl.BlockSpec(
            shape, lambda g: (0,) * len(shape), memory_space=pltpu.VMEM)
        if pretile in ("vmem", "hbm"):
            # off-critical-path L-build: tile every uop's Mt^T over the
            # (a, b) blocks once, in XLA (each output cell copies ONE
            # Mt cell — exact, no accumulation); integer variants store
            # the table at 1 byte/entry
            mt_in = jnp.einsum("iv,uvw,wj->uij", jnp.asarray(U1), mtT,
                               jnp.asarray(U2)).astype(tdtype)
            mt_spec = (full((U, MV, MV)) if pretile == "vmem" else
                       pl.BlockSpec(memory_space=pl.ANY))
        else:
            mt_in = mtT.astype(tdtype)
            mt_spec = full((U, V, V))
        kexp_in = jnp.asarray(Kexp).astype(vdtype)
        u_dtype = jnp.float32 if variant == "f32" else jnp.int8
        kern = kernel_hbm if pretile == "hbm" else kernel_resident
        return pl.pallas_call(
            kern,
            grid=(G,),
            in_specs=[
                pl.BlockSpec((1, T, S), lambda g: (g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, T, S), lambda g: (g, 0, 0),
                             memory_space=pltpu.VMEM),
                mt_spec,
                pl.BlockSpec((1, T, 1), lambda g: (g, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, T, 1), lambda g: (g, 0, 0),
                             memory_space=pltpu.VMEM),
                full((S, MV, MV)),
                full((S, MV, MV)),
                full((MV, V)),
                full((V, MV)),
            ],
            out_specs=pl.BlockSpec((1, MV, MV), lambda g: (g, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((G, MV, MV), jnp.bfloat16),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=PALLAS_VMEM_LIMIT),
            interpret=interpret,
        )(pend, ids, mt_in, slots, valid,
          jnp.asarray(Rexp), kexp_in,
          jnp.asarray(U1).astype(u_dtype), jnp.asarray(U2).astype(u_dtype))

    @jax.jit
    def run(pend, ids, mtT, slots, valid):
        """Accepts the scan-path layout (pend/ids [T, G, S], slots/valid
        [T, G]) and relayouts on device to the kernel's [G, T, ...]."""
        return grid_fn(
            jnp.transpose(pend.astype(jnp.float32), (1, 0, 2)),
            jnp.transpose(ids.astype(jnp.int32), (1, 0, 2)),
            mtT.astype(jnp.float32),
            jnp.transpose(slots.astype(jnp.int32), (1, 0))[..., None],
            jnp.transpose(valid.astype(jnp.float32), (1, 0))[..., None])

    return run


# tests set True to exercise the kernels on CPU through the production
# dispatch (pallas interpret mode); never set in production
FORCE_INTERPRET = False


def variant_ok(variant: str, S: int, V: int) -> bool:
    """Shape gates per representation, cheaper than (and checked
    before) the differential probe."""
    MV = (1 << S) * V
    if variant not in VARIANTS:
        return False
    return S <= PALLAS_MAX_SLOTS and MV <= PALLAS_MAX_MV


def chunk_product(S: int, V: int, T: int, U: int,
                  interpret: bool | None = None, variant: str = "f32"):
    """The compiled kernel for these static shapes, or None when out of
    the pallas regime. Lowering/compile failures are reported by the
    first actual call — use ``enabled``/``best_variant`` for an upfront
    check."""
    if not available() or not variant_ok(variant, S, V):
        return None
    mode = _pretile_mode(S, V, U, variant)
    if mode == "hbm" and not hbm_pretile_enabled(S, V, variant):
        mode = "none"           # DMA streaming unproven here: demote
    return _build(S, V, T, U,
                  FORCE_INTERPRET if interpret is None else interpret,
                  mode, variant)


# ---------------------------------------------------------------------------
# Probes: per-(S, V, variant) differential self-tests, sidecar-cached
# ---------------------------------------------------------------------------

_PROBED: dict = {}
_DISABLED: set = set()
_PROBE_SECONDS: list = [0.0]


def probe_seconds() -> float:
    """Cumulative probe wall this process (compile + oracle replay) —
    the cost ``JEPSEN_TPU_PALLAS_PROBE``'s sidecar cache avoids on
    later processes. bench.py surfaces it as ``pallas_probe_seconds``
    so it can't hide inside first-check compile time."""
    return _PROBE_SECONDS[0]


def _note_probe_seconds(dt: float) -> None:
    _PROBE_SECONDS[0] += dt
    from jepsen_tpu import telemetry
    reg = telemetry.get_registry()
    if reg.enabled:
        reg.counter("pallas_probe_seconds_total",
                    "wall seconds spent in pallas self-test probes "
                    "(kernel variants + fused combine)").inc(dt)


def _probe_sidecar_key(kind: str, *parts):
    import jax
    return ("pallas-probe", jax.default_backend(), jax.__version__,
            kind) + tuple(str(p) for p in parts)


def _sidecar_load(key):
    if probe_mode() == "force":
        return None
    try:
        from jepsen_tpu import fs_cache
        data = fs_cache.load_data(key)
    except Exception:  # noqa: BLE001 — an unreadable cache is a miss
        return None
    if isinstance(data, dict) and isinstance(data.get("ok"), bool):
        return data
    return None


def _sidecar_save(key, ok: bool, seconds: float) -> None:
    try:
        from jepsen_tpu import fs_cache
        with fs_cache.lock(key):
            fs_cache.save_data(key, {"ok": ok,
                                     "seconds": round(seconds, 4)})
    except Exception:  # noqa: BLE001 — cache write failure is cosmetic
        logger.debug("pallas probe sidecar write failed", exc_info=True)


def _transient_probe_error(e: BaseException) -> bool:
    """A probe failure that may not reproduce (device busy, co-tenant
    OOM): its verdict must NOT persist in the
    cross-process sidecar — one bad moment would otherwise silently
    pin every future process on this machine to the slow path until an
    operator thinks of JEPSEN_TPU_PALLAS_PROBE=force. Lowering/compile
    failures and oracle mismatches are deterministic per (backend, jax
    version) and do persist."""
    from jepsen_tpu.checker.ladder import is_resource_exhausted
    return is_resource_exhausted(e)


def _probe_verdict(mem_key, side_key, run_probe, describe: str) -> bool:
    """The shared probe protocol for every self-test gate (kernel
    variants, hbm pretile, fused combine): runtime-failure disables
    stick hardest (even under FORCE_INTERPRET), FORCE_INTERPRET skips
    probing (tests drive interpret kernels directly), then the
    in-process memo, the ``skip`` override, the fs_cache sidecar, and
    finally one timed differential probe whose verdict is memoized and
    — unless the failure was transient — persisted."""
    if mem_key in _DISABLED:
        return False
    if FORCE_INTERPRET:
        return True
    if mem_key in _PROBED:
        return _PROBED[mem_key]
    if probe_mode() == "skip":
        # the operator vouches for this backend: shape gates only
        _PROBED[mem_key] = True
        return True
    cached = _sidecar_load(side_key)
    if cached is not None:
        _PROBED[mem_key] = cached["ok"]
        return cached["ok"]
    ok = False
    persist = True
    t0 = time.perf_counter()
    try:
        ok = run_probe()
        if not ok:
            logger.warning("%s MISCOMPILES on this backend (probe "
                           "mismatch); demoting", describe)
    except Exception as e:  # noqa: BLE001 — any lowering failure
        persist = not _transient_probe_error(e)
        logger.warning("%s unavailable%s: %s", describe,
                       "" if persist else " (transient — not cached)", e)
    dt = time.perf_counter() - t0
    _note_probe_seconds(dt)
    if persist:
        _sidecar_save(side_key, ok, dt)
    _PROBED[mem_key] = ok
    return ok


def _oracle_product(S, V, pend, ids, mtT, slots, valid):
    """Numpy replay of the factored chunk product — the probes' and the
    tests' independent reference (variant-independent: every variant
    must reproduce it bit-for-bit)."""
    MV = (1 << S) * V
    T, G = slots.shape
    Rexp, Kexp, U1, U2 = _static_tables(S, V)
    eye = np.eye(MV, dtype=np.float32)
    n_sq = 0
    while (1 << n_sq) < S:
        n_sq += 1
    P = np.broadcast_to(eye, (G, MV, MV)).copy()
    for t in range(T):
        for g in range(G):
            L = np.zeros((MV, MV), np.float32)
            for s in range(S):
                L += (pend[t, g, s]
                      * Rexp[s] * (U1 @ mtT[ids[t, g, s]] @ U2))
            Bm = ((L + eye) > 0).astype(np.float32)
            for _ in range(n_sq):
                Bm = ((Bm @ Bm) > 0).astype(np.float32)
            A = ((Kexp[slots[t, g]] @ Bm) > 0).astype(np.float32)
            if not valid[t, g]:
                A = eye
            P[g] = ((A @ P[g]) > 0).astype(np.float32)
    return P


def _probe_inputs(S, V, T=256, U=16, G=2):
    rng = np.random.default_rng(0)
    pend = (rng.random((T, G, S)) < 0.5).astype(np.float32)
    ids = rng.integers(0, U, (T, G, S)).astype(np.int32)
    mtT = (rng.random((U, V, V)) < 0.3).astype(np.float32)
    slots = rng.integers(0, S, (T, G)).astype(np.int32)
    valid = (rng.random((T, G)) < 0.8).astype(np.float32)
    return pend, ids, mtT, slots, valid


def _run_probe(S: int, V: int, variant: str, pretile: str) -> bool:
    """One end-to-end differential probe: a random run through the REAL
    compiled kernel, checked bit-for-bit against the numpy oracle.

    T=256 puts the probe in the production tiling regime: T is a
    trailing block dimension, so a tiny T (the old 3) compiled a
    differently-padded Mosaic program than the ~1-2k-row chunks
    production dispatches — a shape-dependent miscompile there would
    have slipped past the probe. 256 crosses the sublane tile boundary
    like production T does while keeping the bit-for-bit numpy oracle
    (T*G matrix products) sub-second; residual caveat: the probe's U=16
    uop table is still smaller than production's."""
    T, U = 256, 16
    pend, ids, mtT, slots, valid = _probe_inputs(S, V, T, U)
    fn = _build(S, V, T, U, False, pretile, variant)
    got = np.asarray(fn(pend, ids, mtT, slots, valid), dtype=np.float32)
    ref = _oracle_product(S, V, pend, ids, mtT, slots, valid)
    return np.array_equal(got, ref)


def enabled(S: int, V: int, variant: str = "f32") -> bool:
    """Should the matrix kernel take the pallas path for (S, V) with
    this representation? Gates on the env switch and shape caps, then
    memoizes a small RANDOM end-to-end run checked bit-for-bit against
    the numpy oracle — so a backend that fails to lower (CPU) OR
    miscompiles the kernel disables itself and the next variant (or the
    XLA scan path) takes over. Verdicts persist per
    (backend, jax version, S, V, variant) in the fs_cache sidecar;
    ``JEPSEN_TPU_PALLAS_PROBE`` overrides (module docstring). A
    disable() (runtime failure) sticks even under FORCE_INTERPRET —
    otherwise a failing interpret-mode kernel would retrace and fail
    on every dispatch; it is tracked apart from probe results, so a
    CPU probe failure (no pallas backend) can't poison forced
    interpret-mode runs, which don't need one."""
    if not available() or not variant_ok(variant, S, V):
        return False
    # probe the same pretile variant production dispatches at this U —
    # the kernels differ in their L-build data path
    return _probe_verdict(
        (S, V, variant), _probe_sidecar_key("kernel", S, V, variant),
        lambda: _run_probe(S, V, variant, _pretile_mode(S, V, 16, variant)),
        f"pallas matrix kernel (S={S} V={V} variant={variant})")


def hbm_pretile_enabled(S: int, V: int, variant: str = "f32") -> bool:
    """Is the DMA-streamed (HBM-resident) pre-tiled L-build proven on
    this backend for (S, V, variant)? Same probe/sidecar protocol as
    ``enabled`` but exercising the ``hbm`` kernel explicitly (the
    regular probe's U=16 table always fits VMEM, so it never walks the
    DMA path). A miss demotes to the in-kernel tiling dots, never
    fails."""
    if not available() or not variant_ok(variant, S, V):
        return False
    return _probe_verdict(
        (S, V, variant, "hbm"),
        _probe_sidecar_key("kernel-hbm", S, V, variant),
        lambda: _run_probe(S, V, variant, "hbm"),
        f"pallas hbm-streamed L-build (S={S} V={V} variant={variant})")


def best_variant(S: int, V: int, force: str | None = None) -> str | None:
    """The densest representation that passes its probe for (S, V), or
    None when no pallas path is viable (XLA scan takes over). ``force``
    (or JEPSEN_TPU_MATRIX_VARIANT) pins the first candidate; a pinned
    variant that fails its gates or probe DEMOTES down the auto order
    — PR-3 semantics, never an error."""
    pref = force if force in VARIANTS else None
    if pref is None:
        env = matrix_variant()
        pref = env if env in VARIANTS else None
    order = ((pref,) + tuple(v for v in VARIANTS if v != pref)
             if pref else VARIANTS)
    for v in order:
        if enabled(S, V, v):
            return v
    return None


def disable(S: int, V: int, variant: str = "f32") -> None:
    """Permanently (for this process) route (S, V, variant) away from
    the pallas path — called by the dispatcher after a runtime failure.
    Unlike a probe miss, this also sticks under FORCE_INTERPRET."""
    _DISABLED.add((S, V, variant))


# ---------------------------------------------------------------------------
# Fused streaming combine: the chunk-product reduction as ONE kernel
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _build_combine(B: int, C: int, MV: int, interpret: bool = False):
    """One pallas program per key streams its C time-ordered chunk
    products [MV, MV] through a VMEM-resident running product:

        total_b = P[b, C-1] @ ... @ P[b, 0] @ tot0[b]

    The tree combine (jitlin._kernel_math.make_combine) round-trips
    ceil(log2 C) levels of [B, C_l, MV, MV] intermediates through HBM;
    here each product is read from HBM exactly once (the pallas grid
    pipeline double-buffers the next chunk's HBM->VMEM copy under the
    current dot) and only the [B, MV, MV] total is written back.
    Products run int8 through the MXU with int32 accumulation and a
    saturating > 0 threshold; boolean matrix products are exact under any
    association and any exact dtype, so the result is bit-identical to
    the tree."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(p_ref, t0_ref, out_ref, acc_ref):
        c = pl.program_id(1)

        @pl.when(c == 0)
        def _():
            acc_ref[...] = t0_ref[0].astype(jnp.int8)
        prod = jnp.dot(p_ref[0, 0].astype(jnp.int8), acc_ref[...],
                       preferred_element_type=jnp.int32)
        out = (prod > 0).astype(jnp.int8)
        acc_ref[...] = out

        @pl.when(c == C - 1)
        def _():
            out_ref[0] = out.astype(jnp.bfloat16)

    @jax.jit
    def run(P, tot0):
        """P [B, C, MV, MV] 0/1 (any float dtype), tot0 [B, MV, MV] ->
        total [B, MV, MV] bf16."""
        return pl.pallas_call(
            kernel,
            grid=(B, C),
            in_specs=[
                pl.BlockSpec((1, 1, MV, MV), lambda b, c: (b, c, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, MV, MV), lambda b, c: (b, 0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, MV, MV), lambda b, c: (b, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, MV, MV), jnp.bfloat16),
            scratch_shapes=[pltpu.VMEM((MV, MV), jnp.int8)],
            interpret=interpret,
        )(P, tot0)

    return run


def combine_product(B: int, C: int, MV: int,
                    interpret: bool | None = None):
    """The fused streaming combine for these static shapes (see
    ``_build_combine``), or None when out of regime."""
    if not available() or MV > PALLAS_MAX_MV:
        return None
    return _build_combine(
        B, C, MV, FORCE_INTERPRET if interpret is None else interpret)


def _combine_oracle(P, tot0):
    B, C, MV, _ = P.shape
    out = np.zeros((B, MV, MV), np.float32)
    for b in range(B):
        acc = np.asarray(tot0[b], np.float32)
        for c in range(C):
            acc = ((np.asarray(P[b, c], np.float32) @ acc)
                   > 0).astype(np.float32)
        out[b] = acc
    return out


def _run_combine_probe(MV: int) -> bool:
    import jax.numpy as jnp
    B, C = 2, 5
    rng = np.random.default_rng(1)
    P = (rng.random((B, C, MV, MV)) < 0.2).astype(np.float32)
    tot0 = np.broadcast_to(np.eye(MV, dtype=np.float32),
                           (B, MV, MV)).copy()
    fn = _build_combine(B, C, MV, False)
    got = np.asarray(fn(jnp.asarray(P, jnp.bfloat16),
                        jnp.asarray(tot0, jnp.bfloat16)),
                     dtype=np.float32)
    return np.array_equal(got, _combine_oracle(P, tot0))


def combine_enabled(MV: int) -> bool:
    """Should chunk combines run through the fused streaming kernel at
    this operator size? Same probe/sidecar/override protocol as
    ``enabled``; JEPSEN_TPU_FUSE_COMBINE=0 vetoes, =1 only skips the
    probe when it already passed elsewhere (a forced-on fused combine
    still never replaces a probe miss — bit-identity outranks the
    toggle)."""
    forced = fuse_combine_mode()
    if forced is False or not available() or MV > PALLAS_MAX_MV:
        return False
    return _probe_verdict(
        ("combine", MV), _probe_sidecar_key("combine", MV),
        lambda: _run_combine_probe(MV),
        f"fused combine (MV={MV})")


def disable_combine(MV: int) -> None:
    """Route combines at this MV back to the tree after a runtime
    failure (sticks under FORCE_INTERPRET, like ``disable``)."""
    _DISABLED.add(("combine", MV))
