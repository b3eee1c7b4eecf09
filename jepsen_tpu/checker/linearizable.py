"""Linearizability checker with an accelerator switch.

Reference surface: jepsen.checker/linearizable (checker.clj:185-216), which
dispatches on :algorithm to knossos's linear/wgl/competition searches. Here
the dispatch axes are:

* ``algorithm``: "wgl" (object-model DFS oracle), "jitlin" (int-encoded
  breadth-first search — the TPU kernel's CPU twin), or "auto".
* ``accelerator``: "cpu", "tpu" (any JAX device), or "auto" — the
  :accelerator option called for by BASELINE.json's north star. "auto" uses
  the device kernel for histories big enough to amortize compilation and
  falls back to CPU when the device frontier overflows (mirroring the
  reference's competition mode, checker.clj:199-203). "tpu" pins the
  device: a device rung that fails raises
  :class:`~jepsen_tpu.checker.ladder.DeviceFailed` instead of settling on
  the CPU.

Device verdicts are labelled with the platform that ran them and the
kernel (``jitlin-tpu-matrix`` on a TPU, ``jitlin-cpu-matrix`` under
XLA's CPU backend; ``-frontier`` for the event-scan kernel), never with
the platform that was asked for.

Failure output is truncated (the reference truncates :final-paths/:configs
to 10 because "Writing these can take *hours*", checker.clj:213-216).
"""
from __future__ import annotations

import logging
import time
from typing import Any

from jepsen_tpu import telemetry, trace
from jepsen_tpu.checker import Checker
from jepsen_tpu.checker.linear_cpu import (
    LinearResult, cas_register_step_py, check_stream, wgl,
)
from jepsen_tpu.checker.linear_encode import encode_register_ops
from jepsen_tpu.models import CASRegister, Model

logger = logging.getLogger("jepsen.checker.linearizable")

# Histories below this many events run on CPU under accelerator="auto":
# kernel launch + compile isn't worth it.
AUTO_TPU_THRESHOLD = 512

# Failure reports re-run the exact CPU search to recover the dying
# frontier; skip that recovery for histories longer than this.
MAX_REPORT_EVENTS = 200_000

def device_algorithm(suffix: str) -> str:
    """The result label of a device-kernel verdict: ``jitlin-<platform>``
    plus the kernel's suffix, naming the JAX platform the kernel ran on
    (the suffix keeps ``jitlin-cpu-*`` apart from the host search's
    ``jitlin-cpu``)."""
    import jax
    return f"jitlin-{jax.default_backend()}{suffix}"


# Backends that have completed at least one dispatch this process: the
# first call's wall time includes JIT compilation, later calls don't —
# exporting both makes the compile/execute split readable from metrics.
_FIRST_CHECK_SEEN: set = set()


class LinearizableChecker(Checker):
    def __init__(
        self,
        model: Model | None = None,
        algorithm: str = "auto",
        accelerator: str = "auto",
        capacity: int = 256,
        multi_shape: tuple = (3, 5),
        watchdog_s: float | None = None,
        breaker_threshold: int | None = None,
    ):
        self.model = model if model is not None else CASRegister()
        self.algorithm = algorithm
        self.accelerator = accelerator
        self.capacity = capacity
        # (n_keys, n_values) for the MultiRegister int encoding — the
        # multi-key-acid workload's shape (multi_key_acid.clj key-range/
        # rand-val)
        self.multi_shape = multi_shape
        # degradation-ladder tunables (doc/robustness.md); None = the
        # ladder module's env-tunable defaults
        self.watchdog_s = watchdog_s
        self.breaker_threshold = breaker_threshold
        self._kernel = None
        self._ladder = None

    def _encoding(self, history, ir=None):
        """(stream, step_py, spec) when the model has an int encoding for
        the device/stream paths, else None (object-model wgl search).
        With an ``ir`` (the run's shared history IR) the stream is the
        memoized view — a second checker over the same history pays
        nothing (bit-identical either way; tests/test_history_ir.py)."""
        from jepsen_tpu.models import MultiRegister, multi_register_spec

        if isinstance(self.model, CASRegister):
            from jepsen_tpu.history import Intern
            from jepsen_tpu.models import cas_register_spec
            if ir is not None:
                from jepsen_tpu.history_ir import views
                stream = views.register_stream(ir,
                                               init_value=self.model.value)
            else:
                intern = Intern()
                # a non-None initial register value interns FIRST so its
                # id is the kernel's init state (single-key-acid at 0)
                if self.model.value is not None:
                    intern.id(self.model.value)
                stream = encode_register_ops(history, intern=intern)
            init_id = (0 if self.model.value is None
                       else stream.intern.id(self.model.value))
            return (stream, cas_register_step_py,
                    cas_register_spec(init_id))
        if isinstance(self.model, MultiRegister):
            from jepsen_tpu.checker.linear_cpu import multi_register_step_py
            from jepsen_tpu.checker.linear_encode import (
                encode_multi_register_ops)
            k, v = self.multi_shape
            if ir is not None:
                from jepsen_tpu.history_ir import views
                stream = views.multi_register_stream(ir, k, v)
                if stream is None:
                    return None  # outside the packed encoding: wgl
            else:
                try:
                    stream = encode_multi_register_ops(history, k, v)
                except ValueError:
                    return None  # outside the packed encoding: wgl
            return (stream, multi_register_step_py(k, v),
                    multi_register_spec(k, v))
        return None

    def _tpu_kernel(self, spec):
        if self._kernel is None:
            from jepsen_tpu.ops.jitlin import JitLinKernel
            self._kernel = JitLinKernel(step_ids=spec.step_ids,
                                        init_state=spec.init_state)
        return self._kernel

    def check(self, test, history, opts):
        with trace.phase(trace.CHECK_SPAN, ops=len(history), keys=1):
            return self._check(test, history, opts)

    def _check(self, test, history, opts):
        algorithm = opts.get("algorithm", self.algorithm)
        accelerator = opts.get("accelerator", self.accelerator)
        # multi-device sharding knobs (doc/performance.md "Multi-device
        # sharding"): checker_sharded force-enables/disables the sharded
        # rung (None = env default + cost model), mesh_devices caps the
        # mesh width
        from jepsen_tpu import parallel as par
        from jepsen_tpu.checker import explain as explain_mod
        sharded, mesh_devices = par.sharding_knobs(test, opts)
        explain_on = explain_mod.enabled(test, opts)
        # matrix-kernel routing knobs (doc/performance.md "Packed
        # boolean kernels"): matrix_variant pins the representation
        # (probe-gated, demotes down the auto order), combine_fused
        # pins the combine path; both tolerantly coerced, opts over test
        from jepsen_tpu.ops import pallas_matrix as pm
        tmap = test if isinstance(test, dict) else {}
        matrix_variant = pm.coerce_variant(
            opts.get("matrix_variant", tmap.get("matrix_variant")))
        combine_fused = par.coerce_flag(
            opts.get("combine_fused", tmap.get("combine_fused")),
            knob="combine_fused")

        t0 = time.perf_counter()
        if algorithm == "wgl":
            res = wgl(history, self.model)
            self._record_metrics(res, time.perf_counter() - t0,
                                 len(history), None)
            return self._finish(res, history, test)

        # jitlin path: encode once — through the run's shared history
        # IR when one is attachable (history_ir.of memoizes on the test
        # map, so composed checkers share a single encode)
        from jepsen_tpu import history_ir
        with trace.phase("encode.ir", events=len(history)):
            ir = history_ir.of(test, history)
        with trace.phase("encode.stream", keys=1) as span:
            enc = self._encoding(history, ir=ir)
            if enc is not None:
                span.set(events=len(enc[0]))
        if enc is None:
            res = wgl(history, self.model)
            self._record_metrics(res, time.perf_counter() - t0,
                                 len(history), None)
            return self._finish(res, history, test)
        stream, step_py, spec = enc
        extras: dict = {}
        # durable checker checkpoints (doc/robustness.md "Resumable
        # checks and the elastic mesh"): a run-dir-backed check persists
        # its tiny carry to check.ckpt and auto-resumes a valid one
        ckpt = self._ckpt_store(test)
        min_devices = par.coerce_devices(
            opts.get("mesh_min_devices", tmap.get("mesh_min_devices")),
            knob="mesh_min_devices")
        res = self._search_stream(stream, step_py, spec, algorithm,
                                  accelerator, history=history,
                                  sharded=sharded,
                                  mesh_devices=mesh_devices,
                                  explain=explain_on, extras=extras,
                                  matrix_variant=matrix_variant,
                                  combine_fused=combine_fused,
                                  ckpt=ckpt, mesh_min_devices=min_devices)
        if ckpt is not None:
            # the check settled: a surviving check.ckpt would mark an
            # interrupted check and mislead the next analyze
            ckpt.clear()
        self._record_metrics(res, time.perf_counter() - t0, len(stream),
                             stream)
        return self._finish(res, history, test, stream, step_py=step_py,
                            init_state=spec.init_state,
                            step_ids=spec.step_ids,
                            explain_on=explain_on,
                            explain_loc=extras.get("loc"), opts=opts)

    def _ckpt_store(self, test):
        """The run's durable check.ckpt store, or None when the test
        map has no store coordinates (bare re-checks, unit tests) or
        checkpointing AND resumption are both off."""
        if not isinstance(test, dict) or not test.get("start_time"):
            return None
        from jepsen_tpu.checker import checkpoint as ckpt_mod
        interval = ckpt_mod.ckpt_interval(test)
        resume = ckpt_mod.resume_enabled(test)
        if interval is None and not resume:
            return None
        try:
            from jepsen_tpu import store
            path = store.path(test, ckpt_mod.CKPT_NAME)
        except Exception:  # noqa: BLE001 — no store dir: no checkpoints
            return None
        return ckpt_mod.CheckpointStore(path, interval_s=interval,
                                        resume=resume)

    def _search_stream(self, stream, step_py, spec, algorithm,
                       accelerator, history=None, sharded=None,
                       mesh_devices=None, explain=True,
                       extras=None, matrix_variant=None,
                       combine_fused=None, ckpt=None,
                       mesh_min_devices=None) -> LinearResult:
        """The full encoded-stream dispatch, shared by check() and the
        stored-column re-check lane (module check_stored), routed
        through the :class:`~jepsen_tpu.checker.ladder.BackendLadder`:
        host rungs (native C++ first, exact Python stream search) below
        the device threshold, device rungs (mesh-sharded matrix,
        transfer-matrix screen, frontier kernel) above it, with the
        exact CPU twin as the terminal rung every demotion lands on."""
        device_regime = not (accelerator == "cpu" or (
            accelerator == "auto" and len(stream) < AUTO_TPU_THRESHOLD))
        ctx = {
            "stream": stream,
            "step_py": step_py,
            "spec": spec,
            "history": history,
            "device_regime": device_regime,
            # accelerator="tpu": a failed device rung raises instead of
            # settling on a host rung (checker/ladder.py DeviceFailed)
            "strict_device": accelerator == "tpu",
            "capacity": self.capacity,
            # sharded-rung routing (doc/performance.md): True forces,
            # False disables, None = env default + cost-model gate
            "sharded": sharded,
            "mesh_devices": mesh_devices,
            # anomaly forensics (doc/observability.md): invalid matrix
            # verdicts localize on device instead of demoting to a full
            # re-scan just to find the op
            "explain": explain,
            # matrix-kernel routing (doc/performance.md "Packed boolean
            # kernels"): pinned representation / combine path, or None
            # for the probe order
            "matrix_variant": matrix_variant,
            "combine_fused": combine_fused,
            # durable checkpoints + the elastic shrink floor
            # (doc/robustness.md "Resumable checks and the elastic
            # mesh"): the rungs persist/resume their carries through
            # _ckpt, and the sharded rung's shrink ladder bottoms out
            # at mesh_min_devices
            "_ckpt": ckpt,
            "mesh_min_devices": mesh_min_devices,
            # the encoded-stream search applies for jitlin/auto, and for
            # the stored-column lane (no op history to wgl over)
            "stream_path": (algorithm in ("jitlin", "auto")
                            or history is None),
        }
        res, _backend = self._get_ladder().run(ctx)
        if extras is not None and "_explain_loc" in ctx:
            # the rung's device localization rides out so _finish can
            # reuse it for the witness shrink (no second bisection)
            extras["loc"] = ctx["_explain_loc"]
        phases = ctx.pop("_matrix_phase", None)
        if phases:
            # the matrix rung may have run on a watchdog thread; make
            # its phase split visible to this thread's readers
            # (_record_metrics, bench)
            from jepsen_tpu.ops.jitlin import publish_phase_seconds
            publish_phase_seconds(phases)
        return res

    def _get_ladder(self):
        """The degradation ladder, built once per checker: sharded-matrix
        (mesh) -> pallas-matrix -> jitlin device frontier -> native C++
        -> exact CPU. Demotion,
        watchdog, adaptive-shrink retry, and circuit-breaker policy all
        live in checker/ladder.py; the rungs here only encode *what*
        each backend computes and *when* it is in regime."""
        if self._ladder is not None:
            return self._ladder
        from jepsen_tpu.checker.ladder import (
            Backend, BackendLadder, is_device_loss, is_resource_exhausted,
        )

        is_cas = isinstance(self.model, CASRegister)

        def carry_sink(ctx):
            """A gen-guarded carry publisher for the segmented matrix
            chain: carries only land while the publishing attempt still
            owns the ladder (a watchdog-abandoned zombie's late writes
            are dropped — the demoted rung already resumed)."""
            gen = ctx.get("_gen", 0)

            def sink(carry):
                if ctx.get("_gen", 0) == gen:
                    ctx["_carry"] = carry
            return sink

        def matrix_rung_check(ctx, mesh):
            """The matrix screen one rung runs: one-shot for short
            streams, the crash-resumable segmented chain when a
            durable checkpoint store is attached, a demotion carry is
            waiting, or the stream is longer than one segment —
            bit-identical either way (boolean operator products are
            exact under any association)."""
            from jepsen_tpu.ops.jitlin import (
                MATRIX_SEGMENT_EVENTS, matrix_check, matrix_check_segmented,
            )
            stream, spec = ctx["stream"], ctx["spec"]
            kw = dict(step_ids=spec.step_ids, init_state=spec.init_state,
                      num_states=len(stream.intern), mesh=mesh,
                      variant=ctx.get("matrix_variant"),
                      combine_fused=ctx.get("combine_fused"))
            carry = ctx.get("_carry")
            if carry is not None and carry.get("rep") != "matrix":
                carry = None
            ckpt = ctx.get("_ckpt")
            # a stream within one segment can never write a mid-chain
            # checkpoint, so it only takes the chain when a resume is
            # actually pending (a surviving check.ckpt or a demotion
            # carry) — short checks keep the one-shot dispatch
            resume_pending = carry is not None or (
                ckpt is not None and ckpt.resume and ckpt.path.exists())
            if not resume_pending and len(stream) <= MATRIX_SEGMENT_EVENTS:
                return matrix_check(stream, force=False, **kw)
            return matrix_check_segmented(stream, ckpt=ctx.get("_ckpt"),
                                          carry=carry,
                                          carry_sink=carry_sink(ctx),
                                          **kw)

        def matrix_eligible(ctx):
            # long histories over small value domains: the block-composed
            # transfer-matrix kernel settles the verdict with far less
            # sequential depth (MXU boolean matmuls over chunks); the
            # event scan remains the diagnostics path (died-at, peak)
            if not ctx["device_regime"]:
                return False
            import numpy as np
            from jepsen_tpu.ops.jitlin import matrix_ok
            stream = ctx["stream"]
            n_returns = int((np.asarray(stream.kind) == 1).sum())
            return matrix_ok(stream.n_slots, len(stream.intern), n_returns)

        def matrix_settle(ctx, m, algo):
            """A COMPLETED matrix screen verdict -> LinearResult, or
            None to demote. An exact True settles valid. An exact False
            localizes the first anomaly ON DEVICE (the forensics
            bisection over the composable chunk products,
            jitlin.matrix_localize — bit-identical to the CPU
            frontier's rejection) and settles INVALID with the precise
            event, instead of demoting to a full event re-scan just to
            find the op (doc/observability.md "Anomaly forensics").
            Inexact (oob) proves nothing either way and always
            demotes."""
            if m is None:
                return None
            if m[2]:
                return None
            if m[0]:
                return LinearResult(
                    valid=True, failed_event=-1, failed_op_index=-1,
                    configs_max=0, algorithm=algo)
            if not ctx.get("explain", True):
                return None  # explain off: the old demote-to-scan path
            from jepsen_tpu.ops.jitlin import matrix_localize
            stream, spec = ctx["stream"], ctx["spec"]
            try:
                loc = matrix_localize(stream, step_ids=spec.step_ids,
                                      init_state=spec.init_state,
                                      num_states=len(stream.intern))
            except Exception:  # noqa: BLE001 — localization never fails a check
                logger.exception("matrix localization failed; demoting")
                loc = None
            if loc is None:
                return None
            ctx["_explain_loc"] = loc
            return LinearResult(
                valid=False, failed_event=loc.failed_event,
                failed_op_index=loc.failed_op_index, configs_max=0,
                algorithm=algo)

        def matrix_fn(ctx):
            from jepsen_tpu.ops.jitlin import last_phase_seconds
            if ctx.get("_matrix_screened"):
                # the sharded rung already ran the bit-identical screen
                # to completion and it didn't settle; don't pay for it
                # twice (a sharded CRASH leaves the flag unset, so the
                # demotion path still gets its single-device screen —
                # resuming from the sharded rung's threaded carry)
                return None
            m = matrix_rung_check(ctx, mesh=None)
            # capture the phase split on THIS (possibly watchdog) thread;
            # _search_stream re-publishes it on the checker's thread
            ctx["_matrix_phase"] = last_phase_seconds()
            return matrix_settle(ctx, m, device_algorithm("-matrix"))

        def matrix_shrink(ctx):
            # halve the chunk element budget: _matrix_plan sizes the
            # per-step [G, MV, MV] working set under it, so halving it
            # halves the device-resident intermediates. The halved value
            # sticks (adaptive): the device told us its real capacity.
            from jepsen_tpu.ops import jitlin
            if jitlin.MATRIX_MAX_ELEMS <= (1 << 20):
                return False
            jitlin.MATRIX_MAX_ELEMS //= 2
            return True

        def sharded_eligible(ctx):
            # the mesh-sharded matrix rung: same regime gate as the
            # single-device matrix screen, plus ≥2 devices and the
            # per-device-count cost model (small histories must not pay
            # mesh overhead). checker_sharded=True skips the cost gate
            # (the operator asked); False disables the rung outright.
            if not matrix_eligible(ctx):
                return False
            from jepsen_tpu import parallel
            flag = ctx.get("sharded")
            if flag is False:
                return False
            if flag is not True and not parallel.sharded_enabled():
                return False
            if flag is True:
                mesh = parallel.auto_mesh(ctx.get("mesh_devices"))
            else:
                mesh = parallel.sharded_mesh_for(len(ctx["stream"]),
                                                 ctx.get("mesh_devices"))
            if mesh is None:
                return False
            ctx["_sharded_mesh"] = mesh
            return True

        def sharded_fn(ctx):
            # the multi-device twin of matrix_fn: chunk axis sharded
            # over the mesh, carries tree-combined device-side. A
            # collective error / device loss raises — sharded_shrink
            # below rebuilds the mesh over the survivors and the retry
            # RESUMES from the threaded carry (elastic mesh,
            # doc/robustness.md "Resumable checks and the elastic
            # mesh"); only when the shrink ladder bottoms out does the
            # ladder demote to the single-device rungs — which also
            # resume from the carry, so sharding unavailability
            # degrades, never fails and never restarts.
            from jepsen_tpu.ops.jitlin import last_phase_seconds
            m = matrix_rung_check(ctx, mesh=ctx["_sharded_mesh"])
            ctx["_matrix_phase"] = last_phase_seconds()
            res = matrix_settle(ctx, m,
                                device_algorithm("-matrix-sharded"))
            if res is not None:
                return res
            # the screen COMPLETED but didn't settle (inexact, or
            # invalid with localization declined/off): the
            # single-device screen is bit-identical, so matrix_fn
            # re-running it would pay a full matrix dispatch to learn
            # the same thing — flag it to decline instead
            ctx["_matrix_screened"] = True
            return None

        def sharded_shrink(ctx):
            # the elastic mesh: rebuild over the surviving device set
            # and let the retry resume from the carry. A genuine OOM
            # (RESOURCE_EXHAUSTED) first gets the classic element-budget
            # halving — shrinking the mesh INCREASES per-device load,
            # and an OOM message that happens to name a device must
            # never poison a healthy device's health record — and only
            # shrinks the mesh (unattributed) once the budget bottoms
            # out. Device-loss/collective failures shrink with casualty
            # attribution from the error text.
            from jepsen_tpu import parallel
            mesh = ctx.get("_sharded_mesh")
            exc = ctx.get("_shrink_error")
            oom = exc is not None and is_resource_exhausted(exc)
            if oom and matrix_shrink(ctx):
                return True
            new = parallel.shrink_mesh(
                mesh, exc=None if oom else exc,
                min_devices=ctx.get("mesh_min_devices")) \
                if mesh is not None else None
            if new is not None:
                ctx["_sharded_mesh"] = new
                return True
            return False

        def frontier_fn(ctx):
            from jepsen_tpu.ops.jitlin import verdict
            stream, spec = ctx["stream"], ctx["spec"]
            alive, died, overflow, peak = self._tpu_kernel(spec).check(
                stream, capacity=ctx["capacity"])
            valid = verdict(alive, overflow)
            if valid == "unknown":
                # frontier overflowed K and died: the exact CPU twin
                # settles it (terminal rung)
                return None
            return LinearResult(
                valid=valid,
                failed_event=died,
                failed_op_index=(int(stream.op_index[died])
                                 if died >= 0 else -1),
                configs_max=peak,
                algorithm=device_algorithm("-frontier"),
            )

        def frontier_shrink(ctx):
            # halve the frontier capacity K: less device memory per
            # step. A verdict the smaller frontier can't settle becomes
            # unknown -> CPU demotion — never a wrong answer.
            if ctx["capacity"] <= 16:
                return False
            ctx["capacity"] = max(16, ctx["capacity"] // 2)
            return True

        def native_eligible(ctx):
            # native C++ search (same algorithm, ~100x the Python loop);
            # host regime only, and only the configuration it hardcodes
            # (CAS register, init id 0)
            return (not ctx["device_regime"] and ctx["stream_path"]
                    and is_cas and ctx["spec"].init_state == 0)

        def native_fn(ctx):
            from jepsen_tpu.native import check_stream_native
            res = check_stream_native(ctx["stream"])
            if res is not None and res.valid == "unknown":
                return None  # capacity blown (>63 slots live): Python
            return res  # None when unbuilt -> decline

        def cpu_fn(ctx):
            from_device = any(n in ("sharded-matrix", "pallas-matrix",
                                    "jitlin-device")
                              for n in ctx.get("_attempted", ()))
            if ctx["stream_path"] or from_device:
                step = ctx["step_py"]
                init = ctx["spec"].init_state
                # a demoted matrix rung's threaded carry seeds the exact
                # frontier at its last quiescent cut — a watchdog
                # timeout or mesh collapse keeps its completed segments
                # instead of restarting (doc/robustness.md)
                session = None
                carry = ctx.get("_carry")
                if carry is not None and carry.get("rep") == "matrix" \
                        and carry.get("init_state") == init:
                    from jepsen_tpu.checker import checkpoint as ckpt_mod
                    session = ckpt_mod.frontier_from_matrix_carry(
                        carry, step, init)
                    if session is not None:
                        ckpt_mod.count_resume("carry")
                        logger.info(
                            "exact CPU frontier resuming from the "
                            "demoted matrix rung's carry at event %d",
                            session.events_absorbed)
                ckpt = ctx.get("_ckpt")
                if ckpt is not None:
                    from jepsen_tpu.checker import checkpoint as ckpt_mod
                    res = ckpt_mod.checkpointed_check_stream(
                        ctx["stream"], step, init, ckpt,
                        session=session)
                elif session is not None:
                    res = session.absorb(ctx["stream"],
                                         start=session.events_absorbed)
                else:
                    res = check_stream(ctx["stream"], step=step,
                                       init_state=init)
                if from_device:
                    res.algorithm = "jitlin-cpu(fallback)"
                return res
            return wgl(ctx["history"], self.model)

        kw = {}
        if self.watchdog_s is not None:
            kw["watchdog_s"] = self.watchdog_s
        if self.breaker_threshold is not None:
            kw["breaker_threshold"] = self.breaker_threshold
        self._ladder = BackendLadder([
            # the sharded rung is ELASTIC: device-loss/collective
            # failures shrink the mesh over the survivors (up to
            # max_shrinks steps, e.g. 8→4→2) and resume from the
            # carry, demoting to single-device only when the shrink
            # ladder bottoms out at mesh_min_devices
            Backend("sharded-matrix", sharded_fn, eligible=sharded_eligible,
                    shrink=sharded_shrink, device=True, max_shrinks=6,
                    retryable=is_device_loss),
            Backend("pallas-matrix", matrix_fn, eligible=matrix_eligible,
                    shrink=matrix_shrink, device=True),
            Backend("jitlin-device", frontier_fn,
                    eligible=lambda ctx: ctx["device_regime"],
                    shrink=frontier_shrink, device=True),
            Backend("native-c", native_fn, eligible=native_eligible),
            Backend("cpu", cpu_fn),
        ], **kw)
        return self._ladder

    def _record_metrics(self, res: LinearResult, dt: float, n_events: int,
                        stream) -> None:
        """Runtime telemetry for one check dispatch: which backend won,
        first-call (JIT compile included) vs steady-state latency,
        events/sec, device-memory high-water, and — on the matrix path —
        the matrix dispatch's phase split."""
        reg = telemetry.get_registry()
        if not reg.enabled:
            return
        try:
            backend = res.algorithm or "unknown"
            reg.counter("checker_backend_total",
                        "checks settled, by winning backend",
                        labels=("backend",)).inc(backend=backend)
            reg.histogram("checker_check_seconds",
                          "check dispatch wall time", labels=("backend",)
                          ).observe(dt, backend=backend)
            first = reg.gauge(
                "checker_first_check_seconds",
                "first dispatch per backend (includes JIT compile)",
                labels=("backend",))
            if backend not in _FIRST_CHECK_SEEN:
                _FIRST_CHECK_SEEN.add(backend)
                first.set(dt, backend=backend)
            else:
                reg.gauge("checker_steady_check_seconds",
                          "most recent non-first dispatch (compile "
                          "amortized; first minus steady ~= compile cost)",
                          labels=("backend",)).set(dt, backend=backend)
            if dt > 0:
                reg.gauge("checker_events_per_sec",
                          "events verified per second, last check",
                          labels=("backend",)
                          ).set(n_events / dt, backend=backend)
            if backend.startswith("jitlin-tpu"):
                peak_bytes = telemetry.device_memory_peak_bytes()
                if peak_bytes is not None:
                    reg.gauge("checker_device_memory_peak_bytes",
                              "device allocator high-water"
                              ).set_max(peak_bytes)
            if "-matrix" in backend and stream is not None and dt > 0:
                # per-phase attribution (doc/performance.md): where the
                # matrix dispatch wall went — host encode
                # (prepass/grids) vs the async call vs device compute +
                # readback
                from jepsen_tpu.ops.jitlin import last_phase_seconds
                phase_g = reg.gauge(
                    "checker_matrix_phase_seconds",
                    "host/device phase split of the last matrix "
                    "dispatch", labels=("phase",))
                split = last_phase_seconds()
                for ph, secs in split.items():
                    # the split also carries the routing labels
                    # (variant / combine) — strings, counted below
                    if isinstance(secs, (int, float)):
                        phase_g.set(secs, phase=ph)
                if "variant" in split:
                    reg.counter(
                        "checker_matrix_variant_total",
                        "matrix dispatches by kernel representation "
                        "and combine path",
                        labels=("variant", "combine")).inc(
                        variant=str(split["variant"]),
                        combine=str(split.get("combine", "tree")))
        except Exception:  # noqa: BLE001 — telemetry never fails a check
            logger.exception("checker telemetry recording failed")

    def _finish(self, res: LinearResult, history, test=None,
                stream=None, step_py=None, init_state: int = 0,
                step_ids=None, explain_on: bool = True, explain_loc=None,
                opts=None) -> dict:
        out: dict[str, Any] = {
            "valid?": res.valid,
            "algorithm": res.algorithm,
            "configs-max": res.configs_max,
        }
        if res.valid is False and res.failed_op_index >= 0:
            i = res.failed_op_index
            lo = max(0, i - 5)
            out["failed-op"] = history[i] if i < len(history) else None
            out["context"] = history[lo : i + 1][-10:]
            self._trace_anomaly(history, i, res)
            # device verdicts carry no frontier detail: one exact CPU pass
            # recovers the dying configurations for the report (the
            # knossos :configs surface). Gated by length — the history was
            # routed to the device because host search may be slow, and a
            # report must never cost more than the verdict. A device
            # localization (explain_loc) carries the exact event already,
            # so the recovery stays purely report detail.
            with trace.phase("settle.report"):
                if res.final_configs is None and stream is not None \
                        and len(stream) <= MAX_REPORT_EVENTS:
                    try:
                        res2 = check_stream(
                            stream, step=step_py or cas_register_step_py,
                            init_state=init_state)
                        if res2.valid is False:
                            res.final_configs = res2.final_configs
                    except Exception:  # noqa: BLE001 report detail is optional
                        logger.exception("final-configs recovery failed")
                if res.final_configs is not None:
                    out["final-configs"] = res.final_configs
                out["plot"] = self._render(res, history, test)
            with trace.phase("settle.explain", keys=1):
                self._explain(out, res, history, test, stream, step_py,
                              init_state, step_ids, explain_on,
                              explain_loc, opts)
        return out

    def _trace_anomaly(self, history, op_index: int, res) -> None:
        """The causal-trace half of an INVALID verdict: an ``explain``
        instant on the checker track carrying the first-anomaly op's
        stable trace id — the same id the interpreter's dispatch slice
        carries in its args, so the anomaly links straight back to its
        original dispatch. ``op_index`` may name either half of the op
        (the matrix localizer reports the fatal return); the id is
        always minted from the *invocation*'s time, which is what
        dispatch used. Never fails a check (doc/observability.md
        "Causal trace")."""
        try:
            from jepsen_tpu import trace as trace_mod
            tracer = trace_mod.get_tracer()
            if not tracer.enabled or not (0 <= op_index < len(history)):
                return
            op = history[op_index]
            inv = op
            if op.get("type") != "invoke":
                # walk back to this process's invocation — the most
                # recent earlier invoke by the same process
                for j in range(op_index - 1, -1, -1):
                    cand = history[j]
                    if cand.get("process") == op.get("process") \
                            and cand.get("type") == "invoke":
                        inv = cand
                        break
            tr_id = trace_mod.trace_id_for(inv.get("process"),
                                           inv.get("time"))
            tracer.instant(trace_mod.TRACK_CHECKER, "explain",
                           args={"op_index": op_index,
                                 "f": str(op.get("f")),
                                 "process": op.get("process"),
                                 "algorithm": res.algorithm,
                                 "trace_id": tr_id})
        except Exception:  # noqa: BLE001 — tracing never masks a verdict
            logger.exception("anomaly trace emission failed")

    def _explain(self, out, res, history, test, stream, step_py,
                 init_state, step_ids, explain_on, explain_loc,
                 opts) -> None:
        """Anomaly forensics for an INVALID verdict: localize + shrink a
        minimal witness, write ``anomaly.json`` + the witness timeline
        into the store dir, and surface a summary in the result
        (doc/observability.md "Anomaly forensics"). Never fails the
        check; ``explain: False`` in the test map turns it off."""
        if not explain_on or stream is None:
            return
        try:
            from jepsen_tpu.checker import explain as explain_mod
            tmap = test if isinstance(test, dict) else {}
            forensics = explain_mod.explain_stream(
                stream, step_ids=step_ids, step_py=step_py,
                init_state=init_state, loc=explain_loc, failure=res,
                shrink_budget=explain_mod.shrink_budget(tmap),
                max_witness_ops=explain_mod.max_witness_ops(tmap))
            if forensics is None:
                return
            out["explain"] = {
                "first-anomaly-op": forensics["first_anomaly"]["op_index"],
                "witness-ops": len(forensics["witness"]["op_indices"]),
                "backend": forensics["backend"],
                "bisect-steps": forensics["bisect_steps"],
            }
            if isinstance(test, dict) and test.get("start_time"):
                arts = explain_mod.write_artifacts(test, history,
                                                   forensics, opts=opts)
                if arts:
                    out["explain"]["artifacts"] = sorted(
                        str(k) for k in arts)
        except Exception:  # noqa: BLE001 — forensics never mask a verdict
            logger.exception("anomaly forensics failed")

    def _render(self, res, history, test) -> str | None:
        """linear.png into the test's store dir (checker.clj:205-212)."""
        if not isinstance(test, dict) or not test.get("start_time"):
            return None  # no store coordinates: a bare re-check
        try:
            from jepsen_tpu import store
            from jepsen_tpu.checker.linear_report import render_failure
            path = str(store.path_mk(test, "linear.png"))
            return render_failure(history, res, path)
        except Exception:  # noqa: BLE001  rendering must not mask verdicts
            logger.exception("linear.png rendering failed")
            return None


def linearizable(model=None, **kw) -> Checker:
    return LinearizableChecker(model=model, **kw)


def check_stored(test_name: str, timestamp: str, store_dir: str = "store",
                 model=None, accelerator: str = "auto") -> dict:
    """Re-checks a STORED register run's linearizability, preferring the
    ``lin_*`` EventStream columns in its history.npz sidecar — no jsonl
    load, no re-encoding (the stored-column twin of
    elle.list_append.check_stored). The fast lane settles only VALID
    verdicts (via the transfer-matrix screen or the exact stream
    search); anything else — invalid (needs op context for the
    failure report), out-of-regime, missing sidecar — falls back to
    the jsonl history through the normal checker."""
    from jepsen_tpu import store
    from jepsen_tpu.checker.linear_encode import stream_from_columns
    from jepsen_tpu.models import CASRegister, cas_register_spec

    model = model if model is not None else CASRegister()
    cols = None
    if isinstance(model, CASRegister):
        try:
            cols = store.load_linear_columns(test_name, timestamp,
                                             store_dir)
        except Exception as e:  # noqa: BLE001 - damaged sidecar: use jsonl
            store.note_sidecar_load_failure(
                f"{test_name}/{timestamp} (lin_*)", e)
            cols = None
    if cols is not None:
        try:
            stream = stream_from_columns(cols)
            init_id = (0 if model.value is None
                       else stream.intern.id(model.value))
            spec = cas_register_spec(init_id)
            checker = LinearizableChecker(model=model,
                                          accelerator=accelerator)
            # the one dispatch check() uses — device threshold, matrix
            # screen, frontier kernel, native-first host lanes — so the
            # stored lane can't drift from the live one
            # explain=False: an invalid stored verdict falls back to the
            # jsonl full check below, which runs forensics itself — a
            # localization here would be paid for and discarded
            res = checker._search_stream(stream, cas_register_step_py,
                                         spec, checker.algorithm,
                                         accelerator, explain=False)
            res.algorithm += "(stored)"
            if res.valid is True:
                return checker._finish(res, [], None)
        except Exception:  # noqa: BLE001 - fast lane must never block
            logger.exception("stored-column linear re-check failed; "
                             "falling back to jsonl")
    history = store.load_history(test_name, timestamp, store_dir)
    checker = LinearizableChecker(model=model, accelerator=accelerator)
    return checker.check({"name": test_name, "start_time": timestamp,
                          "store_dir": store_dir}, history, {})
