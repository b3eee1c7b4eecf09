"""Checker backend degradation ladder: fallback as policy, not scatter.

The linearizable checker accumulated three ad-hoc fallbacks (matrix
screen -> frontier kernel, frontier overflow -> exact CPU retry, native
C++ capacity miss -> Python stream search) with no shared accounting,
watchdog, or failure memory. :class:`BackendLadder` owns that chain —
sharded-matrix (multi-device mesh) -> pallas-matrix -> jitlin device
kernel -> native C++ -> CPU — as one policy object:

* **Soft demotion**: a backend may *decline* a dispatch (return ``None``
  or raise :class:`Unavailable`) — out of regime, capacity miss,
  library unbuilt. The ladder falls through and counts the demotion.
* **Resource exhaustion**: an XLA ``RESOURCE_EXHAUSTED`` (device OOM)
  or compile failure gets ONE adaptive retry with halved tile/batch
  sizes (the backend's ``shrink`` hook) before demoting.
* **Watchdog**: device dispatches run under a timeout — a hung
  dispatch (wedged runtime) demotes to the next backend instead of
  hanging the run. The stuck thread is abandoned (daemon), mirroring
  ``utils.timeout``.
* **Strict device**: a dispatch whose ``ctx["strict_device"]`` is set
  (the checker's explicit ``accelerator="tpu"``) may demote from one
  device rung to another, but a device rung's hard failure never
  settles on a host rung: :class:`DeviceFailed` raises instead, so a
  broken device can't hide behind a CPU verdict. Declines (out of
  regime, frontier overflow) still fall through.
* **Circuit breaker**: ``breaker_threshold`` *consecutive* hard
  failures trip a per-backend breaker; further dispatches skip the
  backend until :meth:`reset`. A flaky accelerator degrades a run to
  CPU once instead of eating the timeout on every check.
* **Telemetry**: ``checker_backend_demotions_total`` (by backend and
  reason), ``checker_watchdog_timeouts_total``,
  ``checker_backend_shrink_retries_total``, and a
  ``checker_circuit_open`` gauge flow through the registry
  (doc/observability.md, doc/robustness.md).

The terminal backend of a well-formed ladder always settles, so
:class:`LadderExhausted` indicates a configuration bug, not a bad
history.
"""
from __future__ import annotations

import contextvars
import logging
import os
import threading
from dataclasses import dataclass, field
from typing import Any, Callable

from jepsen_tpu import telemetry

logger = logging.getLogger("jepsen.checker.ladder")

# Device dispatches hung longer than this demote instead of blocking the
# run. 0 disables the watchdog (dispatch runs inline on the caller's
# thread — zero overhead, the pre-ladder behavior).
DEFAULT_WATCHDOG_S = float(os.environ.get("JEPSEN_TPU_WATCHDOG_S", "600"))
DEFAULT_BREAKER_THRESHOLD = int(
    os.environ.get("JEPSEN_TPU_BREAKER_THRESHOLD", "3"))


class Unavailable(Exception):
    """Raised by a backend to decline a dispatch (capability miss, out of
    regime). A quiet demotion: no failure is counted against the
    backend."""


class DeviceFailed(RuntimeError):
    """A device rung failed under ``strict_device``: settling on a host
    rung would report a CPU verdict for a run that asked for the
    device."""


class LadderExhausted(Exception):
    """Every backend declined or failed — the ladder was configured
    without a terminal always-settles backend."""


# Exception-text markers for device-memory exhaustion and XLA compile
# failures. jaxlib's XlaRuntimeError carries the gRPC-style status name
# in its message; we match text so the ladder needs no jax import (and
# tests can fake the failure with a plain RuntimeError).
_RESOURCE_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                     "OOM ")
_COMPILE_MARKERS = ("XlaRuntimeError", "Compilation failure",
                    "compilation failed", "INTERNAL: Failed to compile")


def is_resource_exhausted(e: BaseException) -> bool:
    s = f"{type(e).__name__}: {e}"
    return any(m in s for m in _RESOURCE_MARKERS)


def is_compile_failure(e: BaseException) -> bool:
    s = f"{type(e).__name__}: {e}"
    return any(m in s for m in _COMPILE_MARKERS)


def _safe_pred(pred, e: BaseException) -> bool:
    try:
        return bool(pred(e))
    except Exception:  # noqa: BLE001 — a broken predicate is a no
        logger.exception("backend retryable predicate failed")
        return False


_TIMED_OUT = object()

# Exception-text markers for losing a device / a collective mid-dispatch
# — the elastic sharded rung's shrink trigger (doc/robustness.md
# "Resumable checks and the elastic mesh"). Text-matched like the
# resource markers so tests can fake the failure with a RuntimeError.
# Capability misses ("collectives are not implemented on this backend")
# are NOT losses: shrinking a mesh the backend can't run at any width
# only delays the demotion, so those demote immediately.
_DEVICE_LOSS_MARKERS = ("UNAVAILABLE", "device lost", "DEVICE_LOST",
                        "collective", "DATA_LOSS", "ABORTED",
                        "failed to connect")
_CAPABILITY_MARKERS = ("not implemented", "not supported", "unimplemented",
                       "UNIMPLEMENTED")


def is_device_loss(e: BaseException) -> bool:
    s = f"{type(e).__name__}: {e}"
    if any(m in s for m in _CAPABILITY_MARKERS):
        return False
    return any(m in s for m in _DEVICE_LOSS_MARKERS)


@dataclass
class Backend:
    """One rung. ``fn(ctx)`` returns a result, or ``None`` /raises
    :class:`Unavailable` to decline. ``eligible(ctx)`` gates routing
    (not counted as demotion — a host-regime dispatch never *attempts*
    the device rungs). ``shrink(ctx)`` halves the backend's tile/batch
    knobs in the shared context before a resource-exhaustion retry
    (the failing exception rides ``ctx["_shrink_error"]`` so an
    elastic rung can attribute a device loss); return False when
    nothing is left to halve. ``max_shrinks`` bounds the retries (1 =
    the classic single adaptive retry; the elastic sharded rung sets
    it to its shrink-ladder depth so an 8-device mesh can step 8→4→2
    before demoting). ``retryable`` extends the shrink-retry trigger
    beyond RESOURCE_EXHAUSTED/compile failures (e.g. device-loss /
    collective errors for the elastic mesh). ``device=True`` opts the
    rung into the watchdog."""

    name: str
    fn: Callable[[dict], Any]
    eligible: Callable[[dict], bool] = field(default=lambda ctx: True)
    shrink: Callable[[dict], bool] | None = None
    device: bool = False
    max_shrinks: int = 1
    retryable: Callable[[BaseException], bool] | None = None


class BackendLadder:
    def __init__(self, backends: list[Backend],
                 watchdog_s: float = DEFAULT_WATCHDOG_S,
                 breaker_threshold: int = DEFAULT_BREAKER_THRESHOLD):
        self.backends = list(backends)
        self.watchdog_s = watchdog_s
        self.breaker_threshold = breaker_threshold
        self._failures: dict[str, int] = {}
        self._broken: set[str] = set()
        # (backend, outcome) attempt regimes this ladder has entered —
        # the rung half of the fuzzer's checker-state coverage signal
        # (doc/robustness.md "Schedule fuzzing")
        self._cov_entries: set[tuple[str, str]] = set()
        self._lock = threading.Lock()

    def coverage_probe(self) -> dict:
        """Rung-regime coverage for the schedule fuzzer: every
        (backend, outcome) pair any attempt has produced on this
        ladder, as stable edge strings. A schedule that first drives a
        rung into shrink-retry or watchdog-timeout is exploring checker
        territory no prior corpus entry reached."""
        with self._lock:
            entries = sorted(self._cov_entries)
        return {"edges": ["rung:%s:%s" % e for e in entries]}

    # -- breaker state ------------------------------------------------------

    def broken(self) -> set[str]:
        with self._lock:
            return set(self._broken)

    def reset(self, backend: str | None = None) -> None:
        """Closes breakers (all, or one backend's) and zeroes failure
        counts — for tests and for operators who fixed the accelerator."""
        with self._lock:
            if backend is None:
                self._broken.clear()
                self._failures.clear()
            else:
                self._broken.discard(backend)
                self._failures.pop(backend, None)
        self._export_breaker()

    def _count_failure(self, name: str) -> None:
        with self._lock:
            n = self._failures.get(name, 0) + 1
            self._failures[name] = n
            tripped = (n >= self.breaker_threshold
                       and name not in self._broken)
            if tripped:
                self._broken.add(name)
        if tripped:
            logger.warning("checker backend %r circuit breaker tripped "
                           "after %d consecutive failures", name, n)
            reg = telemetry.get_registry()
            if reg.enabled:
                reg.event("checker-circuit-open", backend=name, failures=n)
            self._export_breaker()

    def _count_success(self, name: str) -> None:
        with self._lock:
            self._failures[name] = 0

    def _export_breaker(self) -> None:
        reg = telemetry.get_registry()
        if not reg.enabled:
            return
        g = reg.gauge("checker_circuit_open",
                      "1 while a backend's circuit breaker is open",
                      labels=("backend",))
        with self._lock:
            broken = set(self._broken)
        for b in self.backends:
            g.set(1.0 if b.name in broken else 0.0, backend=b.name)

    def _demote(self, name: str, reason: str) -> None:
        reg = telemetry.get_registry()
        if reg.enabled:
            reg.counter("checker_backend_demotions_total",
                        "ladder demotions, by backend and reason",
                        labels=("backend", "reason")
                        ).inc(backend=name, reason=reason)
        from jepsen_tpu import trace as trace_mod
        trace_mod.get_tracer().instant(
            trace_mod.TRACK_LADDER, "demote",
            args={"backend": name, "reason": reason})
        logger.info("checker backend %r demoted (%s)", name, reason)

    # -- dispatch -----------------------------------------------------------

    @staticmethod
    def _run_rung(backend: Backend, ctx: dict) -> Any:
        """``backend.fn(ctx)`` inside its ``ladder.rung`` phase, on the
        thread that runs it."""
        from jepsen_tpu import trace as trace_mod
        with trace_mod.phase("ladder.rung", backend=backend.name) as span:
            try:
                res = backend.fn(ctx)
            except Unavailable:
                span.set(outcome="unavailable")
                raise
            except BaseException:
                span.set(outcome="error")
                raise
            span.set(outcome="declined" if res is None else "settled")
            return res

    def _call(self, backend: Backend, ctx: dict) -> Any:
        """One invocation, under the watchdog for device rungs."""
        if not backend.device or not self.watchdog_s:
            return self._run_rung(backend, ctx)
        result: list = []
        error: list = []

        def run():
            try:
                result.append(self._run_rung(backend, ctx))
            except BaseException as e:  # noqa: BLE001
                error.append(e)

        # the caller's context rides along, so the rung's phases carry
        # the id of the check that dispatched it
        t = threading.Thread(target=contextvars.copy_context().run,
                             args=(run,), daemon=True,
                             name=f"jepsen-checker-{backend.name}")
        t.start()
        t.join(self.watchdog_s)
        if t.is_alive():
            return _TIMED_OUT
        if error:
            raise error[0]
        return result[0]

    def run(self, ctx: dict) -> tuple[Any, str]:
        """Dispatches ``ctx`` down the ladder; returns ``(result,
        backend_name)`` from the first rung that settles. ``ctx``
        accumulates ``_attempted`` — the rung names tried *before* the
        winner — so callers can label results (e.g. the CPU rung tags
        itself ``(fallback)`` only when reached by demotion from a
        device rung). Ineligible rungs are pure routing: neither
        attempted nor counted."""
        attempted: list[str] = ctx.setdefault("_attempted", [])
        last = self.backends[-1] if self.backends else None
        for backend in self.backends:
            try:
                if not backend.eligible(ctx):
                    continue
            except Exception:  # noqa: BLE001 — a broken gate is a decline
                logger.exception("eligibility probe for %r failed",
                                 backend.name)
                continue
            failure = ctx.get("_device_error")
            if failure is not None and not backend.device \
                    and ctx.get("strict_device"):
                raise DeviceFailed(
                    f"device rung failed and the dispatch is pinned to "
                    f"the device (attempted: {attempted}): "
                    f"{failure!r}") from failure
            terminal = backend is last
            # the terminal rung is breaker-exempt: it has no fallback,
            # so skipping it would wedge every subsequent dispatch
            if not terminal and backend.name in self.broken():
                self._demote(backend.name, "circuit-open")
                if backend.device:
                    ctx["_device_error"] = RuntimeError(
                        f"{backend.name} circuit breaker is open")
                attempted.append(backend.name)
                continue
            res = self._attempt(backend, ctx, terminal=terminal)
            if res is None:
                attempted.append(backend.name)
                continue
            self._count_success(backend.name)
            return res, backend.name
        raise LadderExhausted(
            f"no checker backend settled the dispatch "
            f"(attempted: {attempted})")

    def _attempt(self, backend: Backend, ctx: dict,
                 terminal: bool = False) -> Any:
        """One rung's dispatch: watchdog, single shrink retry, failure
        accounting. Returns the result, or None to demote. A hard
        failure in the ``terminal`` rung re-raises instead of demoting
        — there is nothing below it, and the caller's check_safe wants
        the real traceback (the pre-ladder semantics)."""
        from jepsen_tpu import trace as trace_mod
        tracer = trace_mod.get_tracer()
        reg = telemetry.get_registry()
        shrinks = 0
        t0_us = 0

        def rung_span(outcome: str) -> None:
            with self._lock:
                self._cov_entries.add((backend.name, outcome))
            # one self-contained slice per attempt (ph X, not B/E: a
            # watchdog-abandoned zombie attempt may still be emitting
            # when the next rung starts — X slices can't tear a pairing)
            if tracer.enabled:
                tracer.complete(trace_mod.TRACK_LADDER, "rung", t0_us,
                                trace_mod.now_us() - t0_us,
                                args={"backend": backend.name,
                                      "outcome": outcome})

        while True:
            # carry generation: rungs that thread a resume carry through
            # ctx (the segmented matrix chain) capture this at entry and
            # only publish carries while it is still theirs — a
            # watchdog-abandoned zombie's late writes can't clobber the
            # resumed rung's own progress (doc/robustness.md)
            ctx["_gen"] = ctx.get("_gen", 0) + 1
            t0_us = trace_mod.now_us() if tracer.enabled else 0
            try:
                res = self._call(backend, ctx)
            except Unavailable:
                rung_span("unavailable")
                self._demote(backend.name, "unavailable")
                return None
            except Exception as e:  # noqa: BLE001
                rex = is_resource_exhausted(e) or is_compile_failure(e)
                elastic = (backend.retryable is not None
                           and _safe_pred(backend.retryable, e))
                retryable = rex or elastic
                if retryable and shrinks < backend.max_shrinks \
                        and backend.shrink is not None:
                    ctx["_shrink_error"] = e
                    try:
                        can_shrink = backend.shrink(ctx)
                    except Exception:  # noqa: BLE001
                        can_shrink = False
                    finally:
                        ctx.pop("_shrink_error", None)
                    if can_shrink:
                        shrinks += 1
                        rung_span("shrink-retry")
                        if reg.enabled:
                            reg.counter(
                                "checker_backend_shrink_retries_total",
                                "resource-exhaustion retries with halved "
                                "tile/batch sizes", labels=("backend",)
                            ).inc(backend=backend.name)
                        logger.warning(
                            "backend %r failed retryably (%s); retrying "
                            "with shrunk sizes (%d/%d)", backend.name,
                            type(e).__name__, shrinks,
                            backend.max_shrinks)
                        continue
                rung_span("error")
                if terminal:
                    raise
                if backend.device:
                    ctx["_device_error"] = e
                self._count_failure(backend.name)
                self._demote(backend.name,
                             "resource-exhausted" if rex
                             else "device-loss" if elastic else "error")
                logger.warning("checker backend %r failed: %r",
                               backend.name, e)
                return None
            if res is _TIMED_OUT:
                rung_span("watchdog-timeout")
                ctx["_device_error"] = TimeoutError(
                    f"{backend.name} dispatch exceeded the "
                    f"{self.watchdog_s:g}s watchdog")
                if reg.enabled:
                    reg.counter(
                        "checker_watchdog_timeouts_total",
                        "device dispatches abandoned by the watchdog",
                        labels=("backend",)).inc(backend=backend.name)
                self._count_failure(backend.name)
                self._demote(backend.name, "watchdog-timeout")
                return None
            if res is None:
                rung_span("declined")
                self._demote(backend.name, "declined")
                return None
            rung_span("settled")
            return res
