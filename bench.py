"""Benchmark matrix: every BASELINE.json config, one JSON line each.

BASELINE.json publishes five configs plus a scaling metric ("max history
length checked <300s"); the reference's only hard in-repo perf anchor is
the >20k ops/sec generator-scheduling figure
(jepsen/src/jepsen/generator.clj:67-70).  Each config below prints one
compact JSON line {"metric", "value", "unit", "vs_baseline", ...extras}.
All lines are buffered and emitted together at the very end, with the
round-1 headline metric LAST (the driver parses the final line) and a
compact ``bench_summary`` line (every metric's value+ratio) right
before it, so the driver's 2000-char stdout tail always recovers every
metric:

  1. cpu_ref_200op          — 200-op single-register history, CPU oracle
                              (the knossos :linear analog; the anchor the
                              device configs are measured against).
  2. interpreter_sched      — pure generator+interpreter scheduling loop,
                              vs the reference's >20k ops/s anchor.
  3. multikey_64x1k         — 64 independent keys x 1k ops, vmapped
                              per-key on device (BASELINE config 3).
  4. set_full_matrix        — set-full membership-matrix kernel vs the
                              CPU per-element walk (BASELINE config 4).
  5. elle_50k_txns          — 50k-txn list-append dependency check, device
                              SCC trim vs CPU trim (BASELINE config 5).
  6. matrix_kernel_128k     — block-composed transfer-matrix kernel on a
                              small-value-domain 128k-event history vs the
                              event-by-event dense scan on device; carries
                              per-phase attribution (phase_*_s measured
                              host/device split + modeled_*_frac analytic
                              FLOP shares — doc/performance.md).
  7. max_history_len_300s   — largest single history verified on device
                              within the 300 s budget (north-star scaling
                              metric; run length capped by
                              BENCH_SCALE_TARGET_S, default 240).
  8. single_register_ops_verified_per_sec_10k — the round-1 headline:
                              10k-op history vs the reference's 1 h CPU
                              knossos timeout (BASELINE config 2).

Environment knobs: BENCH_SCALE_TARGET_S (seconds of device time the
scaling run aims to fill; 0 skips config 7), BENCH_SKIP (comma-separated
stage keys to skip: cpu_ref, interpreter_sched, wal_ingest, multikey,
set_full, elle_50k, ir_amortization, online_lag, matrix_kernel, explain,
multichip, ckpt, trace, fleet, headline, scale, telemetry — the last
opts out of the per-stage telemetry block in bench_summary).
``fleet`` measures the fleet plane end to end (fleet_runs_sustained:
100 concurrent runs shipped over loopback HTTP into one pool daemon,
one mesh shrink + regrow cycle injected, verdicts checked bit-identical
to local analyze — doc/observability.md "Fleet plane"). ``trace`` measures
the causal-trace cost (trace_overhead_frac: fully-traced vs untraced
interpreter wall, bar <= 5%, with the always-on flight-recorder
configuration <= 1% — doc/observability.md "Causal trace").
``ckpt`` measures the
resumable-check cost/benefit (ckpt_overhead_frac bar <= 5%, plus
resume_savings_frac at a 50% cut — doc/robustness.md "Resumable checks
and the elastic mesh"). ``ir_amortization``
measures the history-IR encode-once contract: a two-checker run over
one 50k-op history reports the first encode's wall vs the second
checker's encode phase (target ~= 0 — views are memoized on the shared
IR; doc/performance.md "History IR"). ``explain`` tracks anomaly-forensics cost
(explain_latency_128k: localize + shrink a planted anomaly; the bar is
< 2× the plain check wall — doc/observability.md "Anomaly forensics").
"""
from __future__ import annotations

import json
import os
import re
import sys
import time
import traceback

import numpy as np

from jepsen_tpu import telemetry

N_OPS = 10_000
N_PROCS = 5
CAPACITY = 256
BASELINE_OPS_PER_SEC = N_OPS / 3600.0  # reference CPU knossos: 1 h timeout
GEN_SCHED_BASELINE = 20_000.0          # generator.clj:67-70

_RESULTS: list[dict] = []

# Per-stage telemetry folded into the bench_summary line (BENCH_SKIP key
# "telemetry" opts out): compile_s (the timed warm-up call — JIT compile
# plus one execute), wall_s (whole stage), device_peak_mb (allocator
# high-water AFTER the stage; monotone across stages, so per-stage
# high-water reads as the running max). The execute side of the
# compile/execute split is each metric's median trial time, already in
# the metric lines.
_STAGE_TELEMETRY: dict = {}
_TELEMETRY_ON = True


def _stage_note(stage: str, **kv):
    if _TELEMETRY_ON:
        _STAGE_TELEMETRY.setdefault(stage, {}).update(kv)


def _warm_timed(stage: str, fn):
    """Runs a warm-up (compile) call, recording its wall time as the
    stage's compile_s via the telemetry block."""
    t0 = time.perf_counter()
    out = fn()
    _stage_note(stage, compile_s=round(time.perf_counter() - t0, 3))
    return out


def emit(metric: str, value: float, unit: str, vs_baseline: float, **extra):
    line = {"metric": metric, "value": round(float(value), 2), "unit": unit,
            "vs_baseline": round(float(vs_baseline), 2)}
    line.update(extra)
    _RESULTS.append(line)
    print(f"[bench] {metric}: {line['value']} {unit} "
          f"(vs_baseline {line['vs_baseline']})", file=sys.stderr, flush=True)


def _block_stream(n_blocks: int, n_procs: int = N_PROCS, n_values: int = 100,
                  start_block: int = 0):
    """Vectorized valid single-register event stream: block t = P invokes
    (proc 0 writes w_t = t mod V; procs 1..P-1 read w_{t-1}) then P
    returns. Reads linearize before the concurrent write, so the history
    is linearizable by construction. O(E) numpy, no Python per-op loop —
    this is what makes multi-million-event scaling runs generatable.

    ``start_block`` continues a longer logical history: block numbering
    (and so the read/write value sequence) picks up at that offset, so
    consecutive segments chain correctly through the carried frontier."""
    from jepsen_tpu.checker.linear_encode import EventStream
    from jepsen_tpu.history import Intern
    from jepsen_tpu.models import CAS_F_READ, CAS_F_WRITE

    P, V = n_procs, n_values
    intern = Intern()
    for v in range(V):
        intern.id(v)  # ids 1..V

    t = np.arange(start_block, start_block + n_blocks, dtype=np.int64)
    w_id = (t % V).astype(np.int32) + 1              # this block's write
    r_id = np.where(t > 0, ((t - 1) % V).astype(np.int32) + 1, 0)  # read

    kind = np.tile(np.concatenate([np.zeros(P, np.int8), np.ones(P, np.int8)]),
                   n_blocks)
    slot = np.tile(np.concatenate([np.arange(P), np.arange(P)]).astype(np.int32),
                   n_blocks)
    f = np.zeros((n_blocks, 2 * P), np.int32)
    f[:, 0] = CAS_F_WRITE
    f[:, 1:P] = CAS_F_READ
    a = np.zeros((n_blocks, 2 * P), np.int32)
    a[:, 0] = w_id
    a[:, 1:P] = r_id[:, None]
    E = n_blocks * 2 * P
    return EventStream(
        kind=kind, slot=slot, f=f.reshape(-1), a=a.reshape(-1),
        b=np.zeros(E, np.int32), op_index=np.arange(E, dtype=np.int32),
        n_slots=P, n_ops=n_blocks * P, intern=intern)


def _prefix(stream, n_events: int):
    """Stream prefix: a truncated history is still a history (the cut-off
    pending invokes simply never return)."""
    from dataclasses import replace
    return replace(stream, kind=stream.kind[:n_events],
                   slot=stream.slot[:n_events], f=stream.f[:n_events],
                   a=stream.a[:n_events], b=stream.b[:n_events],
                   op_index=stream.op_index[:n_events])


def _device_args(batch, num_states):
    """The frontier scan's arguments for a one-stream padded batch."""
    import jax

    from jepsen_tpu.ops.jitlin import scan_inputs
    args, _ = scan_inputs(*(batch[k] for k in ("kind", "slot", "f", "a", "b")),
                          max(1, batch["n_slots"]), num_states)
    return tuple(jax.numpy.asarray(x[0]) for x in args)


def _force(*xs):
    """Forces completion by reading results back to host in ONE batched
    transfer (one device_get for every array, not one readback each)."""
    import jax

    return list(jax.device_get(xs))


def _best_of(fn, n: int = 2):
    """(result, best dt) over n runs — the shared host is noisy, so all
    quick configs take the minimum for BOTH sides of any comparison."""
    out, times = _trials(fn, n)
    return out, min(times)


def _trials(fn, n: int = 5):
    """(result, [dt...]) over n runs. Metrics report the MEDIAN with
    min/max spread (VERDICT r2: single-shot numbers made regressions and
    measurement fixes indistinguishable on this noisy shared host; r4
    widened 3 -> 5 trials after clean-run medians still swung 40%)."""
    times = []
    out = None
    for _ in range(n):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return out, times


_ROOFLINE: dict = {}


def device_roofline() -> dict:
    """Measured single-chip peaks used as denominators for the
    hardware-efficiency fractions (VERDICT r4 #7: every ratio was
    vs-CPU; nothing said what fraction of the chip the kernels use).
    Empirical, not datasheet: best-of-3 large square matmuls (f32 and
    bf16) and a large elementwise add for HBM read+write bandwidth."""
    if _ROOFLINE:
        return _ROOFLINE
    import jax
    import jax.numpy as jnp
    from jax import lax

    # chain enough work inside ONE dispatch that the per-dispatch
    # overhead amortizes away — a single 4096 matmul finishes in
    # microseconds of device time and would measure the dispatch instead
    measured: dict = {}   # publish all-or-nothing: a partial cache
    #                       would silently drop fractions forever
    n, reps = 4096, 32
    for dt, key in ((jnp.float32, "f32_matmul_flops"),
                    (jnp.bfloat16, "bf16_matmul_flops")):
        a = jnp.eye(n, dtype=dt) * 0.5

        @jax.jit
        def chain(x, a=a):
            return lax.fori_loop(0, reps, lambda i, y: y @ a, x)

        chain(a).block_until_ready()
        _, ts = _trials(lambda: chain(a).block_until_ready(), 3)
        measured[key] = reps * 2.0 * n ** 3 / min(ts)
    big = jnp.ones((64 * 1024 * 1024,), jnp.float32)   # 256 MB
    bw_reps = 64

    @jax.jit
    def adds(x):
        return lax.fori_loop(0, bw_reps, lambda i, y: y + 1.0, x)

    adds(big).block_until_ready()
    _, ts = _trials(lambda: adds(big).block_until_ready(), 3)
    measured["hbm_bytes_per_sec"] = bw_reps * 2.0 * big.size * 4 / min(ts)
    _ROOFLINE.update(measured)
    return _ROOFLINE


def matrix_roofline_extras(n_returns: int, S: int, V: int,
                           seconds: float) -> dict:
    """Roofline accounting for the transfer-matrix kernels: each return
    composes one [MV, MV] operator via ~(ceil(log2 S) + 2) dense f32
    matmuls (closure squarings + K-apply + P-update; the elementwise L
    build is excluded, so this is a LOWER bound on issued FLOPs).
    ``roofline_frac`` = modeled achieved FLOP/s over the measured f32
    matmul peak — small matrices (MV ~ 2^S·V) under-tile the MXU, which
    is exactly what this fraction is here to make visible."""
    flops_per_return = telemetry.matrix_modeled_flops(1, S, V)
    achieved = telemetry.matrix_modeled_flops(n_returns, S, V) / seconds
    peak = device_roofline()["f32_matmul_flops"]
    return {
        "modeled_flops_per_return": round(flops_per_return),
        "achieved_matmul_flops": round(achieved),
        "device_f32_matmul_peak_flops": round(peak),
        "roofline_frac": round(achieved / peak, 4),
    }


def _median(ts):
    """Upper median — the one idiom shared by every bench reporter."""
    s = sorted(ts)
    return s[len(s) // 2]


def _combine_reduction(keys, chunks, mv, fused) -> float:
    """tree/fused modeled-HBM-byte ratio of the chunk combine — the
    fused combine's designed win (1.0 when the tree ran: the regression
    signal). Shared by matrix_kernel_128k and the segmented scale
    metric so the two can't silently diverge."""
    if not fused:
        return 1.0
    return round(
        telemetry.combine_modeled_hbm_bytes(keys, chunks, mv, False)
        / max(telemetry.combine_modeled_hbm_bytes(keys, chunks, mv, True),
              1), 2)


def _spread(times, scale: float):
    """Spread extras for emit(): rates at the median/min/max timings."""
    ts = sorted(times)
    med = _median(ts)
    return med, {"trials": len(ts),
                 "value_min": round(scale / ts[-1], 2),
                 "value_max": round(scale / ts[0], 2)}


def cfg_cpu_ref_200() -> float:
    """BASELINE config 1: the CPU oracle (knossos :linear analog)."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops

    history = _register_history(200, n_procs=N_PROCS, seed=1)
    stream = encode_register_ops(history)
    check_stream(stream)  # warm interpreter caches
    res, times = _trials(lambda: check_stream(stream), 5)
    assert res.valid is True
    med, extras = _spread(times, 200)
    rate = 200 / med
    # this IS the CPU reference anchor the device configs compare against
    emit("cpu_ref_200op_ops_per_sec", rate, "ops/s", 1.0, **extras)
    return rate


def cfg_interpreter_sched():
    """Reference anchor: >20k ops/sec pure-generator scheduling
    (generator.clj:67-70). The simulated loop rides the native
    scheduler lane (columnar_ext.c sim_lane) when probed; the
    ``sched_batch_*`` extras measure the THREADED interpreter's chunked
    completion bus (``sched_batch_ops``) against its per-op fallback —
    Tentpole B of the host ingest spine (doc/performance.md)."""
    import jepsen_tpu.generator as gen
    from jepsen_tpu.generator.interpreter import (
        DEFAULT_SCHED_BATCH_OPS, run as interp_run,
    )
    from jepsen_tpu.generator.simulate import quick

    n = 50_000
    test = {"concurrency": 5}
    history, times = _trials(lambda: quick(
        test, gen.limit(n, gen.Fn(lambda: {"f": "write", "value": 1}))), 3)
    n_inv = sum(1 for op in history if op["type"] == "invoke")
    assert n_inv == n, n_inv
    med, extras = _spread(times, n)

    class _Echo:
        def open(self, test, node):
            return self

        def setup(self, test):
            pass

        def invoke(self, test, op):
            return {**op, "type": "ok"}

        def teardown(self, test):
            pass

        def close(self, test):
            pass

    m = 10_000

    def threaded(batch):
        t = {"concurrency": 8, "client": _Echo(), "nodes": ["n1"],
             "name": "bench-sched", "sched_batch_ops": batch,
             "generator": gen.clients(gen.limit(
                 m, gen.Fn(lambda: {"f": "write", "value": 1})))}
        h = interp_run(t)
        assert sum(1 for op in h if op["type"] == "invoke") == m
        return h

    _, t_batched = _trials(lambda: threaded(DEFAULT_SCHED_BATCH_OPS), 3)
    _, t_per_op = _trials(lambda: threaded(0), 3)
    batched_rate = m / _median(t_batched)
    per_op_rate = m / _median(t_per_op)
    emit("interpreter_sched_ops_per_sec", n / med, "ops/s",
         (n / med) / GEN_SCHED_BASELINE,
         sched_batch_default=DEFAULT_SCHED_BATCH_OPS,
         sched_batch_ops_per_sec=round(batched_rate, 1),
         sched_batch_per_op_ops_per_sec=round(per_op_rate, 1),
         sched_batch_vs_per_op=round(batched_rate / per_op_rate, 3),
         **extras)


def cfg_wal_ingest():
    """wal_ingest_native: the raw WAL chunk scan+parse rate, native
    (columnar_ext.c ingest_chunk) vs the pure-Python twin over the same
    bytes — the tail side of the 1M ops/s ingest bar, isolated from
    encode+frontier (those ride online_lag)."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.history_ir import ingest
    from jepsen_tpu.journal import parse_wal_chunk_py
    from jepsen_tpu.store import _serializable

    history = _register_history(100_000, n_procs=5, seed=3, n_values=5)
    n = len(history)  # invokes + completions
    chunk = "".join(json.dumps(_serializable(op)) + "\n"
                    for op in history).encode()

    def native():
        m = ingest.native_mod()
        assert m is not None, "native ingest unavailable"
        with ingest.ingest_burst():
            ops, consumed, torn, _tr = m.ingest_chunk(
                chunk, True, ingest._line_fallback,
                ingest._SKIP, ingest._TORN)
        assert len(ops) == n and torn == 0 and consumed == len(chunk)

    def python():
        with ingest.ingest_burst():
            ops, consumed, torn, _tr = parse_wal_chunk_py(chunk,
                                                          final=True)
        assert len(ops) == n and torn == 0 and consumed == len(chunk)

    _, t_nat = _trials(native, 5)
    _, t_py = _trials(python, 3)
    med, extras = _spread(t_nat, n)
    rate = n / med
    emit("wal_ingest_native_ops_per_sec", rate, "ops/s",
         rate / (n / _median(t_py)),  # vs_baseline IS the ratio
         python_ops_per_sec=round(n / _median(t_py), 1),
         chunk_mb=round(len(chunk) / 2 ** 20, 1), **extras)


def cfg_multikey():
    """BASELINE config 3: independent per-key registers, 1k ops each,
    batched on device. Values are drawn from a 5-value domain like the
    reference's linearizable-register workload (``(rand-int 5)``); the
    measured baseline is the CPU oracle checking the same keys
    sequentially (the host execution model).

    Emits the 64-key config (r1/r2 comparability) AND the batch-scaling
    curve at 256/1024 keys — the matrix path splits big batches into
    pipelined ≤256-key sub-dispatches, so the win opens with batch size
    (VERDICT r2 item 2). The CPU side is measured DIRECTLY at every
    batch size (r3 weak #3 closed: no linear extrapolation; big sizes
    take fewer trials to bound the added wall time)."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.parallel import batch_check

    all_streams = [encode_register_ops(
        _register_history(1000, n_procs=N_PROCS, seed=1000 + k, n_values=5))
        for k in range(1024)]

    def cpu_n(n):
        for s in all_streams[:n]:
            assert check_stream(s).valid is True

    from jepsen_tpu.parallel import pipeline

    for nk, main, cpu_trials in ((64, True, 3), (256, False, 2),
                                 (1024, False, 2)):
        streams = all_streams[:nk]
        _, cpu_times = _trials(lambda: cpu_n(nk), cpu_trials)
        dt_cpu = min(cpu_times)  # noisy host: best run is the fair anchor
        _warm_timed(f"multikey_{nk}x1k",            # warm-up compile
                    lambda: batch_check(streams, capacity=CAPACITY))
        results, times = _trials(
            lambda: batch_check(streams, capacity=CAPACITY), 3)
        assert all(r[0] and not r[2] for r in results)
        med, extras = _spread(times, nk * 1000)
        name = ("multikey_64x1k_ops_per_sec" if main
                else f"multikey_{nk}x1k_ops_per_sec")
        try:
            n_rets = sum(int((np.asarray(s.kind) == 1).sum())
                         for s in streams)
            extras.update(matrix_roofline_extras(
                n_rets, streams[0].n_slots, len(streams[0].intern), med))
        except Exception:
            print("[bench] roofline add-on failed:", file=sys.stderr)
            traceback.print_exc()
        # dispatch-pipeline occupancy (the overlap evidence for the
        # small-batch fix): stats of the last trial's pipeline
        ps = pipeline.last_stats()
        if ps.get("queue") == "matrix":
            extras.update(
                pipeline_batches=ps["batches"],
                pipeline_inflight_peak=ps["inflight_peak"],
                pipeline_overlap_frac=ps["overlap_frac"],
                pipeline_stall_s=ps["stall_s"])
            # ... and into the bench_summary telemetry block, so the
            # occupancy evidence survives the driver's stdout tail
            _stage_note(f"multikey_{nk}x1k",
                        pipeline={k: ps[k] for k in
                                  ("batches", "inflight_peak",
                                   "overlap_frac", "stall_s", "sync_s")})
        emit(name, nk * 1000 / med, "ops/s", dt_cpu / med,
             cpu_sequential_ops_per_sec=round(nk * 1000 / dt_cpu, 2),
             cpu_trials=cpu_trials, **extras)


def cfg_set_full():
    """BASELINE config 4: membership-matrix kernel vs CPU walk."""
    from jepsen_tpu.checker import SetFullChecker

    n_els, read_every = 20_000, 50
    history, present = [], []
    t = 0
    for v in range(n_els):
        history.append({"type": "invoke", "process": v % 5, "f": "add",
                        "value": v, "time": t})
        history.append({"type": "ok", "process": v % 5, "f": "add",
                       "value": v, "time": t + 1})
        present.append(v)
        t += 2
        if (v + 1) % read_every == 0:
            history.append({"type": "invoke", "process": 5, "f": "read",
                            "value": None, "time": t})
            history.append({"type": "ok", "process": 5, "f": "read",
                            "value": list(present), "time": t + 1})
            t += 2
    test, opts = {}, {}
    dev = SetFullChecker(accelerator="tpu")
    cpu = SetFullChecker(accelerator="cpu")
    _warm_timed("set_full", lambda: dev.check(test, history, opts))
    # per-trial kernel-only time (setscan.last_kernel_seconds): the
    # hbm_frac roofline divides bytes moved by DEVICE time, not the
    # whole stage (which is mostly host history parse)
    from jepsen_tpu.ops import setscan
    kernel_times: list[float] = []

    def dev_phased():
        out = dev.check(test, history, opts)
        kernel_times.append(setscan.last_kernel_seconds())
        return out

    r_dev, t_dev = _trials(dev_phased, 5)
    r_cpu, t_cpu = _trials(lambda: cpu.check(test, history, opts), 5)
    assert r_dev["valid?"] and r_cpu["valid?"]
    assert r_dev["stable-count"] == r_cpu["stable-count"]
    med, extras = _spread(t_dev, n_els)
    cpu_med, _ = _spread(t_cpu, n_els)
    try:
        n_reads = n_els // read_every
        mb = setscan.modeled_bytes(n_reads, n_els)
        k_med = _median(kernel_times)
        bw = device_roofline()["hbm_bytes_per_sec"]
        extras.update(
            modeled_hbm_bytes=mb,
            kernel_seconds=round(k_med, 4),
            hbm_frac=round((mb / max(k_med, 1e-9)) / bw, 4))
    except Exception:
        print("[bench] set-full roofline add-on failed:", file=sys.stderr)
        traceback.print_exc()
    emit("set_full_elements_per_sec", n_els / med, "elements/s",
         cpu_med / med, cpu_elements_per_sec=round(n_els / cpu_med, 2),
         **extras)


def _elle_history(n_txns: int, n_keys: int = 100, crossed_pairs: int = 0):
    """Serializable list-append history; ``crossed_pairs`` appends pairs
    of mutually-observing txns (wr edges both ways → G1c 2-cycles), which
    defeats the acyclicity screen and forces the trim + cycle search."""
    history = []
    t = 0

    def txn(proc, mops_inv, mops_ok):
        nonlocal t
        history.append({"type": "invoke", "process": proc,
                        "value": mops_inv, "time": t})
        history.append({"type": "ok", "process": proc,
                        "value": mops_ok, "time": t + 1})
        t += 2

    for i in range(n_txns):
        k = i % n_keys
        seen = list(range(k, i + 1, n_keys))  # every append to k so far
        txn(i % 10, [["append", k, i], ["r", k, None]],
            [["append", k, i], ["r", k, seen]])
    for p in range(crossed_pairs):
        ka, kb = 10_000 + 2 * p, 10_001 + 2 * p
        va, vb = 2_000_000 + 2 * p, 2_000_001 + 2 * p
        # A observes B's append before B commits; B observes A's: a wr
        # cycle between the two on fresh keys
        txn(10, [["append", ka, va], ["r", kb, None]],
            [["append", ka, va], ["r", kb, [vb]]])
        txn(11, [["append", kb, vb], ["r", ka, None]],
            [["append", kb, vb], ["r", ka, [va]]])
    return history


def cfg_elle_50k():
    """BASELINE config 5: 50k-txn list-append check. Two regimes: a
    serializable history (settled by the vectorized acyclicity screen —
    the production fast path) and an anomalous one with 50 injected wr
    cycles (forces the SCC trim + exact cycle search on both backends)."""
    from jepsen_tpu.elle import list_append

    n_txns = 50_000
    history = _elle_history(n_txns)
    # warm caches on a tail WITH the same anomaly count so the φ-cluster
    # screen kernel compiles at the anomalous run's exact bucket shapes
    # (the valid tail alone never reaches it: no back edges, no clusters)
    warm = _elle_history(2_000, crossed_pairs=50)
    _warm_timed("elle_50k", lambda: list_append.check(warm, accelerator="tpu"))
    # 5 trials: the build is host-bound (C parser + numpy tail) and this
    # shared VM's ambient noise swung 3-trial medians by 40%+ between
    # clean runs. Per-trial phase split on BOTH regimes (r4 weak #1: the
    # clean-path regression was unattributable without it) — build is
    # the host-side history parse, cycles is the device screen + search.
    from jepsen_tpu.elle import columnar
    from jepsen_tpu.native import columnar_c

    def phased(h, phases):
        def run():
            out = list_append.check(h, accelerator="tpu")
            phases.append(dict(columnar.LAST_PHASE_SECONDS))
            return out
        return run

    r_cpu, t_cpu = _trials(
        lambda: list_append.check(history, accelerator="cpu"), 5)
    clean_phases: list[dict] = []
    r_dev, t_dev = _trials(phased(history, clean_phases), 5)
    assert r_dev["valid?"] is True and r_cpu["valid?"] is True
    med, extras = _spread(t_dev, n_txns)
    cpu_med, _ = _spread(t_cpu, n_txns)
    emit("elle_50k_txns_per_sec", n_txns / med, "txns/s",
         cpu_med / med, cpu_txns_per_sec=round(n_txns / cpu_med, 2),
         trial_seconds=[round(t, 2) for t in t_dev],
         phase_build_s=[p.get("build") for p in clean_phases],
         phase_cycles_s=[p.get("cycles") for p in clean_phases],
         c_parser=columnar_c.available(),
         **extras)

    # stored-column re-check: the same verdict straight off the
    # history.npz elle_* sidecar — no jsonl, no PyObject parse (the
    # analyze/re-check path for saved runs, SURVEY §7's
    # struct-of-arrays stance carried to its conclusion)
    cols = columnar.parse_columns(history)
    if cols is not None:
        r_cols = columnar.check_columns(cols, accelerator="tpu")  # warm
        assert r_cols["valid?"] is True
        stored_phases: list[dict] = []

        def stored_run():
            out = columnar.check_columns(cols, accelerator="tpu")
            stored_phases.append(dict(columnar.LAST_PHASE_SECONDS))
            return out

        _, t_cols = _trials(stored_run, 5)
        med_c, extras_c = _spread(t_cols, n_txns)
        # phase_build_s reduction: the object path's host build vs the
        # stored/IR array path's — the 7:1 build-dominance trend
        # (BENCH_r04) tracked release over release
        build_obj = _median(sorted(p.get("build") or 0.0
                                   for p in clean_phases))
        build_arr = _median(sorted(p.get("build") or 0.0
                                   for p in stored_phases))
        emit("elle_50k_stored_columns_txns_per_sec", n_txns / med_c,
             "txns/s", cpu_med / med_c,
             object_path_txns_per_sec=round(n_txns / med, 2),
             phase_build_s=[p.get("build") for p in stored_phases],
             phase_build_reduction=round(build_obj / max(build_arr, 1e-4),
                                         2),
             **extras_c)

    bad = _elle_history(n_txns, crossed_pairs=50)
    n_bad = n_txns + 100
    r_cpu, t_cpu = _trials(
        lambda: list_append.check(bad, accelerator="cpu"), 5)
    # the 2k-txn warm above covers the clean path only: the anomalous
    # 50k run compiles the cluster screen/search at ITS bucket shapes,
    # and that one-time ~16 s compile was landing inside trial 0 (r5
    # measured phase_cycles_s[0]=15.9 vs 0.13 steady) — warm it out
    _warm_timed("elle_50k_anomalous",
                lambda: list_append.check(bad, accelerator="tpu"))
    phases: list[dict] = []
    r_dev, t_dev = _trials(phased(bad, phases), 5)
    assert r_dev["valid?"] is False and r_cpu["valid?"] is False
    assert "G1c" in r_dev["anomaly-types"], r_dev.get("anomaly-types")
    med, extras = _spread(t_dev, n_bad)
    cpu_med, _ = _spread(t_cpu, n_bad)
    emit("elle_50k_anomalous_txns_per_sec", n_bad / med, "txns/s",
         cpu_med / med, cpu_txns_per_sec=round(n_bad / cpu_med, 2),
         trial_seconds=[round(t, 2) for t in t_dev],
         phase_build_s=[p.get("build") for p in phases],
         phase_cycles_s=[p.get("cycles") for p in phases],
         **extras)


def cfg_ir_amortization():
    """The history-IR encode-once contract: two checkers over the SAME
    50k-op register history through one shared IR. first_encode_s is
    the IR build + the first checker's view derivation; the second
    checker's encode phase is a memo hit and must be ~zero (the
    acceptance bar for ROADMAP item 3 / ISSUE 11). Both checkers then
    actually run (Compose-style shared test map) so the sharing is the
    production code path, not a synthetic probe."""
    from __graft_entry__ import _register_history
    from jepsen_tpu import history_ir
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.history_ir import views

    n = 50_000
    history = _register_history(n, n_procs=N_PROCS, seed=11)
    test = {"name": "bench-ir"}

    t0 = time.perf_counter()
    ir = history_ir.of(test, history)
    stream = views.register_stream(ir)      # first checker's encode
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = views.register_stream(ir)       # second checker's encode
    second_s = time.perf_counter() - t0
    assert again is stream, "second checker re-encoded: memo broken"

    # the real two-checker path: both checks share the test map's IR
    c1 = LinearizableChecker(accelerator="cpu")
    c2 = LinearizableChecker(accelerator="cpu")
    t0 = time.perf_counter()
    r1 = c1.check(test, history, {})
    wall_1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    r2 = c2.check(test, history, {})
    wall_2 = time.perf_counter() - t0
    assert r1["valid?"] is True and r2["valid?"] is True
    assert test.get("_history_ir") is ir, "checkers didn't share the IR"

    emit("ir_encode_amortization", second_s * 1000.0, "ms",
         first_s / max(second_s, 1e-9),
         first_encode_s=round(first_s, 4),
         second_encode_s=round(second_s, 6),
         checker_wall_first_s=round(wall_1, 3),
         checker_wall_second_s=round(wall_2, 3),
         ops=n)


def cfg_matrix_kernel():
    """Block-composed transfer-matrix kernel on its home regime — long
    history, small value domain — vs the event-by-event dense scan."""
    import jax
    from jepsen_tpu.checker.linear_encode import pad_streams
    from jepsen_tpu.ops.jitlin import (
        JitLinKernel, _bucket, matrix_check, matrix_ok)

    stream = _block_stream(12_800, n_values=4)   # 128k events, V=5
    E = len(stream)
    S, V = stream.n_slots, len(stream.intern)
    n_returns = int((np.asarray(stream.kind) == 1).sum())
    assert matrix_ok(S, V, n_returns), "bench config must be in-regime"

    m = _warm_timed("matrix_kernel",              # warm-up compile
                    lambda: matrix_check(stream))
    assert m is not None and m[0] and not m[2], m
    # per-trial host/device phase split (r5 weak #1: the 17.6%-of-peak
    # single-dispatch fraction was unattributable): prepass/grids are
    # host encode, dispatch is the async kernel call, fetch is the
    # device compute + readback wait
    from jepsen_tpu.ops import jitlin as jitlin_mod
    phase_trials: list[dict] = []

    def matrix_phased():
        out = matrix_check(stream)
        phase_trials.append(jitlin_mod.last_phase_seconds())
        return out

    m, t_matrix = _trials(matrix_phased, 5)
    dt_matrix, extras = _spread(t_matrix, E)
    try:
        from jepsen_tpu.ops.jitlin import _matrix_plan, last_dispatch_info
        Vb = _bucket(V, 8)
        C_plan, _T = _matrix_plan(1, S, n_returns, Vb, None)
        extras.update(telemetry.matrix_phase_model(
            n_returns, S, Vb, C_plan, 1))
        for ph in ("prepass", "grids", "dispatch", "fetch"):
            vals = sorted(p.get(ph, 0.0) for p in phase_trials)
            extras[f"phase_{ph}_s"] = vals[len(vals) // 2]
        # combine-stage HBM share + routing labels: which kernel
        # representation and combine path the dispatch actually ran
        # (probe-selected — "scan"/"tree" on backends without pallas),
        # and the modeled combine traffic over wall time and measured
        # bandwidth. The tree/fused byte ratio is the fused combine's
        # designed win; both are on record so a routing regression is
        # visible in one diff.
        info = last_dispatch_info()
        MV = (1 << S) * Vb
        fused = info.get("combine") == "fused"
        bw = device_roofline()["hbm_bytes_per_sec"]
        cb = telemetry.combine_modeled_hbm_bytes(1, C_plan, MV, fused)
        extras.update(
            matrix_variant=info.get("variant", "unknown"),
            combine_path=info.get("combine", "unknown"),
            combine_modeled_hbm_bytes=cb,
            combine_hbm_frac=round((cb / dt_matrix) / bw, 6),
            combine_fused_reduction=_combine_reduction(
                1, C_plan, MV, fused))
        from jepsen_tpu.ops import pallas_matrix
        extras["pallas_probe_seconds"] = round(
            pallas_matrix.probe_seconds(), 4)
    except Exception:
        print("[bench] phase attribution failed:", file=sys.stderr)
        traceback.print_exc()

    # per-variant attribution (ISSUE 12): each representation measured
    # through the SAME production dispatch with the variant pinned —
    # probe-gated, so on a backend where a variant can't run the
    # `*_ran` label records what actually executed instead of lying
    # with a zero
    try:
        from jepsen_tpu.ops import pallas_matrix
        from jepsen_tpu.ops.jitlin import last_dispatch_info
        for v in pallas_matrix.VARIANTS:
            _, t_v = _trials(lambda v=v: matrix_check(stream, variant=v), 2)
            dt_v = min(t_v)
            ran = last_dispatch_info().get("variant", "unknown")
            extras[f"events_per_sec_{v}"] = round(E / dt_v, 2)
            extras[f"roofline_frac_{v}"] = matrix_roofline_extras(
                n_returns, S, V, dt_v)["roofline_frac"]
            extras[f"variant_ran_{v}"] = ran
    except Exception:
        print("[bench] per-variant attribution failed:", file=sys.stderr)
        traceback.print_exc()

    batch = pad_streams([stream], length=_bucket(E))
    run = JitLinKernel()._get(S, CAPACITY, batched=False, num_states=V)
    args = _device_args(batch, V)
    _warm_timed("matrix_kernel_scan",             # warm-up compile
                lambda: _force(*run(*args)))
    out, t_scan = _trials(lambda: _force(*run(*args)), 5)
    alive, _, ovf, _ = out
    dt_scan, _ = _spread(t_scan, E)
    assert bool(alive) and not bool(ovf)
    assert bool(m[0]) == bool(alive), "matrix and scan verdicts must agree"
    extra = {"scan_events_per_sec": round(E / dt_scan, 2), **extras}
    try:
        extra.update(matrix_roofline_extras(n_returns, S, V, dt_matrix))
        # the scan path is event-sequential and bandwidth-bound: bound
        # it against measured HBM read+write of its P state per event
        bw = device_roofline()["hbm_bytes_per_sec"]
        MV = (1 << S) * V
        scan_bytes = 2.0 * MV * MV * 4          # P read + write, f32
        extra["scan_hbm_frac"] = round(
            (E / dt_scan) * scan_bytes / bw, 4)
    except Exception:
        print("[bench] roofline add-on failed:", file=sys.stderr)
        traceback.print_exc()

    # failing-history double run: a not-alive matrix verdict falls back to
    # the event scan for diagnostics — measure that total so the cost of
    # the two-pass failure path is on record (VERDICT r1 weak #7). Run
    # guarded AFTER the primary measurement exists, so a failure here
    # can't discard it.
    try:
        from dataclasses import replace
        t = (E // (2 * N_PROCS)) // 2
        a_bad = stream.a.copy()
        e_corrupt = t * 2 * N_PROCS + 1     # block t, proc 1's read invoke
        a_bad[e_corrupt] = (t + 1) % 4 + 1  # neither w_{t-1} nor w_t
        bad = replace(stream, a=a_bad)
        t0 = time.perf_counter()
        mb = matrix_check(bad)
        assert mb is not None and not mb[0]
        batch_bad = pad_streams([bad], length=_bucket(E))
        alive_b, _, _, _ = _force(*run(*_device_args(batch_bad, V)))
        dt_fail = time.perf_counter() - t0
        assert not bool(alive_b)
        extra["failing_double_run_seconds"] = round(dt_fail, 3)
    except Exception:
        print("[bench] failing-path add-on failed:", file=sys.stderr)
        traceback.print_exc()
    emit("matrix_kernel_128k_events_per_sec", E / dt_matrix, "events/s",
         dt_scan / dt_matrix, **extra)


def cfg_explain():
    """explain_latency_128k: anomaly forensics (device localization +
    witness shrink, checker/explain.py) on a planted-anomaly 128k-event
    history. The bar is < 2× the PLAIN matrix check's wall time —
    forensics must stay in the same cost class as the verdict they
    explain, or nobody runs them (vs_baseline = 2×check / explain; ≥ 1
    is under the bar). Steady-state like every quick config: the one
    warm-up explain compiles the forensics kernels (products + prefix
    scan + the ddmin candidate buckets its deterministic round sequence
    touches)."""
    from dataclasses import replace

    from jepsen_tpu.checker.explain import explain_stream
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.ops.jitlin import matrix_check

    stream = _block_stream(12_800, n_values=4)   # 128k events, V=5
    E = len(stream)
    # plant the anomaly the way cfg_matrix_kernel's failing path does:
    # one read observes a value that is neither w_{t-1} nor w_t
    t = (E // (2 * N_PROCS)) // 2
    a_bad = stream.a.copy()
    a_bad[t * 2 * N_PROCS + 1] = (t + 1) % 4 + 1
    bad = replace(stream, a=a_bad)

    m = _warm_timed("explain_check", lambda: matrix_check(bad))
    assert m is not None and not m[0] and not m[2], m
    _, t_check = _trials(lambda: matrix_check(bad), 3)
    check_med = _median(t_check)

    f = _warm_timed("explain", lambda: explain_stream(bad))
    assert f is not None, "planted anomaly must localize"
    # differential anchor: the device bisection must land on the exact
    # CPU frontier rejection (one CPU pass, outside the trials)
    cpu = check_stream(bad)
    assert f["first_anomaly"]["event"] == cpu.failed_event, (
        f["first_anomaly"], cpu.failed_event)
    results, t_explain = _trials(lambda: explain_stream(bad), 3)
    explain_med = _median(t_explain)
    emit("explain_latency_128k", explain_med, "s",
         (2.0 * check_med) / max(explain_med, 1e-9),
         check_seconds=round(check_med, 4),
         first_anomaly_op=results["first_anomaly"]["op_index"],
         witness_ops=len(results["witness"]["op_indices"]),
         bisect_steps=results["bisect_steps"],
         shrink_candidates=results["witness"]["candidates"],
         trials=len(t_explain))


def cfg_scale(device_rate: float):
    """North-star scaling metric: the largest single logical history
    verified on device inside the 300 s budget.

    Runs as a CHAIN of ~1M-event segments through the transfer-matrix
    kernel with the composed operator product carried on device between
    them (jitlin.matrix_check_resume): each segment is generated fresh
    with a continuing block offset, its returns compose as [MV, MV] MXU
    matmuls, and the product chains — one contiguous valid history on the
    faithful rand-int-5 domain, verified end to end, ~300k events/s per
    segment. Segmentation is what lets the run spend the WHOLE budget:
    r2's monolithic 8M+-event dispatches crashed the TPU worker, so it
    stopped at a 4.19M stability cap; bounded dispatches sidestep that
    entirely. (Large
    domains out of the matrix regime take the same segment chain through
    the event-scan kernels' frontier carry — jitlin.segmented_check.) A
    segment failure is caught and named, and the total verified so far (a
    sound prefix verdict) is still reported."""
    from jepsen_tpu.ops.jitlin import matrix_check_resume

    target_s = float(os.environ.get("BENCH_SCALE_TARGET_S", "280"))
    if target_s <= 0:
        return
    SEG_E = 1 << 20                      # ~1M events: well under the
    #                                      monolithic-dispatch crash size,
    #                                      fine-grained enough to respect
    #                                      the budget within one segment
    # faithful small domain (the register workload's rand-int 5 → values
    # 0..4): each return composes one [MV, MV] operator on the MXU, and
    # the segment carry is the composed product — the matrix kernel's
    # home regime
    n_values = 5
    seg_blocks = SEG_E // (2 * N_PROCS)
    seg_events = seg_blocks * 2 * N_PROCS

    def seg_stream(k):
        return _block_stream(seg_blocks, n_values=n_values,
                             start_block=k * seg_blocks)

    def dispatch(k, tot):
        return matrix_check_resume(seg_stream(k), tot, n_slots=N_PROCS,
                                   num_states=n_values + 1)

    # compile + warm outside the budget at both carry shapes (the first
    # call carries the identity, later calls the previous device total)
    a0, ix0, warm_tot = dispatch(0, None)
    a1, ix1, _ = dispatch(1, warm_tot)
    a1, ix1 = _force(a1, ix1)
    assert bool(np.asarray(a1).all()) and not bool(np.asarray(ix1).any())

    # one-deep pipeline: dispatch segment k (async), THEN sync segment
    # k-1 — so segment k's host generation + prepass + grid transfer
    # overlap segment k-1's device compute. The tot carry chains as a
    # lazy device array, no sync needed between dispatches.
    # budget discipline (r3 weak #1): a segment COUNTS only if its sync
    # completed with elapsed <= target_s. A sync that straggles past the
    # budget (r3 caught one 262 s sync after ~2 s steady state) is
    # reported separately, never counted.
    total_events = 0
    segments = 0
    failure = None
    tot = None
    pending = None
    seg_times: list = []
    counted_at = 0.0          # elapsed when the last counted sync landed
    overflow = None           # the uncounted straggler, if any
    t_start = time.perf_counter()

    def sync_counts(p):
        """Forces p; returns True iff it verified AND landed in budget."""
        nonlocal total_events, segments, counted_at, overflow
        pa, pix = _force(*p)
        assert bool(np.asarray(pa).all())
        assert not bool(np.asarray(pix).any())
        elapsed = time.perf_counter() - t_start
        if elapsed <= target_s:
            total_events += seg_events
            segments += 1
            counted_at = elapsed
            return True
        overflow = {"events": seg_events,
                    "synced_at_seconds": round(elapsed, 1)}
        return False

    k = 0
    while True:
        elapsed = time.perf_counter() - t_start
        # next-segment estimate: MEDIAN of recent segments, not max — a
        # single stall (r4 observed 112 s against a 1.2 s steady
        # state) would otherwise poison the estimate and abandon the
        # rest of the budget after the stall clears; straddling syncs
        # never count anyway, so optimism here is budget-safe
        recent = seg_times[-5:]
        est = _median(recent) if recent else 0.0
        if elapsed >= target_s or elapsed + est >= target_s:
            break
        try:
            t0 = time.perf_counter()
            alive, inexact, tot = dispatch(k, tot)
            k += 1
            if pending is not None and not sync_counts(pending):
                pending = None
                break  # budget blown mid-sync: stop dispatching
            pending = (alive, inexact)
            seg_times.append(round(time.perf_counter() - t0, 1))
        except Exception as e:  # noqa: BLE001 — name the failure, keep prefix
            failure = f"{type(e).__name__}: {e}"
            print(f"[bench] scale segment {segments} failed: {failure}",
                  file=sys.stderr)
            traceback.print_exc()
            pending = None
            break
    if pending is not None:
        try:
            sync_counts(pending)
        except Exception as e:  # noqa: BLE001
            failure = f"{type(e).__name__}: {e}"
    wall = time.perf_counter() - t_start
    if total_events:
        ts = sorted(seg_times)
        med_seg = _median(ts) if ts else 0.0
        extra = {"measured_seconds": round(counted_at, 1),
                 "wall_seconds": round(wall, 1), "segments": segments,
                 "segment_events": seg_events,
                 "segment_seconds_median": med_seg,
                 "segment_seconds_max": max(ts) if ts else 0.0,
                 "value_domain": n_values,
                 "path": "matrix-segmented",
                 "events_per_sec": round(total_events / max(counted_at, 1e-9),
                                         1)}
        if ts and max(ts) > 5 * max(med_seg, 0.1):
            extra["stall"] = (f"stall: worst segment "
                              f"{max(ts)}s vs median {med_seg}s")
        try:
            # fused-combine attribution for the segmented path: the
            # routing the chain's dispatches actually took, and the
            # modeled tree/fused HBM-byte ratio the fusion delivers
            # (1.0 = tree combine ran — the regression signal)
            from jepsen_tpu.ops.jitlin import (
                _bucket as _bk, _matrix_plan as _mp, last_dispatch_info)
            info = last_dispatch_info()
            Vb = _bk(n_values + 1, 8)
            MVs = (1 << N_PROCS) * Vb
            Cs, _Ts = _mp(1, N_PROCS, seg_events // 2, Vb, None)
            fused = info.get("combine") == "fused"
            extra["combine_path"] = info.get("combine", "unknown")
            extra["matrix_variant"] = info.get("variant", "unknown")
            extra["combine_fused_reduction"] = _combine_reduction(
                1, Cs, MVs, fused)
        except Exception:
            print("[bench] combine attribution failed:", file=sys.stderr)
            traceback.print_exc()
        if overflow:
            extra["uncounted_overflow_segment"] = overflow
        if failure:
            extra["failure"] = failure
        try:
            # returns = half the events (invoke/return block pairs)
            extra.update(matrix_roofline_extras(
                total_events // 2, N_PROCS, n_values + 1, counted_at))
        except Exception:
            print("[bench] roofline add-on failed:", file=sys.stderr)
            traceback.print_exc()
        # full per-segment timings to stderr only (they once pushed the
        # metric lines out of the driver's 2000-char stdout tail)
        print(f"[bench] scale segment_seconds={seg_times}", file=sys.stderr)
        emit("max_history_len_checked_300s", total_events, "events",
             total_events / N_OPS, **extra)
    else:
        # nothing counted — name WHY (a first-segment stall is
        # sync work that verified late, not a silent no-op)
        print(f"[bench] scale run counted nothing: failure={failure} "
              f"overflow={overflow} wall={round(wall, 1)}s",
              file=sys.stderr)


def _multichip_measure(counts=(1, 2, 4, 8)) -> dict:
    """In-process multichip measurement: events/s of the segmented
    transfer-matrix path (matrix_check_resume chain) at each mesh width,
    plus the host's independent-dispatch ceiling at the widest. Small
    faithful shapes (3-way concurrency, rand-int-5 domain → MV = 64) so
    the CPU mesh finishes inside a bench stage; the mechanism, padding,
    collectives, and per-device staging are exactly the production
    path's."""
    import jax

    from jepsen_tpu.ops import jitlin
    from jepsen_tpu.parallel import get_mesh

    n_procs, n_values = 3, 5
    V = n_values + 1
    seg_events = int(os.environ.get("BENCH_MULTICHIP_SEG_EVENTS",
                                    str(1 << 15)))
    n_segs = int(os.environ.get("BENCH_MULTICHIP_SEGMENTS", "3"))
    seg_blocks = max(1, seg_events // (2 * n_procs))
    streams = [_block_stream(seg_blocks, n_procs=n_procs,
                             n_values=n_values, start_block=k * seg_blocks)
               for k in range(n_segs)]
    E = sum(len(s.kind) for s in streams)
    n_dev = len(jax.devices())
    counts = [c for c in counts if c <= n_dev]
    rates: dict[int, float] = {}
    for nd in counts:
        mesh = get_mesh(nd) if nd > 1 else None

        def chain():
            tot = None
            for s in streams:
                a, ix, tot = jitlin.matrix_check_resume(
                    s, tot, n_slots=n_procs, num_states=V, mesh=mesh)
            assert bool(np.asarray(a).all()), f"nd={nd}: chain not alive"
            assert not bool(np.asarray(ix).any()), f"nd={nd}: inexact"

        _warm_timed(f"multichip_{nd}dev", chain)   # compile + one execute
        t0 = time.perf_counter()
        chain()
        rates[nd] = E / (time.perf_counter() - t0)
        print(f"[bench] multichip nd={nd}: {rates[nd]:,.0f} events/s",
              file=sys.stderr, flush=True)
    top = max(rates)
    ceiling = _independent_dispatch_ceiling(n_procs, n_values, top)
    speedup = rates[top] / rates[min(rates)]
    # efficiency vs what the host can actually deliver: ideal scaling is
    # min(N, the measured embarrassingly-parallel ceiling) — on real
    # N-device hardware the ceiling is ~N and this degrades to the
    # classic speedup/N; on a virtual CPU mesh (one shared host, XLA
    # serializing cross-device executions) raw /N would only measure the
    # container's core count, not the sharding mechanism
    # (doc/performance.md "Multi-device sharding").
    eff = speedup / max(1.0, min(float(top), ceiling))
    return {"events_per_sec": {str(k): round(v, 1)
                               for k, v in rates.items()},
            "speedup_top": round(speedup, 3),
            "top_devices": top,
            "host_parallel_ceiling": round(ceiling, 3),
            "scaling_efficiency_8dev": round(eff, 3),
            "segments": n_segs, "segment_events": seg_blocks * 2 * n_procs,
            "platform": jax.default_backend()}


def _independent_dispatch_ceiling(n_procs: int, n_values: int,
                                  nd: int) -> float:
    """Measured embarrassingly-parallel ceiling: aggregate speedup of
    ``nd`` INDEPENDENT single-device dispatches of the same compiled
    matrix kernel (one per device, zero collectives) over one. This is
    the upper bound ANY sharding of this workload can reach on this
    host, so it is the honest denominator for scaling efficiency."""
    import jax

    from jepsen_tpu.ops import jitlin

    V = n_values + 1
    blocks = max(1, int(os.environ.get("BENCH_MULTICHIP_CEIL_EVENTS",
                                       str(1 << 13))) // (2 * n_procs))
    s = _block_stream(blocks, n_procs=n_procs, n_values=n_values)
    prep = jitlin._returns_prepass(
        np.asarray(s.kind), np.asarray(s.slot), np.asarray(s.f),
        np.asarray(s.a), np.asarray(s.b))
    S = max(n_procs, prep[3])
    R = prep[0].shape[0]
    Vb = jitlin._bucket(V, floor=8)
    C, T = jitlin._matrix_plan(1, S, R, Vb, None)
    grids, uops = jitlin._matrix_grids([prep], S, Vb, 1, C, T, None)
    run = jitlin._matrix_cache(S, Vb, jitlin._default_step_ids(), 0, T, C)
    devs = jax.devices()[:nd]
    args = [[jax.device_put(g, d) for g in grids]
            + [jax.device_put(uops, d)] for d in devs]
    for ar in args:  # compile once, then one warm execute per device
        jax.block_until_ready(run(ar[0], ar[1], ar[4], ar[2], ar[3]))

    def once(n: int) -> float:
        t0 = time.perf_counter()
        outs = [run(ar[0], ar[1], ar[4], ar[2], ar[3]) for ar in args[:n]]
        jax.block_until_ready(outs)
        return time.perf_counter() - t0

    t1 = min(once(1) for _ in range(3))
    tn = min(once(len(devs)) for _ in range(2))
    return len(devs) * t1 / max(tn, 1e-9)


def cfg_multichip_scaling():
    """multichip_scaling: events/s of the segmented path at 1/2/4/8
    devices, plus scaling_efficiency_8dev — the regression guard for the
    multi-device data plane (ROADMAP item 1). Self-provisions an
    8-virtual-CPU-device subprocess when this process cannot supply 8
    devices (the dryrun_multichip recipe: env BEFORE jax import)."""
    in_proc = False
    if "jax" in sys.modules:
        import jax
        try:
            in_proc = len(jax.devices()) >= 8
        except Exception:  # noqa: BLE001 — backend unreachable: child
            in_proc = False
    if in_proc:
        data = _multichip_measure()
    else:
        import subprocess
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # replace (not just append) any pre-existing forced count — a
        # site XLA_FLAGS pinning =4 would otherwise shrink the mesh and
        # the metric would be an 8dev label over a 4-device measurement
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--multichip-child"],
            capture_output=True, text=True, timeout=480, env=env)
        if out.returncode != 0:
            raise RuntimeError(
                f"multichip child failed (rc {out.returncode}):\n"
                f"{out.stderr[-2000:]}")
        data = json.loads(out.stdout.strip().splitlines()[-1])
    rates = {int(k): v for k, v in data["events_per_sec"].items()}
    top = data["top_devices"]
    eff = data["scaling_efficiency_8dev"]
    emit("multichip_scaling", rates[top], "events/s",
         data["speedup_top"],
         events_per_sec_by_devices=data["events_per_sec"],
         host_parallel_ceiling=data["host_parallel_ceiling"],
         segments=data["segments"],
         segment_events=data["segment_events"],
         value_domain=5, n_procs=3, platform=data["platform"],
         path="matrix-segmented-sharded",
         in_process=in_proc)
    emit("scaling_efficiency_8dev", eff, "frac", eff,
         top_devices=top,
         host_parallel_ceiling=data["host_parallel_ceiling"],
         methodology="speedup vs max(1, min(N, measured independent-"
                     "dispatch ceiling)); classic speedup/N on real "
                     "N-device hardware")


def cfg_online_lag():
    """online_checker_lag: sustained ingest rate of the live checking
    path (doc/observability.md "Live checking") — WAL tail (offset
    reader + JSON parse) -> incremental register encode -> resumable
    frontier — with a verdict poll after every chunk, and the worst
    verdict lag observed at any poll. The target shape is the
    acceptance bar: >= 1M ops/s sustained at bounded lag (raised from
    100k by the host ingest spine — native tail+parse, chunked
    ``add_many`` encode, GC deferred per burst)."""
    import tempfile
    from pathlib import Path

    from __graft_entry__ import _register_history
    from jepsen_tpu.history_ir import ingest as ingest_mod
    from jepsen_tpu.journal import Journal, WalTailer
    from jepsen_tpu.live.sessions import LinearLiveSession

    n = 100_000
    chunk = 20_000  # one verdict poll per chunk bounds the lag
    # 3-way concurrency: the live path's steady-state shape (a serving
    # fleet's per-key streams are narrow; wide frontiers are the batch
    # checker's province — and the budget/admission machinery's, not
    # this throughput bar's)
    history = _register_history(n, n_procs=3, seed=7, n_values=5)
    with tempfile.TemporaryDirectory() as tmp:
        wal = Path(tmp) / "history.wal.jsonl"
        j = Journal(wal, fsync_interval_s=-1)
        for op in history:
            j.append(op)
        j.close()

        def consume():
            tailer = WalTailer(wal)
            session = LinearLiveSession(accelerator="cpu")
            lag_max = 0
            with ingest_mod.ingest_burst():
                ops = tailer.poll()
            assert len(ops) == len(history), len(ops)
            for i in range(0, len(ops), chunk):
                with ingest_mod.ingest_burst():
                    session.add_many(ops[i:i + chunk])
                v = session.verdict()
                assert v["valid_so_far"] is True, v
                lag_max = max(lag_max,
                              session.ops_absorbed - v["checked_ops"])
            session.finalize()
            return lag_max

        lag_max, times = _trials(consume, 5)

        # checker-side sustained rate (pre-parsed ops): isolates the
        # incremental encode+frontier from the JSON tail
        parsed = WalTailer(wal).poll()

        def check_only():
            session = LinearLiveSession(accelerator="cpu")
            for i in range(0, len(parsed), chunk):
                with ingest_mod.ingest_burst():
                    session.add_many(parsed[i:i + chunk])
                session.verdict()
            session.finalize()

        _, check_times = _trials(check_only, 3)
    med, extras = _spread(times, len(history))
    rate = len(history) / med
    emit("online_checker_lag", rate, "ops/s", rate / 1_000_000.0,
         lag_ops_max=int(lag_max), chunk_ops=chunk, n_ops=n,
         path="tail+encode+frontier",
         native_ingest=ingest_mod.enabled(),
         check_ops_per_sec=round(len(history) / min(check_times), 1),
         **extras)


def _fleet_measure():
    """100 concurrent synthetic runs shipped over loopback HTTP into
    one FleetDaemon, with one mesh shrink + one regrow cycle injected
    mid-flight. Returns the raw measurement dict (also the
    --fleet-child stdout payload)."""
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    import jax

    from __graft_entry__ import _register_history
    from jepsen_tpu import parallel
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    from jepsen_tpu.fleet.scheduler import FleetDaemon
    from jepsen_tpu.fleet.ship import Shipper
    from jepsen_tpu.journal import Journal
    from jepsen_tpu.live.daemon import load_live_status

    n_runs = 100
    ops_per_run = 120
    histories = {f"r{i:03d}": _register_history(
        ops_per_run, n_procs=3, seed=i, n_values=5)
        for i in range(n_runs)}
    reg = telemetry.Registry()
    # regrow_mesh/shrink_mesh count on the process-global registry
    prev = telemetry.install(reg)
    tmp = tempfile.mkdtemp(prefix="fleet-bench-")
    worst_lag = 0.0
    try:
        src = Path(tmp) / "src"
        store = Path(tmp) / "fleet"
        fd = FleetDaemon(store, port=0, poll_s=0.05,
                         ingest_budget_s=0.5, max_runs=n_runs + 8,
                         accelerator="cpu", registry=reg,
                         regrow_backoff_s=0.05)
        fd.start()
        t0 = time.perf_counter()

        def one(ts, h):
            # ship WHILE producing — the live-shipping shape; a run
            # landing already complete is post-hoc territory
            rd = src / "bench" / ts
            rd.mkdir(parents=True)
            j = Journal(rd / "history.wal.jsonl", fsync_interval_s=-1)
            j.append(h[0])
            sh = Shipper(rd, f"http://127.0.0.1:{fd.port}",
                         poll_s=0.02)
            shipped = []
            st = threading.Thread(
                target=lambda: shipped.append(sh.run(timeout_s=240)),
                daemon=True)
            st.start()
            born = time.monotonic()
            for op in h[1:]:
                j.append(op)
                time.sleep(0.0005)
            j.close()
            # keep the run live for a few discovery polls before the
            # final lands — a run that completes inside one poll is
            # (correctly) post-hoc territory, not the pool's; polls
            # stretch toward ingest_budget_s with 100 runs tracked
            time.sleep(max(0.0, 2.0 - (time.monotonic() - born)))
            with open(rd / "history.jsonl", "w") as f:
                for op in h:
                    f.write(json.dumps(op) + "\n")
            st.join(240)
            if shipped != [True]:
                raise RuntimeError(f"run {ts} never finalized")

        threads = [threading.Thread(target=one, args=(ts, h),
                                    daemon=True)
                   for ts, h in histories.items()]
        for t in threads:
            t.start()

        # one shrink + one regrow cycle mid-flight: fail a device the
        # way a collective error would, then let the fleet daemon's
        # heal probe regrow the mesh
        time.sleep(0.3)
        devs = jax.devices()
        mesh = parallel.auto_mesh() if len(devs) >= 2 else None
        if mesh is not None and int(mesh.devices.size) >= 2:
            casualty = list(mesh.devices.flat)[-1].id
            parallel.shrink_mesh(mesh, RuntimeError(
                f"UNAVAILABLE: device {casualty} lost mid collective"))

        def lag_gauge():
            return reg.gauge("fleet_worst_lag_ops",
                             "largest per-run checker lag across "
                             "the pool").value()

        for t in threads:
            while t.is_alive():
                t.join(0.1)
                worst_lag = max(worst_lag, lag_gauge())
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline and fd.daemon.trackers:
            worst_lag = max(worst_lag, lag_gauge())
            time.sleep(0.05)
        elapsed = time.perf_counter() - t0
        if fd.daemon.trackers:
            raise RuntimeError(
                f"pool never settled {len(fd.daemon.trackers)} runs")
        fd.stop()

        snap = reg.snapshot()

        def ctr(name):
            return sum(r["value"] for r in snap if r["name"] == name)

        # fleet verdicts must be bit-identical to local analyze over
        # the same histories
        mismatches = 0
        invalid = 0
        for ts, h in histories.items():
            status = load_live_status(store / "bench" / ts)
            if status is None or status.get("state") != "final":
                raise RuntimeError(f"run {ts} has no final status")
            local = LinearizableChecker(
                accelerator="cpu").check({}, h, {})
            mismatches += status["valid_so_far"] is not local["valid?"]
            invalid += status["valid_so_far"] is False
        if mismatches:
            raise RuntimeError(
                f"{mismatches} fleet verdicts diverged from local "
                "analyze")
        total_ops = n_runs * ops_per_run
        return {"runs": n_runs, "ops_total": total_ops,
                "ops_per_sec": round(total_ops / elapsed, 1),
                "wall_s": round(elapsed, 2),
                "worst_lag_ops": int(worst_lag),
                "shrinks": int(ctr("mesh_shrink_total")),
                "regrows": int(ctr("mesh_regrow_total")),
                "ingest_bytes": int(ctr("fleet_ingest_bytes_total")),
                "ingest_rejected": int(
                    ctr("fleet_ingest_rejected_total")),
                "invalid_runs": invalid,
                "n_devices": len(devs)}
    finally:
        telemetry.install(prev)
        with parallel._HEALTH_LOCK:
            parallel._FAILED_DEVICES.clear()
        shutil.rmtree(tmp, ignore_errors=True)


def cfg_fleet_runs_sustained():
    """fleet_runs_sustained: sustained ops/s through the full fleet
    plane — 100 concurrent synthetic runs shipping WALs over loopback
    HTTP into one ingest receiver while the pool daemon live-checks
    them all — with one mesh shrink + one regrow cycle injected
    mid-flight (doc/observability.md "Fleet plane"). Guards bounded
    worst live_checker_lag_ops, verdict parity against local analyze
    on the same WALs, and zero ingest rejections on the happy path.
    Self-provisions an 8-virtual-CPU-device subprocess when this
    process cannot supply >= 2 devices (the shrink/regrow leg needs a
    mesh that can narrow and widen)."""
    in_proc = False
    if "jax" in sys.modules:
        import jax
        try:
            in_proc = len(jax.devices()) >= 2
        except Exception:  # noqa: BLE001 — backend unreachable: child
            in_proc = False
    if in_proc:
        data = _fleet_measure()
    else:
        import subprocess
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+",
                       "", env.get("XLA_FLAGS", ""))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--fleet-child"],
            capture_output=True, text=True, timeout=480, env=env)
        if out.returncode != 0:
            raise RuntimeError(
                f"fleet child failed (rc {out.returncode}):\n"
                f"{out.stderr[-2000:]}")
        data = json.loads(out.stdout.strip().splitlines()[-1])
    rate = data["ops_per_sec"]
    # the bar: >= 2k ops/s sustained over network ingest with lag
    # bounded by the admission budget's working set
    emit("fleet_runs_sustained", rate, "ops/s", rate / 2_000.0,
         runs=data["runs"], ops_total=data["ops_total"],
         wall_s=data["wall_s"], worst_lag_ops=data["worst_lag_ops"],
         mesh_shrinks=data["shrinks"], mesh_regrows=data["regrows"],
         ingest_bytes=data["ingest_bytes"],
         ingest_rejected=data["ingest_rejected"],
         invalid_runs=data["invalid_runs"],
         n_devices=data["n_devices"], in_process=in_proc,
         verdict_parity="bit-identical to local analyze")


def cfg_fleet_failover():
    """fleet_failover: kill the ACTIVE pool host under live shipped
    load and measure what HA actually costs (doc/robustness.md "Fleet
    HA"). Real OS processes — the receiver and both leased pool hosts
    are the fleet-chaos harness's child roles — with pool0 holding
    every lease when it is SIGKILLed:

    * ``fleet_failover_adoption_s`` — wall from the kill to the
      standby holding a lease on EVERY in-flight run. Bar: <= 2x the
      lease TTL (one TTL for the lease to expire, one for the
      standby's discovery/claim cadence).
    * ``fleet_failover_recheck_frac`` — fraction of the runs already
      settled before the kill that any host finalized AGAIN
      afterwards. Bar: <= 0.1 (the design says 0: a final verdict is
      durable and discovery skips it; the 10% headroom is for a
      verdict racing the kill itself).
    """
    import shutil
    import tempfile
    import threading
    from pathlib import Path

    from __graft_entry__ import _register_history
    from jepsen_tpu.fleet.chaos import _Child, _free_port
    from jepsen_tpu.fleet.ship import Shipper
    from jepsen_tpu.journal import WAL_NAME, Journal, read_jsonl_tolerant
    from jepsen_tpu.live.daemon import load_live_status

    ttl = float(os.environ.get("BENCH_FAILOVER_TTL_S", "1.0"))
    n_pre = int(os.environ.get("BENCH_FAILOVER_PRE_RUNS", "6"))
    n_live = int(os.environ.get("BENCH_FAILOVER_LIVE_RUNS", "6"))
    ops_per_run = 120
    deadline_s = 120.0
    reg = telemetry.Registry()
    tmp = tempfile.mkdtemp(prefix="fleet-failover-")
    root = Path(tmp)
    fleet = root / "fleet"
    src = root / "src"
    fleet.mkdir()
    src.mkdir()
    port = _free_port()
    receiver = _Child(fleet, "receiver",
                      ["--store", str(fleet), "--port", str(port)],
                      "failover-receiver.log")
    pool0 = _Child(fleet, "pool",
                   ["--store", str(fleet), "--host-id", "pool0",
                    "--ttl", str(ttl)], "failover-pool0.log")
    pool1 = _Child(fleet, "pool",
                   ["--store", str(fleet), "--host-id", "pool1",
                    "--ttl", str(ttl)], "failover-pool1.log")
    release_finals = threading.Event()
    threads: list[threading.Thread] = []

    def lease_host(key):
        try:
            with open(fleet / key / "check.lease",
                      encoding="utf-8") as f:
                return json.load(f).get("host")
        except (OSError, ValueError):
            return None

    def start_run(key, history, hold_final):
        """Producer + shipper for one run; ``hold_final`` gates the
        history.jsonl write on release_finals so the run stays live
        (tailing) until the conductor has measured adoption."""
        rd = src / key
        rd.mkdir(parents=True)

        def produce():
            j = Journal(rd / WAL_NAME, fsync_interval_s=-1)
            for op in history:
                j.append(op)
            j.close()
            if hold_final:
                release_finals.wait(deadline_s)
            else:
                # hold the final until the pool LEASED the run: a
                # history.jsonl landing before the pool's first poll
                # makes it post-hoc territory (discovery skips it) and
                # there'd be no settled verdict to survive the kill
                end = time.monotonic() + deadline_s
                while time.monotonic() < end and lease_host(key) is None:
                    time.sleep(0.02)
            with open(rd / "history.jsonl", "w", encoding="utf-8") as f:
                for op in history:
                    f.write(json.dumps(op) + "\n")

        sh = Shipper(rd, f"http://127.0.0.1:{port}", poll_s=0.02,
                     registry=reg)
        tp = threading.Thread(target=produce, daemon=True)
        ts = threading.Thread(
            target=lambda: sh.run(timeout_s=deadline_s), daemon=True)
        tp.start()
        ts.start()
        threads.extend([tp, ts])

    def await_final(keys, budget):
        end = time.monotonic() + budget
        pending = set(keys)
        while pending and time.monotonic() < end:
            for key in sorted(pending):
                st = load_live_status(fleet / key)
                if st is not None and st.get("state") == "final":
                    pending.discard(key)
            time.sleep(0.05)
        if pending:
            raise RuntimeError(f"failover runs never settled: "
                               f"{sorted(pending)}")

    pre_keys = [f"fob/p{i:02d}" for i in range(n_pre)]
    live_keys = [f"fob/l{i:02d}" for i in range(n_live)]
    try:
        receiver.spawn()
        pool0.spawn()
        # phase A: settle a population under pool0 — the runs whose
        # verdicts must SURVIVE the kill un-rechecked
        for i, key in enumerate(pre_keys):
            start_run(key, _register_history(ops_per_run, n_procs=3,
                                             seed=i, n_values=5),
                      hold_final=False)
        await_final(pre_keys, deadline_s)
        # phase B: live runs; pool0 must hold every lease before the
        # kill so the kill provably hits the ACTIVE host
        for i, key in enumerate(live_keys):
            start_run(key, _register_history(ops_per_run, n_procs=3,
                                             seed=100 + i, n_values=5),
                      hold_final=True)
        end = time.monotonic() + deadline_s
        while time.monotonic() < end and any(
                lease_host(k) != "pool0" for k in live_keys):
            time.sleep(0.05)
        assert all(lease_host(k) == "pool0" for k in live_keys)
        pool1.spawn()  # standby: sees pool0's live leases, claims none
        time.sleep(max(2 * 0.05, ttl / 4))

        t_kill = time.monotonic()
        pool0.kill()
        adopted: set = set()
        adoption_s = None
        end = time.monotonic() + deadline_s
        while time.monotonic() < end:
            for k in live_keys:
                if k not in adopted and lease_host(k) == "pool1":
                    adopted.add(k)
            if len(adopted) == len(live_keys):
                adoption_s = time.monotonic() - t_kill
                break
            time.sleep(0.02)
        if adoption_s is None:
            raise RuntimeError(
                f"standby adopted {len(adopted)}/{n_live} runs within "
                f"{deadline_s}s")
        release_finals.set()
        for t in threads:
            t.join(deadline_s)
        await_final(live_keys, deadline_s)
    finally:
        release_finals.set()
        for child in (receiver, pool0, pool1):
            child.kill()

    rechecked = set()
    for f in sorted(fleet.glob("finals-*.jsonl")):
        rows, _ = read_jsonl_tolerant(f)
        for row in rows:
            key = str(row.get("key"))
            if key in pre_keys and row.get("host") == "pool1":
                rechecked.add(key)
    recheck_frac = len(rechecked) / max(n_pre, 1)
    snap = reg.snapshot()
    resyncs = {r["labels"].get("reason"): int(r["value"])
               for r in snap if r["name"] == "fleet_ship_resyncs_total"}
    shutil.rmtree(tmp, ignore_errors=True)

    emit("fleet_failover_adoption_s", adoption_s, "s",
         (2.0 * ttl) / max(adoption_s, 1e-6),
         lease_ttl_s=ttl, live_runs=n_live, settled_pre=n_pre,
         ship_resyncs=resyncs, killed_host="pool0",
         adopter="pool1")
    emit("fleet_failover_recheck_frac", recheck_frac, "frac",
         0.1 / max(recheck_frac, 1e-6),
         rechecked=sorted(rechecked), settled_pre=n_pre,
         lease_ttl_s=ttl)


def cfg_membership_resolve():
    """membership_resolve_latency: full reconfiguration cycles per
    second through the membership scenario machinery — durable registry
    record (fsynced, pre-op member set + heal spec), State invoke
    (fsynced members file), and the locked resolve fixed point with its
    heal-mark. This is the per-op overhead a membership nemesis adds to
    a run; the bar is 150 cycles/s (~6.7 ms/cycle — three fsyncs per
    cycle dominate on the container's disk, and one reconfig per ~10 s
    of test time needs ~0.07% of a worker)."""
    import tempfile
    from pathlib import Path

    from jepsen_tpu.fakes import FakeClusterState
    from jepsen_tpu.nemesis import membership
    from jepsen_tpu.nemesis.faults import FaultRegistry

    nodes = [f"n{i}" for i in range(1, 6)]
    n_cycles = 200

    def cycle_all():
        with tempfile.TemporaryDirectory() as tmp:
            st = FakeClusterState(Path(tmp) / "members.json", nodes=nodes,
                                  settle_s=0.0)
            nem = membership.MembershipNemesis(st, poll_interval=3600)
            registry = FaultRegistry(Path(tmp) / "faults.jsonl")
            test = {"nodes": nodes, "_faults": registry}
            for i in range(n_cycles):
                f = "shrink" if i % 2 == 0 else "grow"
                nem.invoke(test, {"type": "info", "f": f, "value": "n5"})
            assert nem.pending_count() == 0
            assert registry.unhealed() == []
            registry.close()

    cycle_all()  # warm imports/allocators
    _, times = _trials(cycle_all, 3)
    med, extras = _spread(times, n_cycles)
    rate = n_cycles / med
    emit("membership_resolve_latency", rate, "cycles/s", rate / 150.0,
         cycle="record+invoke+resolve+heal", n_cycles=n_cycles,
         per_cycle_ms=round(1000.0 * med / n_cycles, 3), **extras)


def cfg_ckpt():
    """Resumable-check cost/benefit (doc/robustness.md "Resumable
    checks and the elastic mesh"), riding the segmented 300s metric's
    path at a bench-friendly scale:

    * ``ckpt_overhead_frac`` — segmented matrix chain with a durable
      checkpoint persisted after EVERY segment (interval 0: the
      worst-case write cadence; production's default is one write per
      5 s) vs the plain chain. Bar: <= 5% overhead.
    * ``resume_savings_frac`` — the same chain resumed from a
      checkpoint at the 50% cut vs checked from zero. The checkpoint
      is authored through the same carry/fingerprint machinery the
      checker uses, so the resumed run exercises real validation
      (hash + config match), not a mock.
    """
    import tempfile
    from pathlib import Path

    from jepsen_tpu.checker.checkpoint import (
        CheckpointStore, encode_array, stream_prefix_hash,
    )
    from jepsen_tpu.ops.jitlin import (
        _bucket, _slice_stream, matrix_check_segmented,
        matrix_segmented_config,
    )

    # multichip-bench shapes (3-way concurrency, rand-int-5 domain →
    # MV = 64): big enough to segment, small enough that the CPU
    # container's matrix kernel finishes the trial matrix promptly
    n_procs, n_values = 3, 5
    seg_events = int(os.environ.get("BENCH_CKPT_SEG_EVENTS",
                                    str(1 << 13)))
    n_segs = int(os.environ.get("BENCH_CKPT_SEGMENTS", "6"))
    seg_blocks = seg_events // (2 * n_procs)
    seg_events = seg_blocks * 2 * n_procs
    stream = _block_stream(seg_blocks * n_segs, n_procs=n_procs,
                           n_values=n_values)
    kw = dict(num_states=n_values + 1, n_slots=n_procs,
              max_segment=seg_events)

    def plain():
        a, _, ix, _ = matrix_check_segmented(stream, **kw)
        assert a and not ix

    _warm_timed("ckpt", plain)
    _, t_plain = _trials(plain, 3)
    wall_plain = _median(t_plain)

    with tempfile.TemporaryDirectory() as tmp:
        def with_ckpt():
            store = CheckpointStore(Path(tmp) / "check.ckpt",
                                    interval_s=0.0, resume=False)
            a, _, ix, _ = matrix_check_segmented(stream, ckpt=store,
                                                 **kw)
            assert a and not ix
            assert store.writes >= n_segs - 1, store.writes

        _, t_ckpt = _trials(with_ckpt, 3)
        wall_ckpt = _median(t_ckpt)

        # author a 50%-cut checkpoint through the real carry machinery
        half = seg_blocks * (n_segs // 2) * 2 * n_procs
        carries = []
        a, _, ix, _ = matrix_check_segmented(
            _slice_stream(stream, 0, half), carry_sink=carries.append,
            **kw)
        assert a and not ix and carries
        S, V = n_procs, _bucket(n_values + 1, floor=8)
        resume_path = Path(tmp) / "resume.ckpt"
        CheckpointStore(resume_path, resume=True).save({
            "kind": "matrix",
            "config": matrix_segmented_config(S, V, 0, n_values + 1,
                                              seg_events, None, None),
            "events_done": half, "segment": n_segs // 2,
            "prefix_hash": stream_prefix_hash(stream, half),
            "carry": {"tot0": encode_array(np.asarray(
                carries[-1]["tot0"]))},
        })

        def resumed():
            store = CheckpointStore(resume_path, interval_s=None,
                                    resume=True)
            a2, _, ix2, _ = matrix_check_segmented(stream, ckpt=store,
                                                   **kw)
            assert a2 and not ix2

        _warm_timed("ckpt_resume", resumed)
        _, t_res = _trials(resumed, 3)
        wall_res = _median(t_res)

    overhead = max(0.0, wall_ckpt / max(wall_plain, 1e-9) - 1.0)
    savings = max(0.0, 1.0 - wall_res / max(wall_plain, 1e-9))
    emit("ckpt_overhead_frac", overhead, "frac",
         0.05 / max(overhead, 1e-6),
         plain_wall_s=round(wall_plain, 4),
         ckpt_wall_s=round(wall_ckpt, 4), segments=n_segs,
         segment_events=seg_events, write_cadence="every-segment",
         path="matrix-segmented")
    emit("resume_savings_frac", savings, "frac", savings / 0.33,
         full_wall_s=round(wall_plain, 4),
         resumed_wall_s=round(wall_res, 4), resume_cut_frac=0.5,
         path="matrix-segmented")


def cfg_trace():
    """trace_overhead_frac: the causal trace's cost on the hot path
    (doc/observability.md "Causal trace") — the REAL generator
    interpreter (threads, queues, deadlines) over the standard register
    workload, measured three ways:

    * untraced — NULL tracer (the default run minus the flight
      recorder): the anchor;
    * flight-recorder only — the always-on default configuration; bar
      <= 1% over the anchor;
    * causal trace — streaming Perfetto trace.json sink + flight
      recorder (the run-wide span stream this subsystem adds); bar
      <= 5%.

    The pre-existing per-client span log (tracing.py's trace.jsonl +
    TracedClient, which ``--trace`` also turns on) is measured
    separately as ``client_span_overhead_frac`` — it predates the
    causal trace and its cost must not hide inside (or be blamed on)
    the new stream's number.

    Best-of-N trials on both sides: the interpreter's wall is
    thread-scheduling noisy, and the overhead question is about the
    added per-op work, which the best runs isolate."""
    import tempfile
    from pathlib import Path

    import jepsen_tpu.generator as gen
    from jepsen_tpu import trace as trace_mod
    from jepsen_tpu import tracing
    from jepsen_tpu.fakes import AtomClient, AtomDB, noop_test
    from jepsen_tpu.generator import interpreter

    n = int(os.environ.get("BENCH_TRACE_OPS", "4000"))
    trials = 5

    def build(wrap=None):
        db = AtomDB()
        client = AtomClient(db)
        if wrap is not None:
            client = wrap(client)
        return noop_test(
            name="bench-trace", db=db, client=client, concurrency=5,
            checker=None,
            generator=gen.clients(gen.limit(n, gen.mix([
                gen.repeat({"f": "read"}),
                lambda test, ctx: {"f": "write",
                                   "value": ctx.rng.randrange(5)},
            ]))))

    def measure(make_tracer, wrap=None) -> float:
        best = float("inf")
        for _ in range(trials):
            test = build(wrap)
            tracer = make_tracer()
            with trace_mod.use(tracer):
                t0 = time.perf_counter()
                history = interpreter.run(test)
                dt = time.perf_counter() - t0
            tracer.close()
            n_inv = sum(1 for op in history if op["type"] == "invoke")
            assert n_inv == n, n_inv
            best = min(best, dt)
        return best

    with tempfile.TemporaryDirectory() as tmp:
        t_plain = measure(lambda: trace_mod.NULL_TRACER)
        t_flight = measure(lambda: trace_mod.RunTracer(
            flight=trace_mod.FlightRecorder(
                trace_mod.DEFAULT_FLIGHT_EVENTS)))
        runs = [0]

        def traced_tracer():
            runs[0] += 1
            return trace_mod.RunTracer(
                perfetto=trace_mod.PerfettoSink(
                    Path(tmp) / f"trace-{runs[0]}.json"),
                flight=trace_mod.FlightRecorder(
                    trace_mod.DEFAULT_FLIGHT_EVENTS))

        t_traced = measure(traced_tracer)

        legacy = tracing.Tracer(str(Path(tmp) / "trace.jsonl"))
        t_client = measure(lambda: trace_mod.NULL_TRACER,
                           wrap=lambda c: tracing.TracedClient(c, legacy))
        legacy.close()

    overhead = max(0.0, t_traced / max(t_plain, 1e-9) - 1.0)
    flight_overhead = max(0.0, t_flight / max(t_plain, 1e-9) - 1.0)
    client_overhead = max(0.0, t_client / max(t_plain, 1e-9) - 1.0)
    emit("trace_overhead_frac", overhead, "frac",
         0.05 / max(overhead, 1e-6),
         flight_overhead_frac=round(flight_overhead, 4),
         client_span_overhead_frac=round(client_overhead, 4),
         untraced_wall_s=round(t_plain, 4),
         flight_wall_s=round(t_flight, 4),
         traced_wall_s=round(t_traced, 4),
         client_span_wall_s=round(t_client, 4),
         ops=n, trials=trials,
         untraced_ops_per_sec=round(n / t_plain, 1),
         traced_ops_per_sec=round(n / t_traced, 1))


def cfg_lint():
    """lint_wall_s: full-tree static-analysis wall clock — the cost of
    the tier-1 self-lint gate (tests/test_lint_clean.py) with every
    rule enabled, including the interprocedural thread-edge call graph,
    lock-order deadlock detection, and durability-protocol passes. The
    bar: < 60 s cold (fresh AST cache), < 30 s warm (the steady-state
    cost every tier-1 run actually pays). A regression here silently
    eats the tier-1 budget, so it gets a metric line like any kernel.
    ``vs_baseline`` is bar/actual for the warm number (>1 = under
    bar)."""
    from pathlib import Path

    from jepsen_tpu.analysis import lint as lint_mod
    from jepsen_tpu.analysis.lint import astcache, csrc

    root = Path(__file__).resolve().parent
    pkg = root / "jepsen_tpu"

    def run():
        rep = lint_mod.lint_paths([str(pkg)],
                                  baseline=str(root / "lint-baseline.txt"),
                                  root=str(root))
        assert rep.findings == [], [f.render() for f in rep.findings]
        return rep

    astcache._CACHE.clear()
    csrc._CACHE.clear()
    t0 = time.perf_counter()
    rep = run()
    cold_s = time.perf_counter() - t0
    _, times = _trials(run, 3)
    warm_s = _median(times)
    assert cold_s < 60.0, f"cold full-tree lint took {cold_s:.1f}s"
    assert warm_s < 30.0, f"warm full-tree lint took {warm_s:.1f}s"
    emit("lint_wall_s", warm_s, "s", 30.0 / max(warm_s, 1e-9),
         cold_s=round(cold_s, 2), files=rep.files,
         rules=len(lint_mod.RULE_NAMES), trials=len(times))

    # the JTN family alone over the shipped C sources — the acceptance
    # bar is < 10 s warm for the native rule pass
    def run_native():
        rep = lint_mod.lint_paths([str(pkg / "native")], baseline=False,
                                  root=str(root), rules=["jtn-*"])
        assert rep.findings == [], [f.render() for f in rep.findings]
        return rep

    csrc._CACHE.clear()
    t0 = time.perf_counter()
    nrep = run_native()
    n_cold_s = time.perf_counter() - t0
    _, ntimes = _trials(run_native, 3)
    n_warm_s = _median(ntimes)
    assert n_warm_s < 10.0, f"warm native lint took {n_warm_s:.1f}s"
    emit("lint_native_wall_s", n_warm_s, "s", 10.0 / max(n_warm_s, 1e-9),
         cold_s=round(n_cold_s, 3), files=nrep.files,
         rules=len(lint_mod.C_RULES), trials=len(ntimes))


def cfg_fuzz():
    """fuzz_trials_per_sec + fuzz_coverage_edges_per_1k_trials: the
    schedule fuzzer's throughput and its guidance signal. Two hunts at
    an identical 300-trial budget over a bug-free target (inline pool,
    no early stop): one coverage-guided, one blind-random. Throughput
    is the guided hunt's trials/wall. The guidance bar rides the DEEP
    edges — fault×op interleavings whose active mask composes >= 3
    fault kinds, the class the corpus splicer exists to reach (blind
    triple-overlaps are rare by construction): guided must find >= 2x
    the blind count at equal trials. ``vs_baseline`` on the edges
    metric is ratio/2 (>1 = over bar). Fully deterministic given the
    seed, so the ratio is a regression pin, not a flake."""
    import shutil
    import tempfile

    from jepsen_tpu.fuzz.hunt import Hunter

    trials, seed = 300, 1

    def deep(edges):
        # "op:<kind+kind+...>:<f>" edges with a 3-way composed mask
        return sum(1 for e in edges
                   if e.startswith("op:")
                   and len(e.split(":")[1].split("+")) >= 3)

    tmp = tempfile.mkdtemp(prefix="jepsen-bench-fuzz-")
    try:
        res = {}
        for mode in ("guided", "blind"):
            h = Hunter(os.path.join(tmp, mode), trials=trials,
                       pool_workers=0, trial_ops=120, seed=seed,
                       guided=(mode == "guided"), bug_spec=None,
                       batch_size=25, stop_on_first=False)
            t0 = time.perf_counter()
            summary = h.run()
            wall = time.perf_counter() - t0
            assert summary["trials"] == trials, summary
            assert summary["outcomes"].get("error", 0) == 0, (
                f"{mode} hunt hit errored trials: {summary['outcomes']}")
            res[mode] = {"wall": wall, "edges": set(h.covmap.edges)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    g, b = res["guided"], res["blind"]
    g_deep, b_deep = deep(g["edges"]), deep(b["edges"])
    ratio = g_deep / max(b_deep, 1)
    assert ratio >= 2.0, (
        f"guided found {g_deep} deep edges vs blind {b_deep} at "
        f"{trials} trials — guidance bar is >= 2x")
    trials_per_sec = trials / g["wall"]
    emit("fuzz_trials_per_sec", trials_per_sec, "trials/s",
         trials_per_sec / 20.0, trials=trials, seed=seed,
         guided_wall_s=round(g["wall"], 2),
         blind_wall_s=round(b["wall"], 2))
    emit("fuzz_coverage_edges_per_1k_trials",
         len(g["edges"]) * 1000.0 / trials, "edges/1k",
         ratio / 2.0, deep_edges_guided=g_deep, deep_edges_blind=b_deep,
         edges_guided=len(g["edges"]), edges_blind=len(b["edges"]),
         guided_vs_blind_deep_ratio=round(ratio, 2))


def cfg_fuzz_native():
    """fuzz_native_execs_per_sec: the differential WAL-parser fuzz
    harness's throughput against the plain -O3 build (the san build's
    ~2-5x tax is the lane's, not the harness's), plus corpus coverage —
    every checked-in seed and every mutation operator must have fired
    within the budget (a silently dead operator means a coverage hole,
    not a perf win). Zero divergences is an assertion, not a metric:
    a C-vs-Python disagreement fails the bench like any broken kernel.
    Deterministic under the fixed seed."""
    import shutil
    import tempfile

    from jepsen_tpu.fuzz import native as fuzz_native

    execs, seed = 4000, 1
    tmp = tempfile.mkdtemp(prefix="jepsen-bench-fuzz-native-")
    try:
        res = fuzz_native.run_fuzz(execs, seed=seed, san=False,
                                   store_dir=tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if res["status"] == "no-native":
        print("[bench] fuzz_native skipped: no native build", flush=True)
        return
    assert res["divergences"] == 0, res["artifacts"]
    seeds_hit = len(res["seed_coverage"])
    ops_hit = len(res["operator_coverage"])
    assert seeds_hit == len(fuzz_native.SEEDS), res["seed_coverage"]
    assert ops_hit == len(fuzz_native.OPERATORS), res["operator_coverage"]
    rate = res["execs_per_s"]
    emit("fuzz_native_execs_per_sec", rate, "execs/s", rate / 1000.0,
         execs=res["execs"], seed=seed,
         corpus_seeds_covered=seeds_hit,
         operators_covered=ops_hit,
         ops_parsed=res["ops_parsed"], torn_lines=res["torn_lines"],
         wall_s=round(res["elapsed_s"], 2))


def cfg_headline() -> float:
    """The headline, printed last: a 10k-op single-register history on
    device vs the reference's 1 h CPU knossos timeout.

    The history uses the reference workload's value domain —
    linearizable_register.clj writes ``(rand-int 5)`` — and the
    measurement takes the PRODUCTION dispatch (checker/linearizable.py
    device path): the block-composed transfer-matrix kernel settles the
    small-domain verdict exactly, with the event scan kept as the
    diagnostics path. r1-r2 measured the event scan over an unfaithful
    100-value domain; the scan number stays in the extras for
    continuity. Returns the measured device event rate (drives the scale
    config default)."""
    from __graft_entry__ import _register_history
    from jepsen_tpu.checker.linear_encode import encode_register_ops, pad_streams
    from jepsen_tpu.checker.linearizable import device_algorithm
    from jepsen_tpu.ops.jitlin import (JitLinKernel, _bucket, matrix_check,
                                       verdict)

    history = _register_history(N_OPS, n_procs=N_PROCS, seed=42, n_values=5)
    stream = encode_register_ops(history)

    m = _warm_timed("headline",                   # warm-up compile
                    lambda: matrix_check(stream))
    assert m is not None and m[0] and not m[2], (
        "10k-op valid small-domain history must verify on the matrix path")
    _, times = _trials(lambda: matrix_check(stream), 5)
    dt, extras = _spread(times, N_OPS)

    # continuity extra: the event-scan path on the same history
    batch = pad_streams([stream], length=_bucket(len(stream)))
    S = max(1, batch["n_slots"])
    run = JitLinKernel()._get(S, CAPACITY, batched=False,
                              num_states=len(stream.intern))
    args = _device_args(batch, len(stream.intern))
    _warm_timed("headline_scan", lambda: _force(*run(*args)))
    out, scan_times = _trials(lambda: _force(*run(*args)), 5)
    alive, died, ovf, peak = out
    assert verdict(bool(alive), bool(ovf)) is True, (
        f"10k-op valid history must verify (died at event {int(died)}, "
        f"overflow={bool(ovf)})")
    scan_dt, _ = _spread(scan_times, N_OPS)

    ops_per_sec = N_OPS / dt
    emit("single_register_ops_verified_per_sec_10k", ops_per_sec, "ops/s",
         ops_per_sec / BASELINE_OPS_PER_SEC, value_domain=5,
         algorithm=device_algorithm("-matrix"),
         scan_ops_per_sec=round(N_OPS / scan_dt, 2), **extras)
    return len(stream) / dt


def main() -> None:
    global _TELEMETRY_ON
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    skip = set(filter(None, os.environ.get("BENCH_SKIP", "").split(",")))
    # stage telemetry (compile_s/wall_s/device_peak_mb) uses module
    # helpers only — no registry: bench stages call the kernels directly,
    # below the instrumented checker/interpreter dispatch layers
    _TELEMETRY_ON = "telemetry" not in skip
    device_rate = 50_000.0  # headline's event rate sizes the scaling run
    from jepsen_tpu import compile_cache
    compile_cache.enable()
    failed: list[str] = []

    def guard(name, fn):
        if name in skip:
            return None
        t0 = time.perf_counter()
        try:
            return fn()
        except Exception:
            print(f"[bench] {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
            failed.append(name)
            return None
        finally:
            if _TELEMETRY_ON:
                _stage_note(name, wall_s=round(time.perf_counter() - t0, 2))
                peak = telemetry.device_memory_peak_bytes()
                if peak is not None:
                    _stage_note(name,
                                device_peak_mb=round(peak / 2 ** 20, 1))

    guard("cpu_ref", cfg_cpu_ref_200)
    guard("interpreter_sched", cfg_interpreter_sched)
    guard("wal_ingest", cfg_wal_ingest)
    guard("multikey", cfg_multikey)
    guard("set_full", cfg_set_full)
    guard("elle_50k", cfg_elle_50k)
    guard("ir_amortization", cfg_ir_amortization)
    guard("online_lag", cfg_online_lag)
    guard("membership_resolve", cfg_membership_resolve)
    guard("matrix_kernel", cfg_matrix_kernel)
    guard("explain", cfg_explain)
    guard("multichip", cfg_multichip_scaling)
    guard("ckpt", cfg_ckpt)
    guard("trace", cfg_trace)
    guard("fleet", cfg_fleet_runs_sustained)
    guard("fleet_failover", cfg_fleet_failover)
    guard("lint", cfg_lint)
    guard("fuzz", cfg_fuzz)
    guard("fuzz_native", cfg_fuzz_native)
    device_rate = guard("headline", cfg_headline) or device_rate
    guard("scale", lambda: cfg_scale(device_rate))

    # all lines together at the end (driver tails stdout ~2000 chars);
    # headline last (the driver parses the final line), and a compact
    # every-metric summary right before it so even a short tail
    # recovers every value+ratio (r3 weak #5: verbose extras once
    # pushed 5 of 11 metrics out of the tail)
    headline = "single_register_ops_verified_per_sec_10k"
    summary = {"metric": "bench_summary",
               "all": {r["metric"]: [r["value"], r["vs_baseline"]]
                       for r in _RESULTS}}
    if _STAGE_TELEMETRY:
        summary["telemetry"] = _STAGE_TELEMETRY
    for line in [r for r in _RESULTS if r["metric"] != headline]:
        print(json.dumps(line), flush=True)
    print(json.dumps(summary), flush=True)
    for line in [r for r in _RESULTS if r["metric"] == headline]:
        print(json.dumps(line), flush=True)
    if failed:
        print(f"[bench] failed stages: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        sys.exit(1)


def _multichip_child() -> None:
    """Child-process entry for cfg_multichip_scaling: the parent set
    JAX_PLATFORMS=cpu + the forced-device-count flag BEFORE this
    interpreter started, so the child never touches the chip the parent
    holds; measure, print ONE json line."""
    print(json.dumps(_multichip_measure()), flush=True)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if "--multichip-child" in sys.argv:
        _multichip_child()
    elif "--fleet-child" in sys.argv:
        print(json.dumps(_fleet_measure()), flush=True)
    else:
        main()
