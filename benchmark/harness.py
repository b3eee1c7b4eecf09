"""One run of one cell: set-up, the measured window, the comparison.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
cell's configuration in ``configs/<config>.json``, its traffic mix in
``traffic/<traffic>.json``, the configuration's kind of history in
``checkers/<checker>.py`` (named by the configuration's ``checker`` key)
and each metric's reader in ``metrics/<metric>.py``. Adding a cell, a
configuration or a metric adds files; this module does not change.

The checker module is the one place that knows its kind of history.
It provides:

* ``mix(config, path)``: the traffic mix of the file ``path`` under the
  configuration; the harness reads only its ``pool`` (histories in the
  pool) and ``test`` (the test map each check is given);
* ``history(mix, seed, j)``: history ``j`` of the pool, made from the
  seed, as a ``traffic.Planted`` (the history and its plants, each a
  ``(key, kind, index)``);
* ``check(history, test)``: one check through the program under test;
* ``answer(result, history)``: the check's answer, a dict from key to
  ``(valid, report)`` (see compare.py);
* ``reference(history, which)``: the plain reference's answer
  (``which="reference"``) or the control's (``which="control"``), in
  the shape ``answer`` returns.

A run:

1. set-up (``setup_s``, from the start of the process's work): the
   compile and pallas-probe caches are placed inside the checkout, JAX is
   asked for the cell's chips (none, or too few: the run fails), the pool
   of histories is made from the seed, and the first history of each
   kind in the pool is checked once, which compiles (or loads from the
   cache) every program the window will run;
2. the window: checks in a closed loop, one at a time, cycling through
   the pool, each with a fresh test map; it closes at the end of the
   first pass over the pool that finishes at or after ``--seconds``;
3. after the window: the device memory peak is read, the program's
   state is let go, and the plain reference checks every distinct
   history the window checked. Each answer of the window is compared
   with it.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from pathlib import Path

from benchmark import compare

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
CACHE = HERE / ".cache"
SPEC = REPO / "BENCHMARK.json"

# a traced run's window is one pass over the pool: a trace of every check
# of a long window would take minutes and gigabytes to write, since every
# step of the frontier scan's while loop is an event.
TRACE_SECONDS = 0.0


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(f"[benchmark {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


@dataclass
class Bench:
    """BENCHMARK.json and the directories its names resolve in."""
    spec: dict
    root: Path = HERE
    traffic_dir: Path | None = None

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return json.loads((self.root / "configs" / f"{name}.json")
                          .read_text())

    def mix(self, cell: dict):
        tdir = self.traffic_dir or self.root / "traffic"
        return self.checker(cell).mix(self.config(cell["config"]),
                                      tdir / f"{cell['traffic']}.json")

    def _module(self, kind: str, name: str):
        """``<root>/<kind>/<name>.py``, loaded from its file (and entered
        in ``sys.modules``, where a dataclass of the module looks for
        its module)."""
        path = self.root / kind / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{kind}.{name}", path)
        if spec is None or not path.is_file():
            raise FileNotFoundError(f"no {kind} module {path}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        return mod

    def checker(self, cell: dict):
        return self._module("checkers",
                            self.config(cell["config"])["checker"])

    def metrics(self, cell: dict, trace: bool) -> list[dict]:
        """The metrics this cell reports in a run with or without the
        trace: the end-to-end ones, or the per-layer ones whose
        ``workloads`` name it (or that name no workloads)."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec[key]
                if cell["name"] in m.get("workloads", [cell["name"]])]

    def reader(self, metric: dict):
        return self._module("metrics", metric["name"])


def load(path: Path = SPEC) -> Bench:
    return Bench(json.loads(Path(path).read_text()))


@dataclass
class Check:
    """One check of the window, as the harness saw it."""
    j: int                  # pool index of the history checked
    ops: int
    seconds: float
    answer: dict | None = None
    error: str | None = None


@dataclass
class Run:
    """What a metric reader is given."""
    cell: dict
    mix: object                     # what checkers/<checker>.mix returns
    pool: list
    checks: list
    setup_s: float
    window_s: float
    device_kind: str
    trace: object = None            # tracefile.Summary with --trace 1


def setup_jax(persistent_cache: bool = True):
    """JAX with its persistent compile cache inside the checkout (or
    where ``JAX_COMPILATION_CACHE_DIR`` says) and every compile kept,
    and the pallas probe verdicts kept beside it, so that only the
    first run of a cell in a checkout compiles or probes."""
    if not persistent_cache:
        import jax
        return jax
    os.environ.setdefault("JEPSEN_CACHE_DIR", str(CACHE / "jepsen"))
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR",
                               str(CACHE / "jax"))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


class CompileCounter:
    """Backend compiles (a load from the persistent cache counts as one)
    and new persistent-cache entries, while entered."""

    def __init__(self):
        from jax._src import dispatch
        self.compiles = self.misses = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT

    def _on_duration(self, event, _secs, **_kw):
        if event == self._event:
            self.compiles += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

    def reset(self) -> None:
        self.compiles = self.misses = 0


def devices_for(jax, chips: int, require_tpu: bool) -> list:
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devices[0].platform}")
    if require_tpu and len(devices) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX sees "
                       f"{len(devices)}")
    return devices[:chips]


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


@contextmanager
def profiled(log_dir: Path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def warm_up(pool: list) -> list[int]:
    """The pool indices set-up checks: the first history of each kind
    (valid, or by the anomalies planted in it), which runs every program
    and path the window runs."""
    first: dict = {}
    for j, p in enumerate(pool):
        first.setdefault(tuple(kind for _, kind, _ in p.plants), j)
    return sorted(first.values())


def answers(chk, pool: list, needed, which: str) -> dict:
    """{pool index: answer} of the plain reference (or, ``which=
    "control"``, of the control) for every pool index in ``needed``."""
    return {j: chk.reference(pool[j].history, which) for j in sorted(needed)}


def run(bench: Bench, cell_name: str, seed: int, seconds: float,
        trace: bool, *, require_tpu: bool = True,
        persistent_cache: bool = True, control_checks: int = 0,
        t_start: float | None = None) -> dict:
    """One run of ``cell_name``; returns the result line as a dict.
    ``control_checks`` > 0 answers that many checks with the control in
    the program's place instead of measuring (its ``correct`` must come
    out false). Tests turn ``require_tpu`` and ``persistent_cache``
    off."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = bench.cell(cell_name)
    mix = bench.mix(cell)
    chk = bench.checker(cell)
    metrics = bench.metrics(cell, trace)
    readers = {m["name"]: bench.reader(m) for m in metrics}

    jax = setup_jax(persistent_cache)
    devices = devices_for(jax, cell["chips"], require_tpu)
    log(f"{cell_name}: {len(devices)} x {devices[0].device_kind}, "
        f"seed {seed}")
    pool = []
    for j in range(mix.pool):
        pool.append(chk.history(mix, seed, j))
        log(f"history {j}: {pool[j].ops} ops, planted {pool[j].plants}")

    summary = peak = None
    if control_checks:
        setup_s, window_s = time.perf_counter() - t_start, 0.0
        checks = [Check(i % len(pool), pool[i % len(pool)].ops, 0.0)
                  for i in range(control_checks)]
        control = answers(chk, pool, {c.j for c in checks}, "control")
        for c in checks:
            c.answer = control[c.j]
    else:
        trace_dir = bench.root / ".cache" / "trace" / cell_name
        with CompileCounter() as counter:
            for j in warm_up(pool):
                t0 = time.perf_counter()
                chk.check(pool[j].history, mix.test)
                log(f"warm-up check of history {j}: "
                    f"{time.perf_counter() - t0:.3f}s")
            setup_s = time.perf_counter() - t_start
            log(f"set-up {setup_s:.3f}s: {counter.compiles} compiles, "
                f"{counter.misses} new compile-cache entries")
            counter.reset()
            if trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
            checks = []
            if trace:
                seconds = min(seconds, TRACE_SECONDS)
            with profiled(trace_dir) if trace else nullcontext():
                window_s = measure(jax, chk, mix, pool, seconds, checks)
            log(f"window {window_s:.3f}s: {len(checks)} checks, "
                f"{counter.compiles} compiles (cache loads included), "
                f"{counter.misses} new compile-cache entries")
        peak = memory_peak(devices)
        if trace:
            from benchmark import tracefile
            summary = tracefile.read(trace_dir)
        gc.collect()

    t0 = time.perf_counter()
    ref = answers(chk, pool, {c.j for c in checks}, "reference")
    log(f"reference: {time.perf_counter() - t0:.3f}s over {len(ref)} "
        f"histories")
    compared = compare.compare(checks, ref)

    measured = Run(cell=cell, mix=mix, pool=pool, checks=checks,
                   setup_s=setup_s, window_s=window_s,
                   device_kind=devices[0].device_kind, trace=summary)
    values = {}
    for m in metrics:
        v = None if control_checks else readers[m["name"]].read(measured)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": peak}
    out = {"correct": compare.correct(compared),
           "attempted": len(checks),
           "failed": sum(c.error is not None for c in checks),
           "metrics": values,
           "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": summary.top_ops(),
                            "idle_gaps": summary.longest_gaps()}
    out["compared"] = compared
    return out


def measure(jax, chk, mix, pool, seconds, checks) -> float:
    """The window: whole passes over the pool, one check at a time,
    until a pass finishes at or after ``seconds``; returns its length.

    Whole passes, not whole checks: the pool's checks differ in length
    (an invalid history's report costs more), so a window that could
    close after any check would hold another mix of them from run to
    run, and its rate would jump with the count."""
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("benchmark.window"):
        i = 0
        while True:
            j = i % len(pool)
            history = pool[j].history
            c0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("benchmark.check"):
                try:
                    result = chk.check(history, mix.test)
                    error = None
                except Exception as e:  # noqa: BLE001 — an answer that never came
                    result, error = None, f"{type(e).__name__}: {e}"
                    log(f"check {i} (history {j}) raised {error}")
            c = Check(j, pool[j].ops, time.perf_counter() - c0, error=error)
            if result is not None:
                c.answer = chk.answer(result, history)
            checks.append(c)
            i += 1
            if i % len(pool) == 0 and time.perf_counter() - t0 >= seconds:
                return time.perf_counter() - t0
