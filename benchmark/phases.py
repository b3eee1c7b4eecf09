"""The program's own checker phases in a traced run's profile.

The checker names its phases on the host plane's thread lines
(``jepsen_tpu.trace.phase``): the ``check`` span, and
``<layer>.<phase>`` spans (``encode.ir``, ``dispatch.call``, ...), each
with its counts as event stats. This module sums them by name inside the
benchmark's window (``tracefile.WINDOW_SPAN``), for the per-layer
metrics that read them. A trace of a program that names no phases gives
empty sums, and those metrics are left out.

Read with ``jax.profiler.ProfileData`` from the trace directory the
harness writes for the cell; it is read once for all the metrics of a
run.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from pathlib import Path

from benchmark import harness, tracefile

CHECK_SPAN = "check"
LAYERS = ("encode.", "ladder.", "dispatch.", "settle.")
# a stat that names the check a span belongs to, not a count
CHECK_STAT = "check"


@dataclass
class Phases:
    # span name -> seconds inside the window, summed over its spans
    seconds: dict = field(default_factory=dict)
    # span name -> {stat: sum} of numeric stats, over the spans that
    # start in the window
    stats: dict = field(default_factory=dict)

    def seconds_of(self, *names: str) -> float | None:
        """Seconds of the spans named ``names``; a name that ends in
        ``.`` stands for every span of that layer. None where none of
        them ran."""
        found = [s for k, s in self.seconds.items()
                 if any(k == n or (n.endswith(".") and k.startswith(n))
                        for n in names)]
        return sum(found) if found else None

    def stat_sum(self, name: str, stat: str) -> float | None:
        """The sum of ``stat`` over the spans named ``name``; None where
        no such span started in the window."""
        if name not in self.stats:
            return None
        return self.stats[name].get(stat, 0)


def is_phase(name: str) -> bool:
    return name == CHECK_SPAN or name.startswith(LAYERS)


def summarize(data) -> Phases:
    """The Phases of a ``jax.profiler.ProfileData``. Strings (a rung's
    backend, its outcome) and the check id are no counts and are left
    out of the stats."""
    host = [p for p in data.planes if p.name.startswith("/host:")]
    window = None
    spans = []
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracefile.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif is_phase(ev.name):
                    spans.append(ev)
    if window is None:
        raise RuntimeError(f"no {tracefile.WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    out = Phases()
    for ev in spans:
        s = max(ev.start_ns, w0)
        e = min(ev.start_ns + ev.duration_ns, w1)
        if e > s:
            out.seconds[ev.name] = out.seconds.get(ev.name, 0.0) \
                + (e - s) / 1e9
        if not w0 <= ev.start_ns < w1:
            continue
        sums = out.stats.setdefault(ev.name, {})
        for k, v in getattr(ev, "stats", ()):
            if k != CHECK_STAT and isinstance(v, (int, float)) \
                    and not isinstance(v, bool):
                sums[k] = sums.get(k, 0) + v
    return out


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> Phases:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(path))


def read(log_dir: Path) -> Phases:
    path = tracefile.find_xplane(log_dir)
    return _read(str(path), path.stat().st_mtime_ns)


def of(run) -> Phases | None:
    """The phases of a traced run's window; None for a run without the
    trace."""
    if run.trace is None:
        return None
    return read(harness.CACHE / "trace" / run.cell["name"])


def per_check(run, value: float | None, scale: float = 1.0):
    """``value`` over the run's checks, times ``scale``; None where
    there is no value or no check."""
    if value is None or not run.checks:
        return None
    return scale * value / len(run.checks)
