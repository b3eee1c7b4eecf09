"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
one whose name starts with ``/device:`` and which has an ``XLA Ops`` line:
each event on that line is one operation that ran on that device. The
host's own spans (``jax.profiler.TraceAnnotation``) sit on the host
plane's thread lines, on the same clock.

The window is the span the benchmark names ``WINDOW_SPAN``; everything
is clipped to it. Busy time of a device is the union of its operations'
intervals in the window; idle gaps are the rest. Each idle gap is named
by the innermost host event running at its midpoint, so a gap reads as
what the host was doing while the device waited.

The ``XLA Ops`` line also holds the ops of a while loop's body, nested
inside the loop's own event; op time counts top-level events only, so
nothing is counted twice. An op is named ``<program> <hlo name>
<opcode>``, the program from the ``XLA Modules`` line, and a custom call
adds its target: a pallas kernel reads ``custom-call:tpu_custom_call``.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "benchmark.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class Summary:
    window_s: float
    # per device plane: seconds in which an operation ran
    busy_s: dict = field(default_factory=dict)
    # device op label -> seconds, top-level ops, summed over devices
    op_seconds: dict = field(default_factory=dict)
    # (seconds, name of what the host was doing) per idle gap
    gaps: list = field(default_factory=list)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)

    def program_seconds(self, program: str) -> float:
        """Seconds of the ops of the program named ``program``, per
        device."""
        return sum(s for k, s in self.op_seconds.items()
                   if k.split(" ", 1)[0] == program) / len(self.busy_s)

    def top_ops(self, n: int = 10) -> list:
        per_dev = len(self.busy_s)
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / per_dev] for name, s in top]

    def longest_gaps(self, n: int = 10) -> list:
        return [[name, s] for s, name in
                sorted(self.gaps, key=lambda g: -g[0])[:n]]


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"want one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _host_events(planes) -> tuple[tuple, list]:
    """(window (start, end) in ns, [(start, end, name)] of every host
    event) from the host plane's thread lines."""
    window = None
    events = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == WINDOW_SPAN:
                    window = (s, e)
                elif ev.duration_ns > 0:
                    events.append((s, e, ev.name))
    if window is None:
        raise RuntimeError(f"no {WINDOW_SPAN!r} span in the trace")
    return window, events


def _name_at(events: list, t: float) -> str:
    """The innermost host event running at ``t``: the latest-starting
    one among those that cover it."""
    best = None
    for s, e, name in events:
        if s <= t < e and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best else "host idle"


def op_label(program: str, hlo: str) -> str:
    """``<program> <hlo name> <opcode>`` of one ``XLA Ops`` event."""
    short, _, rest = hlo.partition(" = ")
    m = _OPCODE.search(rest)
    op = m.group(1) if m else "?"
    if op == "custom-call":
        t = _TARGET.search(rest)
        op += ":" + (t.group(1) if t else "?")
    return f"{program} {short} {op}"


def _programs(lines) -> tuple[list, list]:
    """(starts, [(start, end, program name)]) of the ``XLA Modules``
    line, sorted by start."""
    mods = []
    for line in lines:
        if line.name == MODULES_LINE:
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(")[0]) for ev in line.events)
    return [m[0] for m in mods], mods


def _program_at(starts: list, mods: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    return mods[i][2] if i >= 0 and t < mods[i][1] else "?"


def summarize(data) -> Summary:
    """The Summary of a ``jax.profiler.ProfileData``."""
    planes = list(data.planes)
    (w0, w1), host = _host_events(planes)
    out = Summary(window_s=(w1 - w0) / 1e9)
    gaps = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = list(plane.lines)
        ops = [ln for ln in lines if ln.name == OPS_LINE]
        if not ops:
            continue
        starts, mods = _programs(lines)
        spans = []
        top_end = float("-inf")
        for ev in sorted(ops[0].events, key=lambda ev: ev.start_ns):
            s = max(ev.start_ns, w0)
            e = min(ev.start_ns + ev.duration_ns, w1)
            nested = ev.start_ns < top_end
            if not nested:
                top_end = ev.start_ns + ev.duration_ns
            if e <= s or nested:
                continue
            spans.append((s, e))
            key = op_label(_program_at(starts, mods, ev.start_ns), ev.name)
            out.op_seconds[key] = out.op_seconds.get(key, 0.0) + (e - s) / 1e9
        busy = _union(spans)
        out.busy_s[plane.name] = sum(b - a for a, b in busy) / 1e9
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    if not out.busy_s:
        raise RuntimeError("the trace has no device plane with an "
                           f"{OPS_LINE!r} line")
    # name only the gaps that can reach the breakdown's list
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    out.gaps = [((b - a) / 1e9, _name_at(host, (a + b) / 2))
                for a, b in gaps[:50]]
    return out


def read(log_dir: Path) -> Summary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(str(find_xplane(log_dir))))
