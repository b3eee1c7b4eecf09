"""The Elle closure screen's work, for its roofline.

The screen (``jepsen_tpu/ops/scc.py``: ``_screen_kernel``, its XLA
program ``jit_cluster_screen``) settles a batch of φ-clusters in one
dispatch: it scatters the clusters' edges into a bf16 adjacency
``[b, v, v]``, squares it ``steps`` times (``R := R or R·R``, a batched
matmul on the MXU) and reads the diagonal. The program names each
dispatch in a ``dispatch.elle_screen`` span with the bucketed shapes it
runs (``b``, ``v``, ``e``) and ``steps``; this module counts the work of
one dispatch from them and reads those spans from a traced run's
profile.

Counts, per dispatch:

* operations: ``2·b·v³`` per step (the matmul's multiply-adds); the
  elementwise max and the diagonal are left out;
* HBM bytes: the scatter reads the edge arrays (three int32 and one bool
  column of ``e``) and writes the adjacency (``2·b·v²``); each step
  reads its operand and writes its result (``2·b·v²`` each); the
  diagonal read (``2·b·v``) and the verdicts (``b``) close it. The
  float32 product of a step is taken to stay on the chip.

A dispatch's roofline time is ``max(ops / peak, bytes / bandwidth)``,
that is its operations over ``min(peak, intensity × bandwidth)``.
"""
from __future__ import annotations

import functools
from pathlib import Path

from benchmark import tracefile

SPAN = "dispatch.elle_screen"
PROGRAM = "jit_cluster_screen"

# device kind -> (bf16 operations per second, HBM bytes per second)
PEAKS = {"TPU v5 lite": (197e12, 819e9)}


def flops(b: int, v: int, e: int, steps: int) -> int:
    return 2 * b * v ** 3 * steps


def hbm_bytes(b: int, v: int, e: int, steps: int) -> int:
    scatter = e * (3 * 4 + 1) + 2 * b * v * v
    squaring = steps * 2 * (2 * b * v * v)
    return scatter + squaring + 2 * b * v + b


def roofline_seconds(b: int, v: int, e: int, steps: int,
                     device_kind: str) -> float | None:
    """The least time the dispatch can take on ``device_kind``; None for
    a device kind with no peaks here."""
    if device_kind not in PEAKS:
        return None
    peak, bandwidth = PEAKS[device_kind]
    return max(flops(b, v, e, steps) / peak,
               hbm_bytes(b, v, e, steps) / bandwidth)


def summarize(data) -> list[dict]:
    """The stats (``b``, ``v``, ``e``, ``steps``) of every screen span
    that starts in the benchmark's window of a
    ``jax.profiler.ProfileData``."""
    window, spans = None, []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracefile.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name == SPAN:
                    spans.append((ev.start_ns, dict(ev.stats)))
    if window is None:
        raise RuntimeError(f"no {tracefile.WINDOW_SPAN!r} span in the trace")
    return [s for t, s in spans if window[0] <= t < window[1]]


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> tuple:
    from jax.profiler import ProfileData
    return tuple(summarize(ProfileData.from_file(path)))


def dispatches(log_dir: Path) -> list[dict]:
    path = tracefile.find_xplane(log_dir)
    return list(_read(str(path), path.stat().st_mtime_ns))
