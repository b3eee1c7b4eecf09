"""Lowerings of the scan per check: the sum of the ``lowered`` stat
(1 where the call traced and lowered the jitted scan anew) over the
``dispatch.call`` spans that start in the traced window, over the
window's checks. Where the program names no such span, the metric is
left out."""
from benchmark import phases


def read(run):
    p = phases.of(run)
    return None if p is None else \
        phases.per_check(run, p.stat_sum("dispatch.call", "lowered"))
