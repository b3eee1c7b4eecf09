"""Host milliseconds per check in settling the verdict: the
``settle.*`` spans (``settle.report``: final-configs recovery and the
rendered report; ``settle.explain``: anomaly forensics) inside the
traced window, over the window's checks. Only an invalid check reports,
so the mean carries the invalid checks' cost. Where the program names
no such span, the metric is left out."""
from benchmark import phases


def read(run):
    p = phases.of(run)
    return None if p is None else \
        phases.per_check(run, p.seconds_of("settle."), 1e3)
