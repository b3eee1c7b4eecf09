"""Host milliseconds per check in settling the Elle checker's verdict:
the ``settle.*`` spans (``settle.elle_classify``: the typed anomaly
searches on the clusters the screen left live, and the result map)
inside the traced window, over the window's checks. Where the program
names no such span, the metric is left out."""
from benchmark import phases


def read(run):
    p = phases.of(run)
    return None if p is None else \
        phases.per_check(run, p.seconds_of("settle."), 1e3)
