"""Host milliseconds per check in the program's IR build and encode:
the ``encode.*`` spans (``encode.ir``, ``encode.split``,
``encode.stream``) inside the traced window, over the window's checks.
Where the program names no such span, the metric is left out."""
from benchmark import phases


def read(run):
    p = phases.of(run)
    return None if p is None else \
        phases.per_check(run, p.seconds_of("encode."), 1e3)
