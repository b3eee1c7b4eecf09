"""Milliseconds per check in the Elle checker's cycle localization and
screen: the ``dispatch.elle_cluster`` span (back edges, φ-clusters,
their remap, the host screen of small clusters) and the
``dispatch.elle_screen`` spans (each device screen dispatch, readback
included) inside the traced window, over the window's checks. Where
the program names no such span, the metric is left out."""
from benchmark import phases


def read(run):
    p = phases.of(run)
    return None if p is None else phases.per_check(
        run, p.seconds_of("dispatch.elle_cluster", "dispatch.elle_screen"),
        1e3)
