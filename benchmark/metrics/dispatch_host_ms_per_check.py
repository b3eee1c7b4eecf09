"""Host milliseconds per check to get the scan onto the device: the
``dispatch.pad`` (padding, placement) and ``dispatch.call`` (fetching
the jitted scan, lowering it again where it is new, the call) spans
inside the traced window, over the window's checks. The wait for the
scan's outputs (``dispatch.readback``) is left out: the device is busy
then. Where the program names no such span, the metric is left out."""
from benchmark import phases


def read(run):
    p = phases.of(run)
    return None if p is None else phases.per_check(
        run, p.seconds_of("dispatch.pad", "dispatch.call"), 1e3)
