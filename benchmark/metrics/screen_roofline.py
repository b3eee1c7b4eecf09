"""The Elle closure screen's share of its roofline: the least time its
dispatches in the traced window could take on the device
(``benchmark/elle_screen.py``: each dispatch's operations and HBM bytes
from its ``dispatch.elle_screen`` span, against the device's peaks),
over the device time of the screen's program (``jit_cluster_screen``)
in the window, in percent. Where no screen ran, or the device kind has
no peaks, the metric is left out."""
from benchmark import elle_screen, harness


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.program_seconds(elle_screen.PROGRAM)
    spans = elle_screen.dispatches(harness.CACHE / "trace" / run.cell["name"])
    if device_s <= 0 or not spans:
        return None
    least = [elle_screen.roofline_seconds(s["b"], s["v"], s["e"], s["steps"],
                                          run.device_kind) for s in spans]
    if None in least:
        return None
    return 100.0 * sum(least) / device_s
