"""Device time of the frontier scan per history event, in microseconds.

The scan is the program the checker's frontier kernel compiles
(``ops/jitlin.py``: ``jax.jit`` of its ``run``, which the trace names
``jit_run``); its ops' device time in the traced window, per device, is
divided by the events of the window's checks: an invocation and its
completion each, failed ops left out as the checker leaves them out.
Where no scan ran, the metric is left out."""
SCAN_PROGRAM = "jit_run"


def events(history: list[dict]) -> int:
    return len(history) - 2 * sum(op["type"] == "fail" for op in history)


def read(run):
    if run.trace is None:
        return None
    scan_s = run.trace.program_seconds(SCAN_PROGRAM)
    n = sum(events(run.pool[c.j].history) for c in run.checks)
    if scan_s <= 0 or n == 0:
        return None
    return 1e6 * scan_s / n
