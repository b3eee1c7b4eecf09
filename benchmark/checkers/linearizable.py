"""A single register through ``LinearizableChecker(accelerator="tpu")``:
histories from the register generator (traffic.py), answers held to the
cas-register reference (reference.py)."""
from __future__ import annotations

from benchmark import reference as plain
from benchmark import traffic

mix = traffic.load_mix
history = traffic.make_history


def check(history: list[dict], test: dict) -> dict:
    """One check as a user makes it: a new checker and a fresh test map,
    so no memo of an earlier check (the history IR is keyed on the test
    map) serves this one."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    return LinearizableChecker(accelerator="tpu").check(dict(test), history,
                                                        {})


def _index_of(history: list[dict], op) -> int:
    """The index of ``op`` in ``history`` by identity, -2 if absent."""
    for i, h in enumerate(history):
        if h is op:
            return i
    return -2


def answer(result: dict, history: list[dict]) -> dict:
    """{None: (valid, index of the failing op's completion)}. The failing
    op is the ``failed-op`` the checker reports; where it also reports
    the first anomaly (``explain``), the two must agree."""
    valid = result.get("valid?")
    if valid is True:
        return {None: (True, -1)}
    at = _index_of(history, result.get("failed-op"))
    first = (result.get("explain") or {}).get("first-anomaly-op", at)
    return {None: (valid, at if first == at else -3)}


def reference(history: list[dict], which: str) -> dict:
    """The answer of the plain reference (``which="reference"``) or of
    the control (``"control"``), as ``answer`` gives it."""
    v = plain.CHECKS[which](history)
    return {None: (v.valid, v.failed_at)}
