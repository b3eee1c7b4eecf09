"""jepsen.independent registers through
``independent.checker(LinearizableChecker(accelerator="tpu"))``: keyed
histories from the register generator (traffic.py), each key held to
the cas-register reference (reference.py)."""
from __future__ import annotations

from benchmark import reference as plain
from benchmark import traffic

mix = traffic.load_mix
history = traffic.make_history


def check(history: list[dict], test: dict) -> dict:
    """One check with a new checker and a fresh test map (see
    checkers/linearizable.py)."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    chk = independent.checker(LinearizableChecker(accelerator="tpu"))
    return chk.check(dict(test), history, {})


def answer(result: dict, history: list[dict]) -> dict:
    """{str(key): (valid, index of the first anomaly in the key's
    sub-history)} and {"*": (lifted verdict, failing keys)}."""
    out = {}
    for key, r in result.get("results", {}).items():
        valid = r.get("valid?")
        at = -1 if valid is True else \
            (r.get("explain") or {}).get("first-anomaly-op", -2)
        out[key] = (valid, at)
    out["*"] = (result.get("valid?"), frozenset(result.get("failures", ())))
    return out


def reference(history: list[dict], which: str) -> dict:
    """The answer of the plain reference (``which="reference"``) or of
    the control (``"control"``) on each key, and lifted, as ``answer``
    gives it."""
    check = plain.CHECKS[which]
    verdicts = {k: check(h) for k, h in plain.split_keys(history).items()}
    out = {str(k): (v.valid, v.failed_at) for k, v in verdicts.items()}
    out["*"] = (all(v.valid for v in verdicts.values()),
                frozenset(k for k, (valid, _) in out.items() if not valid))
    return out
