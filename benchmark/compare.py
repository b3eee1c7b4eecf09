"""The comparison that decides ``correct``.

An answer is a dict from key to ``(valid, report)``: for a single
register the key is ``None`` and the report the index of the failing
op's completion (-1 when valid); for a keyed history each key's entry
is the same within the key's sub-history, and the key ``"*"`` holds the
lifted verdict and the set of failing keys. The program's answers come
from ``checkers/<name>.answer``, the reference's from the plain check.

Three numbers are compared, each exactly, so each limit is 0:

* ``verdict_mismatches``: entries whose verdict differs from the
  reference's, or that the program left out;
* ``report_mismatches``: invalid entries whose failing op (or failing
  key set) differs from the reference's;
* ``unanswered``: checks of the window that raised instead of answering.
"""
from __future__ import annotations

LIMITS = {"verdict_mismatches": 0, "report_mismatches": 0,
          "unanswered": 0}


def answer_diff(got: dict, want: dict) -> tuple[int, int]:
    """(verdict mismatches, report mismatches) of one answer."""
    verdicts = reports = 0
    for key, (valid, report) in want.items():
        mine = got.get(key)
        if mine is None or mine[0] is not valid:
            verdicts += 1
        elif valid is not True and mine[1] != report:
            reports += 1
    # entries the reference does not know are answers to nothing asked
    verdicts += len(set(got) - set(want))
    return verdicts, reports


def compare(checks: list, ref: dict) -> dict:
    """{name: {"value": n, "limit": limit}} over the window's checks."""
    got = dict.fromkeys(LIMITS, 0)
    for c in checks:
        if c.answer is None:
            got["unanswered"] += 1
            continue
        v, r = answer_diff(c.answer, ref[c.j])
        got["verdict_mismatches"] += v
        got["report_mismatches"] += r
    return {k: {"value": got[k], "limit": LIMITS[k]} for k in LIMITS}


def correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())


def stderr_lines(compared: dict) -> list[str]:
    return [f"compared {k}: {c['value']} (limit {c['limit']})"
            for k, c in compared.items()]
