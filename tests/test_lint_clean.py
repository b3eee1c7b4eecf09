"""Tier-1 gate: ``jepsen_tpu/`` lints at ZERO non-baselined findings
with EVERY rule enabled — including the interprocedural families this
tier added (thread-spawn edges, lock-order, cond-wait,
durability-protocol, telemetry-name).

This is the machine that turns a future regression of any encoded
invariant class — a lock taken in the wrong order, a durable artifact
overwritten in place, a naked ``wait()``, a silent metric rename — into
a red build instead of a review catch. The wall-clock assertions
(< 60 s cold, < 30 s warm) make analysis cost regressions fail here
before they silently eat the tier-1 budget.
"""
from __future__ import annotations

import time
from pathlib import Path

import pytest

from jepsen_tpu.analysis import lint as lint_mod

pytestmark = pytest.mark.lint

ROOT = Path(__file__).resolve().parent.parent


def _lint():
    return lint_mod.lint_paths([str(ROOT / "jepsen_tpu")],
                               baseline=str(ROOT / "lint-baseline.txt"),
                               root=str(ROOT))


def test_all_rules_enabled_and_clean():
    # every registered rule runs (no silent subset): the default
    # selection IS the full set
    t0 = time.monotonic()
    rep = _lint()
    cold_s = time.monotonic() - t0
    assert set(lint_mod.RULE_NAMES) >= {
        "thread-owner", "no-unbounded-block", "lock-order", "cond-wait",
        "durability-protocol", "telemetry-name", "lock-guard",
        "fsync-pairing"}
    assert rep.findings == [], (
        "non-baselined lint findings in jepsen_tpu/ — fix them or add a "
        "documented waiver to lint-baseline.txt:\n"
        + "\n".join(f.render() for f in rep.findings))
    assert rep.stale_waivers == [], (
        "stale lint-baseline.txt entries: " + str(rep.stale_waivers))
    assert cold_s < 60.0, f"cold full-tree lint took {cold_s:.1f}s"


def test_warm_lint_within_budget():
    _lint()  # ensure the AST cache is populated
    t0 = time.monotonic()
    rep = _lint()
    warm_s = time.monotonic() - t0
    assert rep.findings == []
    assert warm_s < 30.0, f"warm full-tree lint took {warm_s:.1f}s"
