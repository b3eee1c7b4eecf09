"""Each fault a cell can have, planted under the timed path on the CPU
at test size, makes the run's ``correct`` come out false. The harness's
look for a chip is skipped; the rest of a run is driven as on the chip."""
from __future__ import annotations

import bench_testing
import pytest


def state_unchanged(monkeypatch):
    """The frontier scan leaves the configurations as they were: every
    history survives."""
    from jepsen_tpu import parallel
    monkeypatch.setattr(parallel, "_scan_batch",
                        lambda streams, *a: [(True, -1, False, 0)]
                        * len(streams))


def half_the_batch(monkeypatch):
    """Half the keys of each batch checked, the answers of the rest
    left out."""
    from jepsen_tpu import parallel
    real = parallel.batch_check

    def half(streams, **kw):
        return real(streams[:len(streams) // 2], **kw)

    monkeypatch.setattr(parallel, "batch_check", half)


def answer_altered(monkeypatch):
    """The event at which the device's frontier dies, moved two events
    earlier."""
    from jepsen_tpu import parallel
    real = parallel._scan_batch

    def moved(*a):
        return [(alive, died if alive else died - 2, ovf, peak)
                for alive, died, ovf, peak in real(*a)]

    monkeypatch.setattr(parallel, "_scan_batch", moved)


def verdict_flipped(monkeypatch):
    """The device's verdict on the first key of each batch, flipped."""
    from jepsen_tpu import parallel
    real = parallel.batch_check

    def flipped(streams, **kw):
        out = list(real(streams, **kw))
        alive, died, overflow, peak = out[0]
        out[0] = (not alive, died, overflow, peak)
        return out

    monkeypatch.setattr(parallel, "batch_check", flipped)


@pytest.mark.parametrize("cell,fault", [
    ("t.register", state_unchanged),
    ("t.keys", state_unchanged),
    ("t.keys", half_the_batch),
    ("t.register", answer_altered),
    ("t.keys", verdict_flipped),
])
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = bench_testing.run(cell)
    assert out["correct"] is False, (fault.__name__, out["compared"])
