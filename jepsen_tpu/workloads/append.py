"""List-append transactional workload: thin wrapper over the Elle-style
checker (reference: jepsen/src/jepsen/tests/cycle/append.clj — a thin
wrapper over elle.list-append/check + gen, append.clj:11-27).
"""
from __future__ import annotations

from jepsen_tpu import generator as gen
from jepsen_tpu.checker import Checker
from jepsen_tpu.elle import list_append


class AppendChecker(Checker):
    def __init__(self, accelerator: str = "auto",
                 consistency_models=("strict-serializable",)):
        self.accelerator = accelerator
        self.consistency_models = consistency_models

    def name(self):
        return "elle-list-append"

    def check(self, test, history, opts):
        from jepsen_tpu import trace
        with trace.phase(trace.CHECK_SPAN, ops=len(history)):
            return self._check(test, history, opts)

    def _check(self, test, history, opts):
        from jepsen_tpu import history_ir, trace
        with trace.phase("encode.ir", events=len(history)):
            ir = history_ir.of(test, history)
        result = list_append.check(
            history,
            accelerator=opts.get("accelerator", self.accelerator),
            consistency_models=opts.get("consistency_models",
                                        self.consistency_models),
            ir=ir)
        # invalid check: leave human-readable per-anomaly explanation
        # files under store/<test>/<ts>/elle/ (the reference passes
        # elle :directory per test, append.clj:17-22)
        from jepsen_tpu.elle import artifacts
        artifacts.write_for_test(test, result, opts, history=history)
        return result


def checker(**kw) -> Checker:
    return AppendChecker(**kw)


def generator(**kw):
    return gen.Fn(list_append.gen(**kw))


def workload(test: dict | None = None, accelerator: str = "auto",
             consistency_models=("strict-serializable",), **gen_kw) -> dict:
    return {
        "generator": generator(**gen_kw),
        "checker": checker(accelerator=accelerator,
                           consistency_models=consistency_models),
    }
