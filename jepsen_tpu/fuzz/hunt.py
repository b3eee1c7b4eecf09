"""The hunter: coverage-guided schedule search over the live fleet.

One hunt = ``fuzz_trials`` short fake-mode runs in batches: a pool
writes each batch's WAL-backed run dirs, one :class:`~jepsen_tpu.live.
daemon.LiveDaemon` per batch ingests and verdicts them through the
same path a production fleet uses (device checkers batch across
trials), and an ``on_final`` hook harvests each session's
``coverage_probe()`` before its tracker is popped. New edges and
shrinking near-miss margins promote schedules into the corpus; an
invalid verdict is an anomaly — minimized through the PR-8 ddmin
(:func:`jepsen_tpu.checker.explain.ddmin` over the schedule's fault
windows, then an op-budget truncation pass) and landed as a
``hunt/<id>/`` artifact whose stored seed tuple replays the failure
bit-identically (doc/robustness.md "Schedule fuzzing").

Knobs (test map / CLI / ``JEPSEN_TPU_FUZZ_*`` env twins; tolerant
coercion here, strictness in preflight's KNB rows): ``fuzz_trials``,
``fuzz_pool_workers``, ``fuzz_trial_ops``, ``fuzz_seed``.
"""
from __future__ import annotations

import json
import logging
import os
import random
import shutil
import threading
import time
from pathlib import Path

from jepsen_tpu import telemetry
from jepsen_tpu.fuzz.corpus import Corpus, mutate, random_schedule
from jepsen_tpu.fuzz.coverage import CoverageMap, history_edges
from jepsen_tpu.fuzz.schedule import Schedule
from jepsen_tpu.fuzz.trial import (
    PlantedBug, pool_run_trial, run_trial, write_run,
)

logger = logging.getLogger("jepsen.fuzz")

DEFAULT_TRIALS = 400
DEFAULT_POOL_WORKERS = 0     # 0 = inline (deterministic single-thread)
DEFAULT_TRIAL_OPS = 120
DEFAULT_SEED = 0
DEFAULT_BATCH = 24
HUNT_DIR = "hunt"

# the canned interleaving-gated bug (--demo-bug, the e2e): arms on a
# cas inside a partition, then a write inside clock skew, and finally
# tears a write acked while ALL FOUR fault kinds overlap — a
# composition one random draw can never contain (the blind generator
# emits at most 3 windows, so at most 3 distinct kinds), while
# coverage guidance builds it incrementally: each partial mask is a
# retained new-edge parent, add-window mutation stacks a fourth kind
# on a 3-kind parent, and splice unions two parents' windows
DEMO_BUG_SPEC = [
    [["net"], "cas"],
    [["clock-rate"], "write"],
    [["clock-rate", "membership", "net", "pause"], "write"],
]

# fuzz knob spec shared with preflight's KNB validation
# (analysis/preflight._NUMERIC_KNOBS): (key, default, min)
FUZZ_KNOBS = (
    ("fuzz_trials", DEFAULT_TRIALS, 1.0),
    ("fuzz_pool_workers", DEFAULT_POOL_WORKERS, 0.0),
    ("fuzz_trial_ops", DEFAULT_TRIAL_OPS, 8.0),
    ("fuzz_seed", DEFAULT_SEED, None),
)


def fuzz_knob(name: str, value, default: float, lo: float | None):
    """Tolerant numeric coercion with a ``JEPSEN_TPU_<NAME>`` env twin:
    explicit value wins, then the env var, then the default; garbage
    warns and falls back (preflight's KNB001/KNB002 rows are where
    strictness lives)."""
    if value is None:
        value = os.environ.get("JEPSEN_TPU_" + name.upper())
    if value is None or value == "":
        return default
    try:
        if isinstance(value, bool):
            raise ValueError("bool is not a number")
        v = float(value)
    except (TypeError, ValueError):
        logger.warning("fuzz knob %s=%r is not numeric; using default "
                       "%r", name, value, default)
        return default
    if lo is not None and v < lo:
        logger.warning("fuzz knob %s=%r below minimum %r; clamping",
                       name, value, lo)
        return lo
    return v


def _cpu_only_worker() -> None:
    """Process-pool initializer: the worker never reaches for the chip."""
    import sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_platforms", "cpu")


class Hunter:
    """One coverage-guided (or, for the baseline, blind-random) hunt.

    ``bug_spec`` plants a :class:`~jepsen_tpu.fuzz.trial.PlantedBug`
    into every trial's target — the seam the e2e/demo uses; production
    hunts run the honest register, where an invalid verdict would mean
    a real checker/simulator bug. The spec is stored in the artifact,
    so replay reconstructs the identical target."""

    def __init__(self, store_root, trials=None, pool_workers=None,
                 trial_ops=None, seed=None, guided: bool = True,
                 bug_spec=None, accelerator: str = "cpu",
                 registry=None, batch_size: int = DEFAULT_BATCH,
                 stop_on_first: bool = True):
        self.store_root = Path(store_root)
        self.trials = int(fuzz_knob("fuzz_trials", trials,
                                    DEFAULT_TRIALS, 1.0))
        self.pool_workers = int(fuzz_knob("fuzz_pool_workers",
                                          pool_workers,
                                          DEFAULT_POOL_WORKERS, 0.0))
        self.trial_ops = int(fuzz_knob("fuzz_trial_ops", trial_ops,
                                       DEFAULT_TRIAL_OPS, 8.0))
        self.seed = int(fuzz_knob("fuzz_seed", seed, DEFAULT_SEED,
                                  None))
        self.guided = guided
        self.bug_spec = bug_spec
        self.accelerator = accelerator
        self.registry = registry if registry is not None \
            else telemetry.Registry()
        self.batch_size = max(1, int(batch_size))
        self.stop_on_first = stop_on_first
        self.rng = random.Random(self.seed)
        self.covmap = CoverageMap()
        base = Schedule(seed=self.seed, n_ops=self.trial_ops)
        self.corpus = Corpus(base=base)
        self.anomalies: list[dict] = []
        self.trials_run = 0
        self.outcomes = {"valid": 0, "invalid": 0, "error": 0}

    # -- schedule generation --------------------------------------------

    def _next_schedule(self) -> Schedule:
        if not self.guided:
            # the blind baseline IS the fuzzer's own seed generator —
            # what the search would be without a corpus. Composition
            # beyond any single draw (schedules mutation/splice builds
            # out of retained parents) is exactly what guidance buys.
            return random_schedule(self.rng, n_ops=self.trial_ops)
        parent = self.corpus.pick(self.rng)
        splice = (self.corpus.pick(self.rng)
                  if len(self.corpus) > 1 and self.rng.random() < 0.3
                  else None)
        return mutate(parent, self.rng, splice_from=splice)

    # -- trial execution ------------------------------------------------

    def _run_batch_trials(self, schedules, batch_root: Path) -> dict:
        """Writes every trial's run dir; returns {idx: history}.
        Results are applied in trial-index order regardless of pool
        completion order — the corpus/coverage updates must not depend
        on worker scheduling."""
        jobs = [(i, s.to_json(),
                 str(batch_root / f"t{i:05d}" / "0"), self.bug_spec)
                for i, s in enumerate(schedules)]
        histories: dict[int, list] = {}
        if self.pool_workers <= 1:
            for job in jobs:
                idx, h = pool_run_trial(job)
                histories[idx] = h
            return histories
        try:
            import concurrent.futures as _fut
            import multiprocessing
            # spawned, CPU-pinned workers: they only run fake trials, and
            # a fork of a parent that holds the chip (the live daemon
            # checks on self.accelerator) would copy its device state
            with _fut.ProcessPoolExecutor(
                    max_workers=self.pool_workers,
                    mp_context=multiprocessing.get_context("spawn"),
                    initializer=_cpu_only_worker) as pool:
                for idx, h in pool.map(pool_run_trial, jobs):
                    histories[idx] = h
            return histories
        except Exception:  # noqa: BLE001 — pool loss degrades, never kills
            logger.exception("process pool failed; falling back to a "
                             "thread pool")
        lock = threading.Lock()
        queue = list(jobs)

        # owner: worker — fuzzer pool thread: pops one trial job at a
        # time under the lock; writes only its own run dir + its slot
        # in the (lock-guarded) histories dict
        def worker():
            while True:
                with lock:
                    if not queue:
                        return
                    job = queue.pop(0)
                idx, h = pool_run_trial(job)
                with lock:
                    histories[idx] = h

        threads = [threading.Thread(target=worker, daemon=True,
                                    name=f"jepsen-fuzz-pool-{i}")
                   for i in range(self.pool_workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return histories

    def _verdict_batch(self, batch_root: Path, n: int) -> dict:
        """Fleet-path verdicts: one LiveDaemon over the batch's trial
        run dirs, probes harvested via on_final before trackers pop.
        The dirs are passed explicitly — a fuzz trial lands complete
        (WAL + history.jsonl at once), which the store-root scan would
        reject as post-hoc territory."""
        from jepsen_tpu.live.daemon import LiveDaemon
        collected: dict[str, dict] = {}

        def on_final(tr, results):
            probe_fn = getattr(tr.session, "coverage_probe", None)
            collected[tr.name] = {
                "results": results,
                "verdict": dict(tr.last_verdict),
                "probe": probe_fn() if probe_fn is not None else {},
            }

        run_dirs = [batch_root / f"t{i:05d}" / "0" for i in range(n)]
        daemon = LiveDaemon(run_dirs=run_dirs, poll_s=0.01,
                            max_runs=max(32, self.batch_size),
                            check_budget_s=30.0,
                            accelerator=self.accelerator,
                            registry=self.registry, on_final=on_final)
        daemon.run_until_idle(timeout_s=max(60.0, 2.0 * n))
        return collected

    # -- the hunt loop --------------------------------------------------

    def run(self) -> dict:
        """Hunts until the trial budget is spent (or, with
        ``stop_on_first``, until an anomaly lands). Returns the summary
        the CLI prints and tests assert on."""
        t0 = time.perf_counter()
        reg = self.registry
        trials_c = reg.counter(
            "fuzz_trials_total",
            "schedule-fuzz trials by verdict outcome",
            labels=("outcome",))
        batch_no = 0
        work_root = self.store_root / "work"
        while self.trials_run < self.trials:
            n = min(self.batch_size, self.trials - self.trials_run)
            schedules = [self._next_schedule() for _ in range(n)]
            batch_root = work_root / f"b{batch_no:04d}"
            histories = self._run_batch_trials(schedules, batch_root)
            collected = self._verdict_batch(batch_root, n)
            found = None
            for i in range(n):
                got = collected.get(f"t{i:05d}") or {}
                verdict = got.get("verdict") or {}
                probe = got.get("probe") or {}
                valid = verdict.get("valid_so_far")
                outcome = ("valid" if valid is True
                           else "invalid" if valid is False
                           else "error")
                self.outcomes[outcome] += 1
                trials_c.inc(outcome=outcome)
                self.trials_run += 1
                edges = history_edges(histories.get(i) or [])
                edges += list(probe.get("edges") or ())
                new_edges = self.covmap.observe(edges)
                near_miss = self.covmap.observe_margin(
                    probe.get("margin"))
                if outcome == "invalid":
                    self.anomalies.append({
                        "schedule": schedules[i],
                        "verdict": verdict,
                        "results": got.get("results"),
                    })
                    if self.guided:
                        self.corpus.add(schedules[i], reason="anomaly")
                    if found is None:
                        found = i
                elif self.guided and new_edges:
                    self.corpus.add(schedules[i], reason="new-edge")
                elif self.guided and near_miss:
                    self.corpus.add(schedules[i], reason="near-miss")
            reg.gauge("fuzz_coverage_edges",
                      "distinct coverage edges discovered by the hunt"
                      ).set(float(len(self.covmap)))
            reg.gauge("fuzz_corpus_size",
                      "schedules retained in the fuzz corpus"
                      ).set(float(len(self.corpus)))
            if self.covmap.best_margin is not None:
                reg.gauge("fuzz_near_miss_margin",
                          "smallest surviving frontier seen (1 = one "
                          "linearization from a verdict flip)"
                          ).set(float(self.covmap.best_margin))
            # trial dirs are scratch: anomalies carry their whole
            # reproduction in the schedule, so the batch dir goes
            shutil.rmtree(batch_root, ignore_errors=True)
            batch_no += 1
            if found is not None and self.stop_on_first:
                break
        summary = {
            "trials": self.trials_run,
            "outcomes": dict(self.outcomes),
            "coverage_edges": len(self.covmap),
            "corpus_size": len(self.corpus),
            "best_margin": self.covmap.best_margin,
            "anomalies": len(self.anomalies),
            "wall_s": round(time.perf_counter() - t0, 3),
            "guided": self.guided,
            "seed": self.seed,
        }
        if self.anomalies:
            summary["hunt_ids"] = [self.land(a)
                                   for a in self.anomalies[:4]]
        return summary

    # -- minimization + artifacts ---------------------------------------

    def _trial_invalid(self, schedule: Schedule,
                       explain: bool = False) -> dict | None:
        """Direct (daemon-less) re-verdict for minimization probes:
        the batch path already proved the checker agrees with the
        post-hoc result, so ddmin probes use the cheap exact check.
        Adds ``_failed_client_op`` (client-invoke count up to the dying
        op — the op-budget shrink's target, distinct from the raw
        history index because nemesis ops pad the history). ``explain``
        turns the forensics pass on for the one check whose result the
        artifact keeps; probes leave it off (a probe wants a verdict,
        not a witness shrink)."""
        from jepsen_tpu.checker.linearizable import LinearizableChecker
        h = run_trial(schedule, bug=PlantedBug.from_spec(self.bug_spec))
        res = LinearizableChecker(accelerator="cpu").check(
            None, h, {"explain": bool(explain)})
        if res.get("valid?") is not False:
            return None
        res = dict(res)
        fop = res.get("failed-op")
        if fop is not None:
            inv = 0
            for op in h:  # failed-op IS history[i] (same object)
                if op.get("type") == "invoke" \
                        and isinstance(op.get("process"), int):
                    inv += 1
                if op is fop:
                    res["_failed_client_op"] = inv
                    break
        return res

    def minimize(self, schedule: Schedule) -> tuple[Schedule, dict]:
        """PR-8 ddmin over the schedule's fault windows, then a
        greedy op-budget truncation — the minimized schedule still
        produces an invalid verdict (re-proven on every probe)."""
        from jepsen_tpu.checker.explain import ddmin
        kept, info = ddmin(
            list(schedule.faults),
            lambda ws: self._trial_invalid(
                Schedule(seed=schedule.seed, n_ops=schedule.n_ops,
                         concurrency=schedule.concurrency, faults=ws,
                         knobs=dict(schedule.knobs))) is not None,
            budget=48)
        s = schedule.copy()
        s.faults = kept
        res = self._trial_invalid(s)
        # op-budget shrink: cut past the anomaly, then halve toward it
        failed = (res or {}).get("_failed_client_op")
        if failed is not None:
            for n_ops in (failed + 8, failed + 2):
                if n_ops < s.n_ops:
                    cand = s.copy()
                    cand.n_ops = n_ops
                    if self._trial_invalid(cand) is not None:
                        s = cand
        info["n_ops"] = s.n_ops
        return s, info

    def land(self, anomaly: dict) -> str:
        """Minimizes one anomaly and writes the ``hunt/<id>/``
        artifact bundle: seed tuple, minimized schedule, minimized
        history, verdict, and the explain payload."""
        schedule = anomaly["schedule"]
        minimized, shrink_info = self.minimize(schedule)
        res = self._trial_invalid(minimized, explain=True)
        if res is None:  # pragma: no cover — minimize re-proves each step
            minimized, res = schedule, self._trial_invalid(schedule,
                                                           explain=True)
        history = run_trial(minimized,
                            bug=PlantedBug.from_spec(self.bug_spec))
        hunt_id = minimized.key()
        d = self.store_root / HUNT_DIR / hunt_id
        d.mkdir(parents=True, exist_ok=True)
        (d / "schedule.json").write_text(schedule.to_json() + "\n")
        (d / "minimized.json").write_text(minimized.to_json() + "\n")
        with open(d / "history.jsonl", "w", encoding="utf-8") as f:
            for op in history:
                f.write(json.dumps(op) + "\n")
        meta = {
            "id": hunt_id,
            "seed_tuple": minimized.canonical(),
            "bug_spec": self.bug_spec,
            "shrink": shrink_info,
            "live_verdict": anomaly.get("verdict"),
            "edges": history_edges(history),
        }
        (d / "verdict.json").write_text(
            json.dumps({k: v for k, v in (res or {}).items()
                        if _jsonable(v)}, default=repr, indent=2) + "\n")
        (d / "hunt.json").write_text(json.dumps(meta, indent=2) + "\n")
        logger.info("anomaly landed: hunt/%s (windows %d -> %d, "
                    "n_ops -> %d)", hunt_id, len(schedule.faults),
                    len(minimized.faults), minimized.n_ops)
        return hunt_id


def _jsonable(v) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


def replay(store_root, hunt_id: str) -> dict:
    """``jepsen-tpu hunt --replay <id>``: re-runs the minimized
    schedule from the stored seed tuple and checks the reproduction is
    bit-identical — history bytes AND verdict must match what the hunt
    landed. Returns {reproduced, identical, verdict, ...}."""
    d = Path(store_root) / HUNT_DIR / hunt_id
    minimized = Schedule.from_json((d / "minimized.json").read_text())
    meta = json.loads((d / "hunt.json").read_text())
    bug = PlantedBug.from_spec(meta.get("bug_spec"))
    history = run_trial(minimized, bug=bug)
    stored = (d / "history.jsonl").read_text()
    got = "".join(json.dumps(op) + "\n" for op in history)
    from jepsen_tpu.checker.linearizable import LinearizableChecker
    res = LinearizableChecker(accelerator="cpu").check(
        None, history, {"explain": False})
    return {
        "id": hunt_id,
        "identical": got == stored,
        "reproduced": res.get("valid?") is False,
        "valid?": res.get("valid?"),
        "n_ops": minimized.n_ops,
        "windows": len(minimized.faults),
    }


def list_hunts(store_root) -> list[dict]:
    """The landed anomalies under ``<store>/hunt/`` (web + CLI)."""
    root = Path(store_root) / HUNT_DIR
    out = []
    if not root.is_dir():
        return out
    for d in sorted(root.iterdir()):
        meta_p = d / "hunt.json"
        if not d.is_dir() or not meta_p.exists():
            continue
        try:
            meta = json.loads(meta_p.read_text())
        except (OSError, ValueError):
            continue
        seed = meta.get("seed_tuple") or {}
        out.append({"id": d.name,
                    "n_ops": seed.get("n_ops"),
                    "windows": len(seed.get("faults") or ()),
                    "seed": seed.get("seed")})
    return out
