"""Key-lifting: turn single-key workloads into many-key workloads.

Reference: jepsen/src/jepsen/independent.clj. Values become [k, v] tuples
(:21-29); generators run per-key either sequentially (:31-47) or with
groups of n threads working concurrently through a key rotation
(ConcurrentGenerator, :101-209); the checker splits the history per key and
checks each sub-history independently (:264-315).

This is the cleanest TPU win (SURVEY.md §2.6): per-key sub-histories are
embarrassingly parallel, so the lifted linearizability checker batches all
keys into one padded event tensor and runs the jitlin kernel under vmap —
sharded across devices by jepsen_tpu.parallel when a mesh is available
(BASELINE config 3).
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Iterable

from jepsen_tpu import generator as gen_mod
from jepsen_tpu import trace
from jepsen_tpu.checker import Checker, check_safe, merge_valid
from jepsen_tpu.generator import Generator, PENDING, as_gen
from jepsen_tpu.utils import bounded_pmap

logger = logging.getLogger("jepsen.independent")


def tuple_value(k, v) -> list:
    """An independent [key, value] pair (independent.clj:21-29). Plain
    lists so histories stay JSON-serializable."""
    return [k, v]


def is_tuple_value(v) -> bool:
    return isinstance(v, (list, tuple)) and len(v) == 2


def tuple_gen(k, gen) -> Generator:
    """Lifts a generator's values into [k, v] tuples."""
    def lift(op):
        op = dict(op)
        op["value"] = tuple_value(k, op.get("value"))
        return op
    return gen_mod.Map(lift, gen)


@dataclass(frozen=True)
class SequentialGenerator(Generator):
    """One key at a time: exhaust gen_fn(k) for each k in order
    (independent.clj:31-47). ``keys`` may be infinite."""

    keys: "KeyStream" = field(compare=False)
    gen_fn: Callable = field(compare=False)
    idx: int = 0
    current: Any = None
    started: bool = False

    def _advance(self):
        k, ok = self.keys.get(self.idx)
        if not ok:
            return None
        return replace(self, idx=self.idx + 1,
                       current=tuple_gen(k, self.gen_fn(k)), started=True)

    def op(self, test, ctx):
        state = self if self.started else self._advance()
        while state is not None:
            g = as_gen(state.current)
            res = g.op(test, ctx) if g is not None else None
            if res is None:
                state = state._advance()
                continue
            op, g2 = res
            return (op, replace(state, current=g2))
        return None

    def update(self, test, ctx, event):
        if not self.started:
            return self
        g = as_gen(self.current)
        if g is None:
            return self
        return replace(self, current=g.update(test, ctx, event))


def sequential_generator(keys: Iterable, gen_fn: Callable[[Any], Any]) -> Generator:
    """(independent.clj:31-47)."""
    return SequentialGenerator(keys=KeyStream(keys), gen_fn=gen_fn)


class KeyStream:
    """Memoizing immutable view over a possibly-infinite key sequence, so
    ``concurrent_generator`` accepts ``itertools.count()`` the way the
    reference accepts infinite lazy seqs (independent.clj:211-236).
    Functional generator copies share one stream; the memo only grows, so
    ``get(i)`` is referentially transparent."""

    def __init__(self, iterable):
        self._it = iter(iterable)
        self._memo: list = []
        self._done = False

    def get(self, i: int):
        """(key, True) for index i, or (None, False) past the end."""
        while not self._done and len(self._memo) <= i:
            try:
                self._memo.append(next(self._it))
            except StopIteration:
                self._done = True
        if i < len(self._memo):
            return self._memo[i], True
        return None, False


@dataclass(frozen=True)
class ConcurrentGenerator(Generator):
    """Groups of n threads each work through their own sequence of keys
    concurrently (independent.clj:101-209). When a group's generator for
    its current key is exhausted, the group rotates to the next unclaimed
    key; the whole generator is exhausted when no keys remain and every
    group's generator is spent.
    """

    n: int                       # threads per group
    keys: KeyStream = field(compare=False)  # shared lazy key source
    gen_fn: Callable = field(compare=False)
    groups: tuple = ()           # ((threads-frozenset, key, gen) ...)
    next_idx: int = 0            # next unclaimed index into the stream

    def _claim(self, state, idx):
        """Next key from the stream, or (None, state) when exhausted."""
        k, ok = state.keys.get(idx)
        if not ok:
            return None, False
        return k, True

    def _init_groups(self, ctx):
        """Carve client threads into groups of n."""
        client_threads = sorted(t for t in ctx.workers if t != gen_mod.NEMESIS)
        groups = []
        idx = self.next_idx
        for i in range(0, len(client_threads) - self.n + 1, self.n):
            threads = frozenset(client_threads[i:i + self.n])
            k, ok = self.keys.get(idx)
            if ok:
                idx += 1
                groups.append((threads, k, tuple_gen(k, self.gen_fn(k))))
            else:
                groups.append((threads, None, None))
        return replace(self, next_idx=idx, groups=tuple(groups))

    def op(self, test, ctx):
        if not self.groups:
            inited = self._init_groups(ctx)
            if not inited.groups:
                return None
            return inited.op(test, ctx)
        candidates = []
        state = self
        for i, (threads, k, g) in enumerate(state.groups):
            # rotate exhausted groups to fresh keys
            while True:
                gg = as_gen(g)
                res = gg.op(test, ctx.restrict(threads)) if gg is not None else None
                if res is not None:
                    break
                k2, ok = state.keys.get(state.next_idx)
                if ok:
                    k = k2
                    g = tuple_gen(k, state.gen_fn(k))
                    groups = list(state.groups)
                    groups[i] = (threads, k, g)
                    state = replace(state, next_idx=state.next_idx + 1,
                                    groups=tuple(groups))
                else:
                    g = None
                    groups = list(state.groups)
                    groups[i] = (threads, None, None)
                    state = replace(state, groups=tuple(groups))
                    break
            if g is None:
                continue
            op, g2 = res
            candidates.append((op, g2, i))
        if not candidates:
            return None
        best = gen_mod.soonest_op_map(candidates)
        op, g2, i = best
        if op is PENDING:
            return (PENDING, state)
        groups = list(state.groups)
        threads, k, _ = groups[i]
        groups[i] = (threads, k, g2)
        return (op, replace(state, groups=tuple(groups)))

    def update(self, test, ctx, event):
        if not self.groups:
            return self
        p = event.get("process")
        t = gen_mod.NEMESIS if p == gen_mod.NEMESIS else ctx.thread_of(p)
        for i, (threads, k, g) in enumerate(self.groups):
            if t in threads and g is not None:
                gg = as_gen(g)
                if gg is None:
                    return self
                groups = list(self.groups)
                groups[i] = (threads, k,
                             gg.update(test, ctx.restrict(threads), event))
                return replace(self, groups=tuple(groups))
        return self


def concurrent_generator(n: int, keys: Iterable, gen_fn: Callable) -> Generator:
    """(independent.clj:211-236). n threads per key-group; len(client
    threads) should be a multiple of n. ``keys`` may be infinite
    (e.g. itertools.count())."""
    return ConcurrentGenerator(n=n, keys=KeyStream(keys), gen_fn=gen_fn)


def _freeze_key(k):
    return tuple(k) if isinstance(k, list) else k


def split_by_key(history: list[dict]) -> tuple[list, dict]:
    """``(keys, {frozen_key: sub_history})`` in ONE pass over the
    history, inner values unwrapped (independent.clj:238-262). A pass
    per key would be O(keys × history): hours of host time at 1024
    keys × 2M events."""
    keys: dict = {}
    subs: dict = {}
    for op in history:
        v = op.get("value")
        if is_tuple_value(v):
            fk = _freeze_key(v[0])
            if fk not in subs:
                keys[fk] = v[0]
                subs[fk] = []
            subs[fk].append({**op, "value": v[1]})
    return list(keys.values()), subs


def history_keys(history: list[dict]) -> list:
    """All keys in a lifted history (independent.clj:238-248)."""
    return split_by_key(history)[0]


def subhistory(k, history: list[dict]) -> list[dict]:
    """The sub-history for key k, with inner values unwrapped
    (independent.clj:250-262)."""
    return split_by_key(history)[1].get(_freeze_key(k), [])


class IndependentChecker(Checker):
    """Lifts a checker over keys (independent.clj:264-315): splits the
    history, checks each key, merges validity and reports failures by key.

    Fast path: when the inner checker is a register LinearizableChecker and
    a device is wanted, all keys are encoded and batched through one
    vmapped jitlin kernel call (optionally sharded over a mesh); keys whose
    device verdict is unsound (frontier overflow + death) fall back to the
    exact CPU search.
    """

    def __init__(self, checker: Checker):
        self.checker = checker

    def name(self):
        return f"independent({self.checker.name()})"

    @staticmethod
    def _explain_key(test, sub_history, stream, step_py, spec, failure,
                     result: dict, key_opts: dict) -> None:
        """Anomaly forensics for one invalid key of the batched device
        lane: localize + shrink over the key's own stream, artifacts
        under independent/<k> (doc/observability.md "Anomaly
        forensics"). Never fails the batch."""
        try:
            from jepsen_tpu.checker import explain as explain_mod
            tmap = test if isinstance(test, dict) else {}
            forensics = explain_mod.explain_stream(
                stream, step_ids=spec.step_ids, step_py=step_py,
                init_state=spec.init_state, failure=failure,
                shrink_budget=explain_mod.shrink_budget(tmap),
                max_witness_ops=explain_mod.max_witness_ops(tmap))
            if forensics is None:
                return
            result["explain"] = {
                "first-anomaly-op": forensics["first_anomaly"]["op_index"],
                "witness-ops": len(forensics["witness"]["op_indices"]),
                "backend": forensics["backend"],
            }
            if test is not None and isinstance(test, dict) \
                    and test.get("name"):
                arts = explain_mod.write_artifacts(
                    test, sub_history, forensics, opts=key_opts)
                if arts:
                    result["explain"]["artifacts"] = sorted(
                        str(k) for k in arts)
        except Exception:  # noqa: BLE001 — forensics never mask a verdict
            logger.exception("per-key anomaly forensics failed")

    def _explain_keys(self, test, opts, fkeys, streams, subs, step_py,
                      spec, invalid, results) -> None:
        """Forensics for the batched lane's ``invalid`` keys, into their
        ``results`` entries."""
        import jax
        if jax.process_count() > 1:
            # multi-host: split the localizations across processes,
            # allgather only the per-key positions (no witness/artifacts
            # — every host would race on the shared store dir)
            from jepsen_tpu.parallel.distributed import (
                localize_keys_distributed)
            idx = {fk: i for i, fk in enumerate(fkeys)}
            found = localize_keys_distributed(
                streams, [idx[fk] for fk, _, _ in invalid],
                step_ids=spec.step_ids, step_py=step_py,
                init_state=spec.init_state)
            for fk, _, _ in invalid:
                hit = found.get(idx[fk])
                if hit is not None:
                    results[fk]["explain"] = {
                        "first-anomaly-op": hit[1],
                        "backend": "matrix-bisect-distributed"}
            return
        for fk, stream, failure in invalid:
            # full forensics + artifacts under the same independent/<k>
            # lift the per-key lane uses
            self._explain_key(test, subs[fk], stream, step_py, spec,
                              failure, results[fk],
                              self._key_opts(opts, fk))

    @staticmethod
    def _key_opts(opts, k):
        """Per-key opts: sub-checkers write under independent/<k> like the
        reference (independent.clj:287-292), so concurrent keys' artifacts
        (timeline.html, plots) can't overwrite each other."""
        d = opts.get("subdirectory")
        return {**opts,
                "subdirectory": "/".join(
                    filter(None, [d, "independent", str(k)])),
                "history-key": k}

    def check(self, test, history, opts):
        with trace.phase(trace.CHECK_SPAN, ops=len(history)) as span:
            return self._check(test, history, opts, span)

    def _check(self, test, history, opts, span):
        # the per-key split rides the run's shared history IR when one
        # is attachable (memoized subhistories view): composed lifted
        # checkers split the history once, not once per checker
        from jepsen_tpu import history_ir
        with trace.phase("encode.ir", events=len(history)):
            ir = history_ir.of(test, history)
        with trace.phase("encode.split") as split:
            if ir is not None:
                from jepsen_tpu.history_ir import views
                keys, subs = views.subhistories(ir)
            else:
                keys, subs = split_by_key(history)
            split.set(keys=len(keys))
        span.set(keys=len(keys))
        if not keys:
            return {"valid?": True, "results": {}, "count": 0}

        batched = self._try_batched(test, keys, subs, opts)
        if batched is not None:
            results = batched
        else:
            # per-key sub-checks get ir_enabled: False — a sub-history
            # is not the run's history, so attaching it would evict the
            # run-level `_history_ir` (and serialize bounded_pmap on
            # the attach lock); the legacy per-key encode is exactly
            # what these small sub-checks should pay
            sub_test = ({**test, "ir_enabled": False}
                        if isinstance(test, dict) else test)
            pairs = list(subs.items())
            rs = bounded_pmap(
                lambda kv: check_safe(self.checker, sub_test, kv[1],
                                      self._key_opts(opts, kv[0])), pairs)
            results = {k: r for (k, _), r in zip(pairs, rs)}

        valid = merge_valid(r.get("valid?") for r in results.values())
        failures = sorted((str(k) for k, r in results.items()
                           if r.get("valid?") is not True), key=str)
        return {
            "valid?": valid,
            "count": len(results),
            "failures": failures,
            "results": {str(k): r for k, r in results.items()},
        }

    def _try_batched(self, test, keys, subs, opts):
        from jepsen_tpu.checker import Compose
        from jepsen_tpu.checker.linearizable import LinearizableChecker
        from jepsen_tpu.models import CASRegister

        # see through a Compose holding exactly one LinearizableChecker
        # (the register workload's linear+timeline composition): the
        # linear sub-checker takes the one batched kernel call, the rest
        # run per key, and per-key results merge like Compose would
        chk = self.checker
        lin_name, others = None, {}
        if isinstance(chk, Compose):
            lins = [(nm, c) for nm, c in chk.checkers.items()
                    if isinstance(c, LinearizableChecker)]
            if len(lins) != 1:
                return None
            lin_name, chk = lins[0]
            others = {nm: c for nm, c in self.checker.checkers.items()
                      if nm != lin_name}
        if not isinstance(chk, LinearizableChecker):
            return None
        if not isinstance(chk.model, CASRegister):
            return None
        accelerator = opts.get("accelerator", chk.accelerator)
        if accelerator == "cpu":
            return None
        # honor an explicit request for the exact WGL search: the batched
        # kernel is jitlin-only
        if opts.get("algorithm", chk.algorithm) == "wgl":
            return None
        try:
            from jepsen_tpu.checker import merge_valid
            from jepsen_tpu.checker.linear_cpu import check_stream
            from jepsen_tpu.ops.jitlin import verdict
            from jepsen_tpu.parallel import batch_check
            fkeys = list(subs.keys())
            # per-key encode via the checker's own _encoding so the
            # initial register value interns to the kernel's init state
            # (CASRegister(0) — single-key-acid — needs init id 1)
            with trace.phase("encode.stream", keys=len(fkeys)) as span:
                encs = [chk._encoding(subs[fk]) for fk in fkeys]
                if any(e is None for e in encs):
                    return None
                streams = [e[0] for e in encs]
                span.set(events=sum(map(len, streams)))
            step_py, spec = encs[0][1], encs[0][2]
            # accelerator=auto lets batch_check's round-trip cost model
            # route small batches to the C++/CPU lane instead of eating
            # the device dispatch latency (parallel.pipeline.CostModel);
            # the mesh knobs shard the key axis over the devices
            # (doc/performance.md "Multi-device sharding")
            from jepsen_tpu import parallel as par
            sharded, mesh_devices = par.sharding_knobs(test, opts)
            # checker_sharded: False forces single-device, True skips
            # the cost gate (explicit mesh), None = auto (cost-gated)
            mesh = False if sharded is False else None
            if sharded is True:
                mesh = par.auto_mesh(mesh_devices)
            outcomes = batch_check(
                streams, capacity=chk.capacity,
                kernel=chk._tpu_kernel(spec),
                accelerator="auto" if accelerator == "auto" else "device",
                mesh=mesh, mesh_devices=mesh_devices)
            route = par.last_route()
            from jepsen_tpu.checker.linearizable import device_algorithm
            backend = {"cpu": "jitlin-cpu(routed)",
                       "mesh": device_algorithm("-batch-sharded")}.get(
                route, device_algorithm("-batch"))
            from jepsen_tpu.checker import explain as explain_mod
            explain_on = explain_mod.enabled(test, opts)
            results = {}
            invalid: list[tuple] = []
            for fk, stream, (alive, died, ovf, peak) in zip(fkeys, streams, outcomes):
                v = verdict(alive, ovf)
                if v == "unknown":
                    res = check_stream(stream, step=step_py,
                                       init_state=spec.init_state)
                    results[fk] = {"valid?": res.valid,
                                   "algorithm": "jitlin-cpu(fallback)"}
                    v, failure = res.valid, res
                else:
                    results[fk] = {"valid?": v, "algorithm": backend,
                                   "configs-max": peak}
                    failure = None
                if v is False and explain_on:
                    invalid.append((fk, stream, failure))
            if invalid:
                # per-key anomaly forensics — an invalid key is rare, so
                # the localization dispatches stay off the happy path
                with trace.phase("settle.explain", keys=len(invalid)):
                    self._explain_keys(test, opts, fkeys, streams, subs,
                                       step_py, spec, invalid, results)
            if lin_name is None:
                return results
            pairs = list(subs.items())
            other_rs = bounded_pmap(
                lambda kv: {nm: check_safe(c, test, kv[1],
                                           self._key_opts(opts, kv[0]))
                            for nm, c in others.items()}, pairs)
            merged = {}
            for (fk, _), extra in zip(pairs, other_rs):
                sub = {lin_name: results[fk], **extra}
                merged[fk] = {
                    "valid?": merge_valid(r.get("valid?")
                                          for r in sub.values()),
                    **sub,
                }
            return merged
        except Exception:  # noqa: BLE001
            if accelerator == "tpu":
                raise    # pinned to the device: no silent per-key lane
            logger.exception("batched independent check failed; "
                             "falling back to per-key")
            return None


def checker(inner: Checker) -> Checker:
    return IndependentChecker(inner)
