#!/usr/bin/env python3
"""Chip smoke: the checker's main path, once, on a TPU.

Every phase goes through a public checker entry point with
``accelerator="tpu"`` and holds its verdict to an oracle: the CPU
checker on the same input, or an anomaly planted where the answer is
known. All inputs are made from ``--seed``. One process drives the
chip for the whole run; no phase starts a child.

    python chip_smoke.py              # every phase, one device
    python chip_smoke.py --chips 4    # 1M-op chain + 1024-key batch on a
                                      # 4-device mesh vs one device

Each phase prints one JSON line (name, size, verdict, cold and warm
seconds, the ladder rung that settled, the matrix kernel variant). The
last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A run that finds no TPU, or any phase whose verdict differs from its
oracle, whose rung is a host rung, whose pallas probe failed or whose
matrix dispatch fell back to the XLA scan, exits non-zero without that
line.
"""
from __future__ import annotations

import argparse
import faulthandler
import functools
import json
import sys
import time
import traceback

# a read value no writer ever writes: planting it makes the history
# non-linearizable at exactly that read
NEVER_WRITTEN = 1_000_003
PHASE_STACK_DUMP_S = 240


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# inputs, all from the seed
# ---------------------------------------------------------------------------

# inputs are memoized (and never mutated: plant_bad_read copies), so a
# comparison that checks one input twice generates it once
@functools.cache
def register_history(n_ops: int, seed: int) -> list[dict]:
    """BASELINE config 2's shape: 5 processes, rand-int-5 values."""
    from __graft_entry__ import _register_history
    return _register_history(n_ops, n_procs=5, seed=seed, n_values=5)


def plant_bad_read(history: list[dict], after: int) -> tuple[list, int]:
    """A copy with the first ok read at index >= ``after`` returning a
    value never written; returns (copy, index of that read)."""
    for i in range(after, len(history)):
        op = history[i]
        if op["type"] == "ok" and op["f"] == "read":
            bad = list(history)
            bad[i] = {**op, "value": NEVER_WRITTEN}
            return bad, i
    raise SmokeFailure(f"no ok read at or after index {after}")


@functools.cache
def keyed_history(n_keys: int, n_ops: int, seed: int,
                  bad_key: int | None = None) -> list[dict]:
    """``n_keys`` independent registers, ``n_ops`` ops each (BASELINE
    config 3), as one jepsen.independent history of [key, value] ops;
    ``bad_key`` gets a planted bad read."""
    out: list[dict] = []
    for k in range(n_keys):
        h = register_history(n_ops, seed + k)
        if k == bad_key:
            h, _ = plant_bad_read(h, len(h) // 2)
        for op in h:
            out.append({**op, "process": k * 5 + op["process"],
                        "value": [k, op["value"]]})
    return out


def set_full_history(n_els: int, read_every: int = 50) -> list[dict]:
    """BASELINE config 4: every element added, read back every 50 adds."""
    history, present, t = [], [], 0
    for v in range(n_els):
        history.append({"type": "invoke", "process": v % 5, "f": "add",
                        "value": v, "time": t})
        history.append({"type": "ok", "process": v % 5, "f": "add",
                        "value": v, "time": t + 1})
        present.append(v)
        t += 2
        if (v + 1) % read_every == 0:
            history.append({"type": "invoke", "process": 5, "f": "read",
                            "value": None, "time": t})
            history.append({"type": "ok", "process": 5, "f": "read",
                            "value": list(present), "time": t + 1})
            t += 2
    return history


# ---------------------------------------------------------------------------
# what ran
# ---------------------------------------------------------------------------

def rung_of(algorithm: str) -> str:
    """The ladder rung a linearizable label names (labels carry the
    platform that ran them: checker/linearizable.py device_algorithm)."""
    if algorithm.endswith("-matrix-sharded"):
        return "sharded-matrix"
    if algorithm.endswith("-matrix"):
        return "pallas-matrix"
    if algorithm.endswith("-frontier"):
        return "jitlin-device"
    if algorithm.endswith(("-batch", "-batch-sharded")):
        return "batch"
    if algorithm == "jitlin-native":
        return "native-c"
    return "cpu"


def require_device_label(algorithm: str) -> None:
    import jax
    platform = jax.default_backend()
    check(algorithm.startswith(f"jitlin-{platform}")
          and rung_of(algorithm) not in ("native-c", "cpu"),
          f"device phase settled on {algorithm!r}")


def matrix_variant(S: int, V: int, sharded: bool = False) -> str:
    """The variant of the calling thread's last matrix dispatch; a
    single-device dispatch in the pallas regime must not have fallen
    back to the XLA scan, and no pallas probe may have failed. (The
    mesh kernels are XLA scans by design: jitlin's shard_map twin.)"""
    from jepsen_tpu.ops import pallas_matrix as pm
    from jepsen_tpu.ops.jitlin import last_dispatch_info, last_phase_seconds
    failed = sorted(str(k) for k, ok in pm._PROBED.items() if not ok)
    check(not failed, f"pallas probes failed: {failed}")
    check(not pm._DISABLED, f"pallas variants disabled at runtime: "
                            f"{sorted(map(str, pm._DISABLED))}")
    if sharded:
        return "scan(mesh)"
    variant = (last_phase_seconds().get("variant")
               or last_dispatch_info().get("variant"))
    regime = any(pm.variant_ok(v, S, V) for v in pm.VARIANTS)
    check(not (regime and variant == "scan"),
          f"matrix dispatch at S={S} V={V} fell back to the XLA scan")
    return str(variant)


def stream_shape(history) -> tuple[int, int]:
    """(S, V) the matrix kernel runs for this register history."""
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.ops.jitlin import _bucket
    s = encode_register_ops(history)
    return max(1, s.n_slots), _bucket(len(s.intern), floor=8)


def note(msg: str) -> None:
    """Progress on stderr, so a run cut by its time limit shows where."""
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, round(time.perf_counter() - t0, 3)


def cold_warm(fn, warm: bool = True):
    """(first, second, cold seconds, warm seconds): the same work twice,
    the first paying its compiles; ``warm=False`` runs it once."""
    first, cold = timed(fn)
    note(f"cold {cold}s")
    if not warm:
        return first, first, cold, None
    second, warm_s = timed(fn)
    note(f"warm {warm_s}s")
    return first, second, cold, warm_s


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_register(n_ops: int, seed: int, sharded: bool = False,
                   mesh_devices: int | None = None) -> dict:
    """One register history through LinearizableChecker, valid and with
    one planted bad read; both held to the CPU oracle."""
    from jepsen_tpu.checker.linear_cpu import check_stream
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    good = register_history(n_ops, seed)
    bad, bad_i = plant_bad_read(good, len(good) * 9 // 10)
    test = {"checker_sharded": sharded, "mesh_devices": mesh_devices}
    chk = LinearizableChecker(accelerator="tpu")

    def run():
        return (chk.check(dict(test), good, {}),
                chk.check(dict(test), bad, {}))

    (g1, b1), (g2, b2), cold, warm = cold_warm(run)
    for g, b in ((g1, b1), (g2, b2)):
        for out in (g, b):
            require_device_label(out["algorithm"])
    note("cpu oracle")
    cpu_good = check_stream(encode_register_ops(good))
    cpu_bad = check_stream(encode_register_ops(bad))
    check(g1["valid?"] is cpu_good.valid is True,
          f"valid history: device {g1['valid?']}, cpu {cpu_good.valid}")
    check(b1["valid?"] is cpu_bad.valid is False,
          f"bad history: device {b1['valid?']}, cpu {cpu_bad.valid}")
    check(b1.get("failed-op") == bad[cpu_bad.failed_op_index],
          f"device failed-op {b1.get('failed-op')} != cpu "
          f"{bad[cpu_bad.failed_op_index]}")
    check(g2["valid?"] is True and b2["valid?"] is False
          and b2.get("failed-op") == b1.get("failed-op"),
          "warm verdicts differ from cold")
    S, V = stream_shape(good)
    return {"size": f"{n_ops} ops", "verdict": "valid+invalid",
            "oracle": "linear_cpu.check_stream",
            "cold_s": cold, "warm_s": warm,
            "rung": rung_of(b1["algorithm"]),
            "variant": matrix_variant(S, V)}


def phase_register_chain(n_ops: int, seed: int, sharded: bool = False,
                         mesh_devices: int | None = None,
                         warm: bool = True) -> dict:
    """A history longer than one matrix segment (the segmented chain):
    the valid one verifies, and one planted bad read past the midpoint
    is the op the device reports."""
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    good = register_history(n_ops, seed)
    bad, bad_i = plant_bad_read(good, len(good) // 2 + 1)
    test = {"checker_sharded": sharded, "mesh_devices": mesh_devices}
    chk = LinearizableChecker(accelerator="tpu")

    def run():
        return (chk.check(dict(test), good, {}),
                chk.check(dict(test), bad, {}))

    (g1, b1), (g2, b2), cold, warm = cold_warm(run, warm)
    for out in (g1, b1, g2, b2):
        require_device_label(out["algorithm"])
    check(g1["valid?"] is True and g2["valid?"] is True,
          f"valid {n_ops}-op history did not verify: {g1['valid?']}")
    check(b1["valid?"] is False and b2["valid?"] is False,
          f"planted bad read not found: {b1['valid?']}")
    reported = (b1.get("explain") or {}).get("first-anomaly-op")
    check(b1.get("failed-op") == bad[bad_i] or reported == bad_i,
          f"device reported {b1.get('failed-op')} / op {reported}, "
          f"planted at {bad_i}: {bad[bad_i]}")
    check(b2.get("failed-op") == b1.get("failed-op"),
          "warm verdict differs from cold")
    S, V = stream_shape(good[:20_000])
    return {"size": f"{n_ops} ops", "verdict": "valid+invalid",
            "oracle": f"planted bad read at op {bad_i}",
            "cold_s": cold, "warm_s": warm,
            "rung": rung_of(b1["algorithm"]),
            "variant": matrix_variant(S, V, sharded),
            "_verdicts": (g1["valid?"], b1["valid?"], b1.get("failed-op"))}


def phase_independent(n_keys: int, n_ops: int, seed: int,
                      sharded: bool = False,
                      mesh_devices: int | None = None,
                      warm: bool = True) -> dict:
    """jepsen.independent registers with one bad key, device vs CPU
    (``warm=False``, the mesh comparison: one pass, and the planted key
    stands in for the CPU checker)."""
    from jepsen_tpu import independent, parallel
    from jepsen_tpu.checker.linearizable import LinearizableChecker

    bad_key = n_keys // 2
    history = keyed_history(n_keys, n_ops, seed, bad_key=bad_key)
    test = {"checker_sharded": sharded, "mesh_devices": mesh_devices}
    dev = independent.checker(LinearizableChecker(accelerator="tpu"))
    routes: list[str] = []

    def run():
        out = dev.check(dict(test), history, {})
        routes.append(parallel.last_route())
        return out

    d1, d2, cold, warm_s = cold_warm(run, warm)
    per_key = {k: r["valid?"] for k, r in d1["results"].items()}
    check(d1["valid?"] is False and d1["failures"] == [str(bad_key)],
          f"failures: device {d1['failures']}, planted {bad_key}")
    if warm:
        note("cpu oracle")
        cpu = independent.checker(
            LinearizableChecker(accelerator="cpu")).check({}, history, {})
        check(per_key == {k: r["valid?"]
                          for k, r in cpu["results"].items()},
              "per-key verdicts differ from the CPU checker")
    check(per_key == {k: r["valid?"] for k, r in d2["results"].items()},
          "warm verdicts differ from cold")
    for r in d1["results"].values():
        require_device_label(r["algorithm"])
    check(routes[0] == ("mesh" if sharded else "device"),
          f"independent batch took the {routes[0]!r} route")
    S, V = stream_shape(register_history(n_ops, seed))
    return {"size": f"{n_keys}x{n_ops} ops", "verdict": "invalid (1 key)",
            "oracle": ("independent(accelerator=cpu)" if warm
                       else f"planted bad key {bad_key}"),
            "cold_s": cold, "warm_s": warm_s,
            "rung": f"batch:{routes[0]}",
            "variant": matrix_variant(S, V, sharded),
            "_verdicts": per_key}


def phase_set_full(n_els: int) -> dict:
    from jepsen_tpu.checker import SetFullChecker

    history = set_full_history(n_els)
    dev = SetFullChecker(accelerator="tpu")
    d1, d2, cold, warm = cold_warm(lambda: dev.check({}, history, {}))
    cpu = SetFullChecker(accelerator="cpu").check({}, history, {})
    check(not d1.get("device-fallback"), "set-full took the device fallback")
    keys = [k for k in cpu if k.endswith("-count")] + ["valid?"]
    check(all(d1.get(k) == cpu[k] == d2.get(k) for k in keys),
          f"set-full differs from cpu: "
          f"{ {k: (d1.get(k), cpu[k]) for k in keys} }")
    check(d1["valid?"] is True, f"set-full invalid: {d1['valid?']}")
    return {"size": f"{n_els} elements", "verdict": d1["valid?"],
            "oracle": "SetFullChecker(accelerator=cpu)",
            "cold_s": cold, "warm_s": warm, "rung": "setscan"}


def _elle_history(n_txns: int, n_keys: int = 100, crossed_pairs: int = 0):
    """Serializable list-append history; ``crossed_pairs`` appends pairs
    of mutually-observing txns (wr edges both ways → G1c 2-cycles), which
    defeats the acyclicity screen and forces the trim + cycle search."""
    history = []
    t = 0

    def txn(proc, mops_inv, mops_ok):
        nonlocal t
        history.append({"type": "invoke", "process": proc,
                        "value": mops_inv, "time": t})
        history.append({"type": "ok", "process": proc,
                        "value": mops_ok, "time": t + 1})
        t += 2

    for i in range(n_txns):
        k = i % n_keys
        seen = list(range(k, i + 1, n_keys))  # every append to k so far
        txn(i % 10, [["append", k, i], ["r", k, None]],
            [["append", k, i], ["r", k, seen]])
    for p in range(crossed_pairs):
        ka, kb = 10_000 + 2 * p, 10_001 + 2 * p
        va, vb = 2_000_000 + 2 * p, 2_000_001 + 2 * p
        # A observes B's append before B commits; B observes A's: a wr
        # cycle between the two on fresh keys
        txn(10, [["append", ka, va], ["r", kb, None]],
            [["append", ka, va], ["r", kb, [vb]]])
        txn(11, [["append", kb, vb], ["r", ka, None]],
            [["append", kb, vb], ["r", ka, [va]]])
    return history


def phase_elle(n_txns: int, crossed_pairs: int) -> dict:
    """Elle list-append, device vs the CPU path's anomaly types."""
    from jepsen_tpu.elle import list_append

    history = _elle_history(n_txns, crossed_pairs=crossed_pairs)
    d1, d2, cold, warm = cold_warm(
        lambda: list_append.check(history, accelerator="tpu"))
    cpu = list_append.check(history, accelerator="cpu")
    types = sorted(d1.get("anomaly-types") or [])
    check(d1["valid?"] is cpu["valid?"] is (crossed_pairs == 0),
          f"elle: device {d1['valid?']}, cpu {cpu['valid?']}")
    check(types == sorted(cpu.get("anomaly-types") or [])
          == sorted(d2.get("anomaly-types") or []),
          f"elle anomaly types: device {types}, cpu "
          f"{cpu.get('anomaly-types')}")
    # a history whose dependency edges all advance the phi order is
    # settled by the host screen before any cluster reaches the device
    rung = "phi-screen(host)" if crossed_pairs == 0 else "cluster-screen"
    return {"size": f"{n_txns + 2 * crossed_pairs} txns",
            "verdict": d1["valid?"], "anomalies": types,
            "oracle": "list_append.check(accelerator=cpu)",
            "cold_s": cold, "warm_s": warm, "rung": rung}


def phase_suite(time_limit: float) -> dict:
    """The etcd suite in fake mode through core.run, in this process."""
    import tempfile

    from jepsen_tpu import core
    from jepsen_tpu.suites import etcd

    def run():
        with tempfile.TemporaryDirectory() as tmp:
            test = etcd.etcd_test({"fake": True, "accelerator": "tpu",
                                   "time_limit": time_limit,
                                   "store_dir": tmp, "no_perf": True})
            return core.run(test)

    r1, r2, cold, warm = cold_warm(run)
    labels = set()

    def walk(x):
        if isinstance(x, dict):
            if isinstance(x.get("algorithm"), str):
                labels.add(x["algorithm"])
            for v in x.values():
                walk(v)

    walk(r1["results"])
    for label in labels:
        if label.startswith("jitlin"):
            require_device_label(label)
    check(r1["results"]["valid?"] is True and r2["results"]["valid?"] is True,
          f"fake etcd run: {r1['results'].get('valid?')}")
    return {"size": f"{len(r1['history'])} events",
            "verdict": r1["results"]["valid?"], "oracle": "valid by design",
            "cold_s": cold, "warm_s": warm,
            "rung": ",".join(sorted(labels)) or "none"}


def default_phases(seed: int) -> list:
    return [
        ("register_10k", lambda: phase_register(10_000, seed)),
        ("register_1m", lambda: phase_register_chain(1_000_000, seed + 1)),
        ("independent_64x1k", lambda: phase_independent(64, 1000, seed + 2)),
        ("independent_1024x1k",
         lambda: phase_independent(1024, 1000, seed + 2)),
        ("set_full_20k", lambda: phase_set_full(20_000)),
        ("elle_50k", lambda: phase_elle(50_000, 0)),
        ("elle_50k_anomalous", lambda: phase_elle(50_000, 50)),
        ("etcd_fake_suite", lambda: phase_suite(5.0)),
    ]


def mesh_phases(seed: int, n: int, chain_ops: int = 1_000_000,
                n_keys: int = 1024, key_ops: int = 1000) -> list:
    """--chips N: the sharded rung and the independent mesh route, each
    against the same check pinned to one device (one pass each: the
    comparison is of verdicts, and four chips cost four times one)."""
    import jax
    from jepsen_tpu import parallel

    def compare(name, fn):
        def run():
            mesh = parallel.auto_mesh(n)
            check(mesh is not None and len(
                {d.id for d in mesh.devices.flat}) == n,
                f"mesh spans {0 if mesh is None else mesh.devices.size} "
                f"distinct devices, want {n}")
            check(len(jax.devices()) >= n, f"need {n} devices")
            one = fn(False, None)
            many = fn(True, n)
            check(one.pop("_verdicts") == many.pop("_verdicts"),
                  f"{name}: {n}-device verdicts differ from one device")
            many["single_device"] = {k: one[k] for k in
                                     ("cold_s", "warm_s", "rung")}
            many["mesh_devices"] = sorted(d.id for d in mesh.devices.flat)
            return many
        return name, run

    return [
        compare("register_1m_mesh", lambda sh, nd: phase_register_chain(
            chain_ops, seed + 1, sharded=sh, mesh_devices=nd, warm=False)),
        compare("independent_1024x1k_mesh", lambda sh, nd: phase_independent(
            n_keys, key_ops, seed + 2, sharded=sh, mesh_devices=nd,
            warm=False)),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mesh phases, against one device")
    args = ap.parse_args(argv)
    try:
        import jax

        from jepsen_tpu import compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the checker: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    compile_cache.enable()
    phases = (default_phases(args.seed) if args.chips == 1
              else mesh_phases(args.seed, args.chips))
    failed = []
    for name, fn in phases:
        note(f"{name} starts")
        # a phase this slow is stuck: dump every thread's stack
        faulthandler.dump_traceback_later(PHASE_STACK_DUMP_S)
        try:
            line = {"phase": name, **fn()}
            line.pop("_verdicts", None)
        except Exception as e:  # noqa: BLE001 — report every phase
            traceback.print_exc()
            failed.append(name)
            line = {"phase": name, "error": f"{type(e).__name__}: {e}"}
        finally:
            faulthandler.cancel_dump_traceback_later()
        print(json.dumps(line, default=str), flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
