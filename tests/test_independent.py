"""independent key-lifting tests (reference: independent_test.clj), incl.
the batched vmapped checker over the 8-device virtual CPU mesh."""
import random

import jepsen_tpu.generator as gen
from jepsen_tpu import independent as ind
from jepsen_tpu.checker.linearizable import LinearizableChecker
from jepsen_tpu.generator.simulate import invocations, perfect, quick


TEST = {"concurrency": 4}


def test_tuple_gen_wraps_values():
    h = quick(TEST, ind.tuple_gen("k1", gen.limit(2, gen.repeat({"f": "read"}))))
    assert all(op["value"][0] == "k1" for op in h)


def test_sequential_generator_orders_keys():
    g = ind.sequential_generator(
        ["a", "b"], lambda k: gen.limit(3, gen.repeat({"f": "w", "value": k})))
    h = quick(TEST, g)
    keys = [op["value"][0] for op in invocations(h)]
    assert keys == ["a"] * 3 + ["b"] * 3


def test_concurrent_generator_groups():
    g = ind.concurrent_generator(
        2, ["a", "b", "c", "d"],
        lambda k: gen.limit(4, gen.repeat({"f": "read"})))
    h = perfect(TEST, gen.clients(g))
    inv = invocations(h)
    assert len(inv) == 16  # 4 keys x 4 ops
    # group 0 = threads {0,1}, group 1 = threads {2,3}... with concurrency 4
    # each group claims keys in rotation; every key's ops stay in one group
    by_key = {}
    for op in inv:
        by_key.setdefault(op["value"][0], set()).add(op["process"] % 4 // 2)
    for k, groups in by_key.items():
        assert len(groups) == 1, (k, groups)


def test_history_keys_and_subhistory():
    h = [
        {"type": "invoke", "process": 0, "f": "w", "value": ["a", 1]},
        {"type": "ok", "process": 0, "f": "w", "value": ["a", 1]},
        {"type": "invoke", "process": 1, "f": "w", "value": ["b", 2]},
        {"type": "ok", "process": 1, "f": "w", "value": ["b", 2]},
    ]
    assert ind.history_keys(h) == ["a", "b"]
    sub = ind.subhistory("a", h)
    assert len(sub) == 2
    assert sub[0]["value"] == 1


def make_key_history(rng, corrupt=False):
    """A small linearizable register history (optionally corrupted)."""
    ops = []
    val = None
    for i in range(30):
        p = rng.randrange(3)
        if rng.random() < 0.5:
            v = rng.randrange(4)
            ops.append({"type": "invoke", "process": p, "f": "write", "value": v})
            ops.append({"type": "ok", "process": p, "f": "write", "value": v})
            val = v
        else:
            ops.append({"type": "invoke", "process": p, "f": "read", "value": None})
            ops.append({"type": "ok", "process": p, "f": "read", "value": val})
    if corrupt:
        for op in reversed(ops):
            if op["type"] == "ok" and op["f"] == "read":
                op["value"] = 77
                break
    return ops


def lift(k, ops):
    return [{**op, "value": [k, op["value"]]} for op in ops]


def test_independent_checker_cpu():
    rng = random.Random(3)
    h = []
    for k in range(6):
        h.extend(lift(f"k{k}", make_key_history(rng, corrupt=(k == 4))))
    chk = ind.checker(LinearizableChecker(accelerator="cpu"))
    r = chk.check({}, h, {})
    assert r["valid?"] is False
    assert r["failures"] == ["k4"]
    assert r["count"] == 6


def test_independent_checker_batched_device():
    """The vmapped/sharded fast path agrees with per-key CPU checking."""
    rng = random.Random(9)
    h = []
    bad_keys = {"k2", "k5"}
    for k in range(8):
        name = f"k{k}"
        h.extend(lift(name, make_key_history(rng, corrupt=name in bad_keys)))
    chk = ind.checker(LinearizableChecker(accelerator="tpu"))
    r = chk.check({}, h, {})
    assert r["valid?"] is False
    assert set(r["failures"]) == bad_keys
    # device kernel actually used
    assert any(v.get("algorithm", "").startswith("jitlin")
               for v in r["results"].values())


def test_batch_check_sharded_over_mesh():
    """batch_check shards keys over the 8-device virtual CPU mesh."""
    import jax
    from jepsen_tpu.checker.linear_encode import encode_register_ops
    from jepsen_tpu.parallel import batch_check, get_mesh
    assert len(jax.devices()) == 8, "conftest should give 8 virtual devices"
    rng = random.Random(11)
    streams = [encode_register_ops(make_key_history(rng, corrupt=(i % 3 == 0)))
               for i in range(11)]  # deliberately not a multiple of 8
    mesh = get_mesh()
    out = batch_check(streams, capacity=64, mesh=mesh)
    assert len(out) == 11
    for i, (alive, died, ovf, peak) in enumerate(out):
        from jepsen_tpu.checker.linear_cpu import check_stream
        expected = check_stream(streams[i]).valid
        from jepsen_tpu.ops.jitlin import verdict
        assert verdict(alive, ovf) == expected, i


def test_batched_path_sees_through_compose(tmp_path):
    """The register workload composes linear+timeline per key; the
    batched kernel path must still engage for the linear sub-checker,
    and each key's timeline must land in its own independent/<k> dir."""
    import os

    from jepsen_tpu import checker as chk
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import CASRegister

    inner = chk.compose({"linear": linearizable(model=CASRegister()),
                         "timeline": chk.timeline_html()})
    c = ind.checker(inner)
    h = []
    for k in ("a", "b"):
        h += [
            {"type": "invoke", "process": 0, "f": "write", "value": [k, 1],
             "time": 1},
            {"type": "ok", "process": 0, "f": "write", "value": [k, 1],
             "time": 2},
            {"type": "invoke", "process": 1, "f": "read", "value": [k, None],
             "time": 3},
            {"type": "ok", "process": 1, "f": "read", "value": [k, 1],
             "time": 4},
        ]
    test = {"name": "ind-compose", "start_time": "t0",
            "store_dir": str(tmp_path)}
    out = c.check(test, h, {})
    assert out["valid?"] is True
    for k in ("a", "b"):
        sub = out["results"][k]
        assert sub["linear"]["algorithm"].startswith("jitlin"), sub
        assert sub["timeline"]["valid?"] is True
        assert os.path.exists(
            tmp_path / "ind-compose" / "t0" / "independent" / k
            / "timeline.html")


def test_batched_device_path_actually_engages():
    """Regression: the batched independent fast path must produce
    device-kernel verdicts (labelled with the platform that ran them),
    not silently fall back per-key (a signature
    drift in the checker once made every batch raise and the broad
    fallback ate it)."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import CASRegister

    history = []
    for k in range(3):
        for i, v in enumerate([1, 2, 3]):
            history.append({"type": "invoke", "process": k, "f": "write",
                            "value": [k, v]})
            history.append({"type": "ok", "process": k, "f": "write",
                            "value": [k, v]})
    chk = independent.checker(linearizable(model=CASRegister(),
                                           accelerator="tpu"))
    out = chk.check({}, history, {})
    assert out["valid?"] is True
    per_key = list(out["results"].values())
    assert len(per_key) == 3, out
    assert all(r.get("algorithm") == "jitlin-cpu-batch"
               for r in per_key), out


def test_batched_device_path_nonzero_init_state():
    """CASRegister(0) (single-key-acid) must thread its initial value
    through the batched encoding: a first read of 0 is valid."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker.linearizable import linearizable
    from jepsen_tpu.models import CASRegister

    history = []
    for k in range(2):
        history.append({"type": "invoke", "process": k, "f": "read",
                        "value": None})
        history.append({"type": "ok", "process": k, "f": "read",
                        "value": [k, 0]})
    chk = independent.checker(linearizable(model=CASRegister(0),
                                           accelerator="tpu"))
    out = chk.check({}, history, {})
    assert out["valid?"] is True, out
