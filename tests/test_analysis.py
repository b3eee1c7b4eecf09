"""Static-analysis tier: preflight diagnostics + the invariant linter.

Every preflight diagnostic and every lint rule gets a deliberately
broken fixture (true positive) AND its corrected twin (must stay
silent) — the "both directions" contract from doc/static-analysis.md.
The self-lint gate at the bottom runs the linter over ``jepsen_tpu/``
itself and fails on any non-baselined finding, which is what turns a
future concurrency/JAX invariant regression into a red build instead of
a review catch.
"""
from __future__ import annotations

import textwrap

import pytest

from jepsen_tpu import core, fakes
from jepsen_tpu import generator as gen
from jepsen_tpu import nemesis as nem
from jepsen_tpu.analysis import lint as lint_mod
from jepsen_tpu.analysis import preflight as pf
from jepsen_tpu.analysis.preflight import PreflightFailed

pytestmark = pytest.mark.lint


def _pf(test):
    return pf.preflight(core.prepare_test(test))


def _codes(diags):
    return [d.code for d in diags]


def _atom_test(**over):
    db = fakes.AtomDB()
    base = dict(db=db, client=fakes.AtomClient(db), ssh={"dummy": True})
    base.update(over)
    return fakes.noop_test(**base)


# ---------------------------------------------------------------------------
# Preflight: one broken fixture per diagnostic, plus the corrected twin
# ---------------------------------------------------------------------------

class TestPreflightDiagnostics:
    def test_gen001_unsupported_f(self):
        t = _atom_test(generator=gen.limit(5, {"f": "frobnicate"}))
        diags = _pf(t)
        assert "GEN001" in _codes(diags)
        assert any(d.severity == "error" for d in diags)

    def test_gen001_silent_on_supported_f(self):
        t = _atom_test(generator=gen.limit(5, {"f": "read"}))
        assert "GEN001" not in _codes(_pf(t))

    def test_gen002_empty_generator(self):
        t = _atom_test(generator=gen.limit(0, {"f": "read"}))
        assert "GEN002" in _codes(_pf(t))

    def test_gen003_truncated_enumeration(self):
        t = _atom_test(generator=gen.repeat({"f": "read"}),
                       preflight_ops=16)
        diags = _pf(t)
        assert "GEN003" in _codes(diags)
        # truncation is informational, never fatal
        assert all(d.severity != "error" for d in diags
                   if d.code == "GEN003")

    def test_gen005_stateful_generator_skipped(self):
        from jepsen_tpu.workloads import set_workload
        w = set_workload.workload()
        kv = fakes.KVStore()
        t = fakes.noop_test(db=kv, client=fakes.KVClient(kv),
                            generator=w["generator"])
        diags = _pf(t)
        assert _codes(diags) == ["GEN005"]

    def test_gen006_malformed_op(self):
        t = _atom_test(generator=gen.limit(2, {"f": "read",
                                               "type": "bogus"}))
        assert "GEN006" in _codes(_pf(t))

    def test_cli001_client_ops_without_client(self):
        t = fakes.noop_test(client=None,
                            generator=gen.limit(3, {"f": "read"}))
        assert "CLI001" in _codes(_pf(t))

    def test_nem001_nemesis_ops_without_nemesis(self):
        t = _atom_test(generator=gen.nemesis_gen(
            gen.limit(2, {"f": "start-partition"})))
        diags = _pf(t)
        assert "NEM001" in _codes(diags)
        assert all(d.severity != "error" for d in diags)  # warning only

    def test_nem002_unhealable_kind(self):
        t = _atom_test(
            nemesis=nem.TruncateFile("/tmp/x"),
            generator=gen.nemesis_gen(gen.limit(2, {"f": "truncate-file"})))
        diags = _pf(t)
        assert [d.code for d in diags if d.severity == "error"] \
            == ["NEM002"]

    def test_nem002_downgraded_by_allow_list(self):
        t = _atom_test(
            nemesis=nem.TruncateFile("/tmp/x"),
            generator=gen.nemesis_gen(gen.limit(2, {"f": "truncate-file"})),
            preflight_allow=["NEM002"])
        diags = _pf(t)
        assert all(d.severity != "error" for d in diags)
        assert "NEM002" in _codes(diags)

    def test_nem003_outside_nemesis_surface(self):
        t = _atom_test(
            nemesis=nem.partition_halves(),
            generator=gen.nemesis_gen(gen.limit(2, {"f": "scramble-clock"})))
        assert "NEM003" in _codes(_pf(t))

    def test_nem003_silent_on_matching_surface(self):
        t = _atom_test(
            nemesis=nem.partition_halves(),
            generator=gen.nemesis_gen(
                gen.limit(2, [{"f": "start-partition"},
                              {"f": "stop-partition"}])))
        diags = _pf(t)
        assert "NEM003" not in _codes(diags)
        assert "NEM002" not in _codes(diags)  # net faults heal fine

    def test_knb001_garbage_knob(self):
        t = _atom_test(op_timeout_s="banana")
        diags = _pf(t)
        assert "KNB001" in _codes(diags)

    def test_knb001_silent_on_numeric(self):
        t = _atom_test(op_timeout_s=30.0)
        assert "KNB001" not in _codes(_pf(t))

    def test_knb002_negative_timeout(self):
        t = _atom_test(drain_timeout_s=-5)
        assert "KNB002" in _codes(_pf(t))

    def test_knb003_bad_concurrency(self):
        t = fakes.noop_test(concurrency="wat")
        # prepare_test would choke on this, so check the raw map
        assert "KNB003" in _codes(pf.preflight(t))

    def test_knb004_nodes_without_workers(self):
        t = _atom_test(concurrency=2)  # 5 nodes
        diags = _pf(t)
        assert "KNB004" in _codes(diags)
        assert all(d.severity == "warning" for d in diags
                   if d.code == "KNB004")

    def test_knb007_matrix_variant_enum(self):
        t = _atom_test(matrix_variant="bf16")
        diags = _pf(t)
        assert "KNB007" in _codes(diags)
        assert "KNB007" not in _codes(_pf(_atom_test(
            matrix_variant="int8")))
        assert "KNB007" not in _codes(_pf(_atom_test(
            matrix_variant="auto")))

    def test_knb_combine_fused_bool(self):
        assert "KNB001" in _codes(_pf(_atom_test(combine_fused="maybe")))
        diags = _pf(_atom_test(combine_fused="yes"))
        assert "KNB001" not in _codes(diags)   # stringly bool: warn only
        assert "KNB006" in _codes(diags)
        assert "KNB001" not in _codes(_pf(_atom_test(combine_fused=True)))

    def test_knb007_env_routing_knobs(self, monkeypatch):
        monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "sometimes")
        assert "KNB007" in _codes(_pf(_atom_test()))
        monkeypatch.setenv("JEPSEN_TPU_PALLAS_PROBE", "skip")
        monkeypatch.setenv("JEPSEN_TPU_MATRIX_VARIANT", "int8")
        monkeypatch.setenv("JEPSEN_TPU_FUSE_COMBINE", "off")
        assert "KNB007" not in _codes(_pf(_atom_test()))
        monkeypatch.setenv("JEPSEN_TPU_FUSE_COMBINE", "fast")
        assert "KNB007" in _codes(_pf(_atom_test()))

    def test_knb005_deadline_exceeds_time_limit(self):
        t = _atom_test(op_timeout_s=600, time_limit=30)
        assert "KNB005" in _codes(_pf(t))

    def test_knb005_silent_when_defaults(self):
        t = _atom_test(time_limit=30)  # op timeout not explicitly set
        assert "KNB005" not in _codes(_pf(t))

    def test_chk001_model_mismatch(self):
        from jepsen_tpu.checker.linearizable import LinearizableChecker
        t = _atom_test(
            client=fakes.KVClient(fakes.KVStore()),
            checker=LinearizableChecker(),
            generator=gen.limit(4, {"f": "enqueue", "value": 1}))
        assert "CHK001" in _codes(_pf(t))

    def test_chk001_silent_on_matching_model(self):
        from jepsen_tpu.checker.linearizable import LinearizableChecker
        t = _atom_test(checker=LinearizableChecker(),
                       generator=gen.limit(4, {"f": "read"}))
        assert "CHK001" not in _codes(_pf(t))

    def test_clean_test_has_no_diagnostics(self):
        t = _atom_test(generator=gen.limit(5, {"f": "read"}))
        assert _pf(t) == []


class TestPreflightGate:
    """The core.run integration: reject before node contact, escape
    hatch restores old behavior."""

    def test_rejects_before_any_node_setup(self, tmp_path):
        db = fakes.AtomDB()
        t = fakes.noop_test(
            db=db, client=fakes.AtomClient(db),
            generator=gen.limit(5, {"f": "frobnicate"}),
            store_dir=str(tmp_path), name="pf-reject")
        with pytest.raises(PreflightFailed) as ei:
            core.run(t)
        assert [d.code for d in ei.value.errors] == ["GEN001"]
        # nothing lifecycle-shaped happened: no db setup, no client open
        assert db.log == []

    def test_no_preflight_escape_hatch(self, tmp_path):
        db = fakes.AtomDB()
        t = fakes.noop_test(
            db=db, client=fakes.AtomClient(db),
            generator=gen.limit(3, {"f": "frobnicate"}),
            store_dir=str(tmp_path), name="pf-skip", preflight=False)
        res = core.run(t)
        # the old behavior: the run happens, unknown fs fail per-op
        assert {op.get("f") for op in res["history"]} == {"frobnicate"}

    def test_clean_run_passes_gate(self, tmp_path):
        db = fakes.AtomDB()
        t = fakes.noop_test(
            db=db, client=fakes.AtomClient(db),
            generator=gen.limit(3, {"f": "read"}),
            store_dir=str(tmp_path), name="pf-clean")
        res = core.run(t)
        assert (res.get("results") or {}).get("valid?") is True

    def test_failure_counter_exported(self, tmp_path):
        from jepsen_tpu import telemetry
        db = fakes.AtomDB()
        t = fakes.noop_test(
            db=db, client=fakes.AtomClient(db),
            generator=gen.limit(5, {"f": "frobnicate"}),
            store_dir=str(tmp_path), name="pf-counter")
        with pytest.raises(PreflightFailed):
            core.run(t)
        # the registry was torn down with the run; check the export
        prom = (tmp_path / "pf-counter").glob("*/metrics.prom")
        text = "".join(p.read_text() for p in prom)
        assert 'preflight_failures_total{code="GEN001"} 1' in text

    def test_skip_counter(self):
        from jepsen_tpu import telemetry
        reg = telemetry.Registry()
        with telemetry.use(reg):
            core._preflight_gate({"preflight": False})
        assert reg.counter("preflight_skipped_total").value() == 1


class TestSimulateCaps:
    def test_seeded_enumeration_is_deterministic(self):
        from jepsen_tpu.generator import simulate as sim
        g = gen.mix([{"f": "a"}, {"f": "b"}, {"f": "c"}])
        t = {"concurrency": 3}
        runs = [sim.quick(t, gen.limit(30, gen.cycle(g)), seed=7)
                for _ in range(2)]
        assert runs[0] == runs[1]
        other = sim.quick(t, gen.limit(30, gen.cycle(g)), seed=8)
        assert [o["f"] for o in other] != [] \
            and isinstance(other, list)

    def test_op_cap_terminates_infinite_generator(self):
        from jepsen_tpu.generator import simulate as sim
        hist = sim.quick({"concurrency": 2},
                         gen.repeat({"f": "read"}), limit=50)
        assert 0 < len(hist) <= 100  # invokes + completions, bounded

    def test_wall_cap_terminates(self):
        from jepsen_tpu.generator import simulate as sim
        import time as _t

        def slow(test, ctx):
            _t.sleep(0.01)
            return {"f": "read"}

        t0 = _t.monotonic()
        sim.quick({"concurrency": 2}, gen.Fn(slow), max_wall_s=0.2)
        assert _t.monotonic() - t0 < 2.0


# ---------------------------------------------------------------------------
# Lint rules: one broken fixture + corrected twin per rule
# ---------------------------------------------------------------------------

def _lint_source(tmp_path, source, rules=None, name="fx.py"):
    d = tmp_path / "fixture_pkg"
    d.mkdir(exist_ok=True)
    (d / name).write_text(textwrap.dedent(source), encoding="utf-8")
    rep = lint_mod.lint_paths([str(d)], baseline=False, rules=rules)
    return rep.findings


class TestLintRules:
    def test_lock_guard_fires_and_corrected_silent(self, tmp_path):
        bad = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def add(self, x):
                    with self._lock:
                        self.items.append(x)

                def racy(self, x):
                    self.items.append(x)
        """
        finds = _lint_source(tmp_path, bad, rules=["lock-guard"])
        assert [f.rule for f in finds] == ["lock-guard"]
        good = bad.replace(
            "def racy(self, x):\n                    self.items.append(x)",
            "def racy(self, x):\n                    "
            "with self._lock:\n                        "
            "self.items.append(x)")
        assert _lint_source(tmp_path, good, rules=["lock-guard"]) == []

    def test_lock_guard_exempts_lock_held_helper(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.items = []

                def add(self, x):
                    with self._lock:
                        self.items.append(x)

                def _wipe(self):
                    self.items.clear()

                def reset(self):
                    with self._lock:
                        self._wipe()
        """
        assert _lint_source(tmp_path, src, rules=["lock-guard"]) == []

    def test_thread_owner_reachability(self, tmp_path):
        bad = """
            def mutate():  # owner: scheduler
                pass

            def step():
                mutate()

            def worker_loop():  # owner: worker
                step()
        """
        finds = _lint_source(tmp_path, bad, rules=["thread-owner"])
        assert [f.rule for f in finds] == ["thread-owner"]
        assert "worker_loop" in finds[0].message
        good = bad.replace("# owner: scheduler", "# owner: any")
        assert _lint_source(tmp_path, good, rules=["thread-owner"]) == []

    def test_no_unbounded_block(self, tmp_path):
        bad = """
            def pump(q):  # owner: scheduler
                q.put_nowait(1)
                return q.get()
        """
        finds = _lint_source(tmp_path, bad, rules=["no-unbounded-block"])
        assert [f.rule for f in finds] == ["no-unbounded-block"]
        good = bad.replace("q.get()", "q.get(timeout=1.0)")
        assert _lint_source(tmp_path, good,
                            rules=["no-unbounded-block"]) == []

    def test_no_unbounded_block_ignores_dict_get(self, tmp_path):
        src = """
            def lookup(d):  # owner: scheduler
                return d.get("k")
        """
        assert _lint_source(tmp_path, src,
                            rules=["no-unbounded-block"]) == []

    def test_fsync_pairing(self, tmp_path):
        bad = """
            import os

            class Wal:  # durability: fsync
                def __init__(self, f):
                    self._f = f

                def append(self, line):
                    self._f.write(line)
                    self._f.flush()
        """
        finds = _lint_source(tmp_path, bad, rules=["fsync-pairing"])
        assert [f.rule for f in finds] == ["fsync-pairing"]
        good = bad.replace(
            "self._f.flush()",
            "self._f.flush()\n                    "
            "os.fsync(self._f.fileno())")
        assert _lint_source(tmp_path, good, rules=["fsync-pairing"]) == []

    def test_fsync_without_flush(self, tmp_path):
        bad = """
            import os

            def sync_only(f):
                os.fsync(f.fileno())
        """
        finds = _lint_source(tmp_path, bad, rules=["fsync-pairing"])
        assert [f.rule for f in finds] == ["fsync-pairing"]

    def test_no_host_effects_in_jit(self, tmp_path):
        bad = """
            import time
            import jax

            @jax.jit
            def traced(x):
                return x + time.time()
        """
        finds = _lint_source(tmp_path, bad,
                             rules=["no-host-effects-in-jit"])
        assert [f.rule for f in finds] == ["no-host-effects-in-jit"]
        good = """
            import jax

            @jax.jit
            def traced(x, now):
                return x + now
        """
        assert _lint_source(tmp_path, good,
                            rules=["no-host-effects-in-jit"]) == []

    def test_donation_reuse(self, tmp_path):
        bad = """
            import jax

            def _step(x):
                return x * 2

            fast = jax.jit(_step, donate_argnums=(0,))

            def dispatch(buf):
                y = fast(buf)
                return buf + y
        """
        finds = _lint_source(tmp_path, bad, rules=["donation-reuse"])
        assert [f.rule for f in finds] == ["donation-reuse"]
        good = bad.replace("return buf + y", "return y")
        assert _lint_source(tmp_path, good, rules=["donation-reuse"]) == []

    def test_donation_reuse_allows_rebind(self, tmp_path):
        src = """
            import jax

            def _step(x):
                return x * 2

            fast = jax.jit(_step, donate_argnums=(0,))

            def dispatch(buf):
                buf = fast(buf)
                return buf
        """
        assert _lint_source(tmp_path, src, rules=["donation-reuse"]) == []

    def test_threshold_dtype_fires_in_kernel_scope(self, tmp_path):
        bad = """
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref):
                def bool_mm(x, y):
                    return (jnp.dot(x, y,
                                    preferred_element_type=jnp.float32)
                            > 0).astype(jnp.float32)
                o_ref[...] = bool_mm(x_ref[...], x_ref[...])
        """
        finds = _lint_source(tmp_path, bad, rules=["threshold-dtype"])
        assert [f.rule for f in finds] == ["threshold-dtype"]
        # the int8 form (the rework's replacement) is the fix
        good = bad.replace("jnp.float32)\n                            > 0",
                           "jnp.int32)\n                            > 0")
        assert _lint_source(tmp_path, good,
                            rules=["threshold-dtype"]) == []

    def test_threshold_dtype_waiver_and_jit_scope(self, tmp_path):
        waived = """
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def kernel(x_ref, o_ref):
                def bool_mm(x, y):
                    return (
                        jnp.dot(x, y,  # lint: ignore[threshold-dtype]
                                preferred_element_type=jnp.float32) > 0
                    ).astype(jnp.float32)
                o_ref[...] = bool_mm(x_ref[...], x_ref[...])
        """
        assert _lint_source(tmp_path, waived,
                            rules=["threshold-dtype"]) == []
        # jitted function in a non-pallas module is kernel scope too
        jit_bad = """
            import jax
            import jax.numpy as jnp

            @jax.jit
            def screen(a, b):
                return (jnp.dot(a, b,
                                preferred_element_type=jnp.float32) > 0)
        """
        finds = _lint_source(tmp_path, jit_bad, rules=["threshold-dtype"])
        assert [f.rule for f in finds] == ["threshold-dtype"]
        # an UN-jitted host function without pallas: not kernel scope
        host = jit_bad.replace("@jax.jit\n            ", "")
        assert _lint_source(tmp_path, host,
                            rules=["threshold-dtype"]) == []
        # a dot without the threshold (magnitude consumer): not flagged
        mag = jit_bad.replace(" > 0", "")
        assert _lint_source(tmp_path, mag,
                            rules=["threshold-dtype"]) == []

    def test_recompile_hazard_jit_in_loop(self, tmp_path):
        bad = """
            import jax

            def hot(xs):
                out = []
                for x in xs:
                    out.append(jax.jit(lambda v: v + 1)(x))
                return out
        """
        finds = _lint_source(tmp_path, bad, rules=["recompile-hazard"])
        assert [f.rule for f in finds] == ["recompile-hazard"]
        good = """
            import jax

            def hot(xs):
                f = jax.jit(lambda v: v + 1)
                return [f(x) for x in xs]
        """
        assert _lint_source(tmp_path, good,
                            rules=["recompile-hazard"]) == []

    def test_recompile_hazard_static_loop_var(self, tmp_path):
        bad = """
            import jax

            def _kernel(x, n):
                return x * n

            k = jax.jit(_kernel, static_argnums=(1,))

            def sweep(x):
                for n in range(100):
                    x = k(x, n)
                return x
        """
        finds = _lint_source(tmp_path, bad, rules=["recompile-hazard"])
        assert [f.rule for f in finds] == ["recompile-hazard"]

    def test_inline_waiver(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def bump(self):
                    with self._lock:
                        self.n += 1

                def racy(self):
                    self.n += 1  # lint: ignore[lock-guard]
        """
        assert _lint_source(tmp_path, src, rules=["lock-guard"]) == []

    def test_baseline_suppresses_and_reports_stale(self, tmp_path):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "fx.py").write_text(textwrap.dedent("""
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def bump(self):
                    with self._lock:
                        self.n += 1

                def racy(self):
                    self.n += 1
        """), encoding="utf-8")
        rep = lint_mod.lint_paths([str(d)], baseline=False)
        assert len(rep.findings) == 1
        bl = tmp_path / "baseline.txt"
        lint_mod.write_baseline(bl, rep.findings)
        rep2 = lint_mod.lint_paths([str(d)], baseline=str(bl))
        assert rep2.findings == [] and len(rep2.baselined) == 1
        bl.write_text(bl.read_text() + "pkg/gone.py::X.y::lock-guard\n",
                      encoding="utf-8")
        rep3 = lint_mod.lint_paths([str(d)], baseline=str(bl))
        assert rep3.stale_waivers == ["pkg/gone.py::X.y::lock-guard"]

    def test_findings_metrics_counter(self, tmp_path):
        from jepsen_tpu import telemetry
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "fx.py").write_text(textwrap.dedent("""
            def sched(q):  # owner: scheduler
                q.put_nowait(1)
                q.get()
        """), encoding="utf-8")
        reg = telemetry.Registry()
        with telemetry.use(reg):
            lint_mod.lint_paths([str(d)], baseline=False)
        assert reg.counter("lint_findings_total", labels=("rule",)).value(
            rule="no-unbounded-block") == 1


# ---------------------------------------------------------------------------
# The gate: jepsen_tpu/ itself lints clean (modulo the checked-in baseline)
# ---------------------------------------------------------------------------

class TestSelfLint:
    def test_package_lints_clean(self):
        import time as _t
        from pathlib import Path
        root = Path(__file__).resolve().parent.parent
        t0 = _t.monotonic()
        rep = lint_mod.lint_paths([str(root / "jepsen_tpu")],
                                  baseline=str(root / "lint-baseline.txt"),
                                  root=str(root))
        elapsed = _t.monotonic() - t0
        assert rep.findings == [], (
            "non-baselined lint findings in jepsen_tpu/ — fix them or "
            "add a documented waiver to lint-baseline.txt:\n"
            + "\n".join(f.render() for f in rep.findings))
        assert rep.stale_waivers == [], (
            "stale lint-baseline.txt entries: " + str(rep.stale_waivers))
        # tier-1 budget: the AST cache must keep this fast
        assert elapsed < 30.0, f"self-lint took {elapsed:.1f}s"

    def test_second_run_hits_ast_cache(self):
        import time as _t
        from pathlib import Path
        root = Path(__file__).resolve().parent.parent
        lint_mod.lint_paths([str(root / "jepsen_tpu")], baseline=False,
                            root=str(root))
        t0 = _t.monotonic()
        lint_mod.lint_paths([str(root / "jepsen_tpu")], baseline=False,
                            root=str(root))
        assert _t.monotonic() - t0 < 10.0

    def test_cli_lint_subcommand(self, capsys):
        from jepsen_tpu import cli
        import os
        cwd = os.getcwd()
        from pathlib import Path
        os.chdir(Path(__file__).resolve().parent.parent)
        try:
            rc = cli.noop_main(["lint", "jepsen_tpu"])
        finally:
            os.chdir(cwd)
        out = capsys.readouterr().out
        assert rc == 0 and "0 findings" in out

    def test_cli_preflight_subcommand(self, capsys):
        from jepsen_tpu import cli
        rc = cli.noop_main(["preflight", "--no-ssh"])
        assert rc == 0
        assert "preflight clean" in capsys.readouterr().out

    def test_cli_lint_json(self, capsys):
        import json
        import os
        from pathlib import Path
        from jepsen_tpu import cli
        cwd = os.getcwd()
        os.chdir(Path(__file__).resolve().parent.parent)
        try:
            rc = cli.noop_main(["lint", "jepsen_tpu", "--format=json"])
        finally:
            os.chdir(cwd)
        assert rc == 0
        lines = [json.loads(x) for x in
                 capsys.readouterr().out.strip().splitlines()]
        assert lines[-1]["summary"] is True
        assert lines[-1]["findings"] == 0
