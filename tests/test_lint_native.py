"""Native-code correctness plane: JTN lint rules + fuzz determinism.

One broken/fixed C fixture pair per JTN diagnostic (the
``_lint_source`` pattern from test_analysis.py, over ``.c`` files),
the C-side waiver grammar, the parse cache, glob rule selection, and
the fuzz harness's seeded-determinism contract
(doc/static-analysis.md "Native code").
"""
from __future__ import annotations

import textwrap

import pytest

from jepsen_tpu.analysis import lint as lint_mod
from jepsen_tpu.analysis.lint import csrc

pytestmark = pytest.mark.lint


def _lint_c(tmp_path, source, rules=None, name="fx.c"):
    d = tmp_path / "cfix"
    d.mkdir(exist_ok=True)
    (d / name).write_text(textwrap.dedent(source), encoding="utf-8")
    rep = lint_mod.lint_paths([str(d)], baseline=False, rules=rules)
    return rep.findings


class TestJTNRules:
    def test_alloc_check_deref_fires_and_checked_silent(self, tmp_path):
        bad = """
            static int use(void) {
                char *p;
                p = malloc(16);
                p[0] = 'x';
                return 0;
            }
        """
        finds = _lint_c(tmp_path, bad, rules=["jtn-alloc-check"])
        assert [f.code for f in finds] == ["JTN001"]
        assert finds[0].qualname == "use"
        good = bad.replace("p[0] = 'x';",
                           "if (!p) return -1;\n    p[0] = 'x';")
        assert _lint_c(tmp_path, good, rules=["jtn-alloc-check"]) == []

    def test_alloc_check_pyarg_discarded(self, tmp_path):
        bad = """
            static PyObject *meth(PyObject *self, PyObject *args) {
                long v;
                PyArg_ParseTuple(args, "l", &v);
                return PyLong_FromLong(v);
            }
        """
        finds = _lint_c(tmp_path, bad, rules=["jtn-alloc-check"])
        assert [f.code for f in finds] == ["JTN001"]
        good = bad.replace(
            'PyArg_ParseTuple(args, "l", &v);',
            'if (!PyArg_ParseTuple(args, "l", &v)) return NULL;')
        assert _lint_c(tmp_path, good, rules=["jtn-alloc-check"]) == []

    def test_cleanup_return_bypass_fires_and_goto_silent(self, tmp_path):
        bad = """
            static PyObject *mk(PyObject *o) {
                PyObject *d = PyDict_New();
                if (!d) goto fail;
                if (PyDict_SetItem(d, o, o) < 0) return NULL;
                return d;
            fail:
                Py_XDECREF(d);
                return NULL;
            }
        """
        finds = _lint_c(tmp_path, bad, rules=["jtn-cleanup-return"])
        assert [f.code for f in finds] == ["JTN002"]
        good = bad.replace("< 0) return NULL;", "< 0) goto fail;")
        assert _lint_c(tmp_path, good, rules=["jtn-cleanup-return"]) == []

    def test_errcheck_fires_and_pyerr_occurred_silent(self, tmp_path):
        bad = """
            static long gx(PyObject *o) {
                long v = PyLong_AsLong(o);
                return v + 1;
            }
        """
        finds = _lint_c(tmp_path, bad, rules=["jtn-errcheck"])
        assert [f.code for f in finds] == ["JTN003"]
        good = bad.replace(
            "return v + 1;",
            "if (v == -1 && PyErr_Occurred()) return -1;\n"
            "    return v + 1;")
        assert _lint_c(tmp_path, good, rules=["jtn-errcheck"]) == []

    def test_gil_call_fires_and_blocked_silent(self, tmp_path):
        bad = """
            static void work(PyObject *o, char *buf, int n) {
                Py_BEGIN_ALLOW_THREADS
                scan(buf, n);
                PyList_Append(o, o);
                Py_END_ALLOW_THREADS
            }
        """
        finds = _lint_c(tmp_path, bad, rules=["jtn-gil-call"])
        assert [f.code for f in finds] == ["JTN004"]
        # re-acquiring with Py_BLOCK_THREADS makes the call legal
        good = bad.replace(
            "PyList_Append(o, o);",
            "Py_BLOCK_THREADS\n    PyList_Append(o, o);\n"
            "    Py_UNBLOCK_THREADS")
        assert _lint_c(tmp_path, good, rules=["jtn-gil-call"]) == []

    def test_bounds_guard_fires_and_masked_or_compared_silent(
            self, tmp_path):
        bad = """
            static void fill(char *buf, int n) {
                int i = n + 2;
                buf[i] = 'x';
            }
        """
        finds = _lint_c(tmp_path, bad, rules=["jtn-bounds-guard"])
        assert [f.code for f in finds] == ["JTN005"]
        compared = bad.replace("buf[i] = 'x';",
                               "if (i < n) buf[i] = 'x';")
        assert _lint_c(tmp_path, compared,
                       rules=["jtn-bounds-guard"]) == []
        # the open-addressing probe idiom: a mask assignment IS the bound
        masked = bad.replace("int i = n + 2;", "int i = n & (16 - 1);")
        assert _lint_c(tmp_path, masked, rules=["jtn-bounds-guard"]) == []


class TestCWaivers:
    BAD = """
        static void fill(char *buf, int n) {
            int i = n + 2;
            buf[i] = 'x';
        }
    """

    def test_trailing_waiver(self, tmp_path):
        src = self.BAD.replace(
            "buf[i] = 'x';",
            "buf[i] = 'x'; /* lint: ignore[jtn-bounds-guard] */")
        assert _lint_c(tmp_path, src, rules=["jtn-bounds-guard"]) == []

    def test_line_above_waiver(self, tmp_path):
        src = self.BAD.replace(
            "buf[i] = 'x';",
            "/* i is caller-bounded: lint: ignore[jtn-bounds-guard] */\n"
            "    buf[i] = 'x';")
        assert _lint_c(tmp_path, src, rules=["jtn-bounds-guard"]) == []

    def test_function_level_boxed_waiver(self, tmp_path):
        # a multi-line boxed why-comment directly above the signature
        # waives the whole function (the csrc comment-map carries the
        # marker to the comment's END line)
        src = ("/* every index here is bounded by the caller's\n"
               " * contract — lint: ignore[jtn-bounds-guard] */\n"
               + textwrap.dedent(self.BAD).lstrip("\n"))
        d = tmp_path / "cfix"
        d.mkdir(exist_ok=True)
        (d / "fx.c").write_text(src, encoding="utf-8")
        rep = lint_mod.lint_paths([str(d)], baseline=False,
                                  rules=["jtn-bounds-guard"])
        assert rep.findings == []

    def test_skip_file(self, tmp_path):
        src = "/* lint: skip-file */\n" + textwrap.dedent(self.BAD)
        assert _lint_c(tmp_path, src, rules=["jtn-bounds-guard"]) == []

    def test_unwaived_still_fires(self, tmp_path):
        assert len(_lint_c(tmp_path, self.BAD,
                           rules=["jtn-bounds-guard"])) == 1


class TestDriverIntegration:
    def test_glob_rule_selection(self, tmp_path):
        # 'jtn-*' expands to exactly the C rule family
        assert lint_mod.resolve_rules(["jtn-*"]) == {
            name for name, _fn in lint_mod.C_RULES}
        with pytest.raises(ValueError):
            lint_mod.resolve_rules(["jtn-nope*"])

    def test_c_files_collected_by_default(self, tmp_path):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "a.py").write_text("x = 1\n", encoding="utf-8")
        (d / "b.c").write_text(
            "static void f(char *b, int n) { int i = n; b[i] = 1; }\n",
            encoding="utf-8")
        rep = lint_mod.lint_paths([str(d)], baseline=False)
        assert rep.files == 2
        assert any(f.code == "JTN005" for f in rep.findings)

    def test_parse_cache_stamp(self, tmp_path):
        p = tmp_path / "c.c"
        p.write_text("static int f(void) { return 0; }\n",
                     encoding="utf-8")
        m1 = csrc.parse_c_module(p)
        m2 = csrc.parse_c_module(p)
        assert m1 is m2  # unchanged stamp -> cache hit
        p.write_text("static int g(void) { return 1; }\n",
                     encoding="utf-8")
        m3 = csrc.parse_c_module(p)
        assert m3 is not m1 and "g" in m3.functions

    def test_real_native_sources_lint_clean(self):
        # the acceptance gate: zero non-baselined JTN findings over the
        # shipped C sources (safe idioms carry inline waivers, not
        # baseline entries)
        from pathlib import Path
        import jepsen_tpu
        native = Path(jepsen_tpu.__file__).parent / "native"
        srcs = sorted(str(p) for p in native.glob("*.c*"))
        assert srcs, "native sources moved?"
        rep = lint_mod.lint_paths(srcs, baseline=False, rules=["jtn-*"])
        assert rep.findings == [], \
            "\n".join(f.render() for f in rep.findings)


class TestFuzzDeterminism:
    def test_mutant_stream_is_seed_deterministic(self):
        from jepsen_tpu.fuzz import native as fn
        a = [(i, bytes(d), s, tuple(o))
             for i, d, s, o in fn.mutant_stream(1234, 300)]
        b = [(i, bytes(d), s, tuple(o))
             for i, d, s, o in fn.mutant_stream(1234, 300)]
        assert a == b  # same seed => byte-identical mutant stream
        c = [d for _i, d, _s, _o in fn.mutant_stream(1235, 300)]
        assert [d for _i, d, _s, _o in a] != c

    def test_exec_rng_is_per_exec_independent(self):
        # exec i's mutant does not depend on how many execs ran before
        # it — artifacts replay by (seed, exec) alone
        from jepsen_tpu.fuzz import native as fn
        solo = fn.mutant(fn.exec_rng(7, 250))
        stream = list(fn.mutant_stream(7, 251))[-1]
        assert stream[1] == solo[0] and stream[2] == solo[1]

    def test_corpus_seeds_cover_the_nasty_shapes(self):
        from jepsen_tpu.fuzz import native as fn
        names = {n for n, _ in fn.SEEDS}
        assert {"happy", "torn-final", "torn-interior", "unicode",
                "numbers", "fleet-chunk"} <= names
        # every seed must itself survive the Python tolerant parser
        from jepsen_tpu.journal import parse_wal_chunk_py
        for _name, data in fn.SEEDS:
            ops, consumed, torn, truncated = parse_wal_chunk_py(
                data, final=True)
            assert consumed == len(data)

    def test_plain_build_run_agrees_and_covers_the_corpus(self, tmp_path):
        # a bounded run against the plain -O3 build: zero C-vs-Python
        # divergences, and every corpus seed and mutation operator fired
        from jepsen_tpu.fuzz import native as fn
        res = fn.run_fuzz(4000, seed=1, san=False, store_dir=str(tmp_path))
        if res["status"] == "no-native":
            pytest.skip("native ingest extension unavailable")
        assert res["divergences"] == 0, res["artifacts"]
        assert set(res["seed_coverage"]) == {n for n, _ in fn.SEEDS}
        assert set(res["operator_coverage"]) == {n for n, _ in fn.OPERATORS}
