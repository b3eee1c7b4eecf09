"""Test config: force an 8-device virtual CPU mesh before JAX is imported.

Mirrors the reference's tier-1/tier-2 test strategy (SURVEY.md §4): pure unit
tests plus fake-cluster integration, no real TPU required. Multi-chip sharding
is exercised on a virtual 8-device CPU mesh, the same mechanism the driver's
``dryrun_multichip`` uses.
"""
import os
import sys

# Must run before any backend init anywhere in the test session: unit
# tests run on the CPU, on an 8-device virtual mesh.
#
# Exception: JEPSEN_TPU_TESTS=1 opts a session INTO the real chip for the
# ``-m tpu`` parity tier (tests/test_tpu_parity.py) — the platform list is
# left alone so the TPU stays the default device.
#
# The ``-m mesh`` lane (multi-device sharding differentials,
# tests/test_mesh.py) overrides even that: its tests NEED the 8-device
# virtual CPU mesh, and a single chip can't provide one — so a
# mesh-lane session is always forced onto the virtual mesh.
TPU_SESSION = bool(os.environ.get("JEPSEN_TPU_TESTS"))


def _wants_mesh_lane() -> bool:
    """True when this session's -m expression selects the mesh marker
    (parsed from argv — this must run before pytest parses options,
    because the XLA flag only works before any jax import)."""
    def selects(expr: str) -> bool:
        return "mesh" in expr and "not mesh" not in expr

    argv = sys.argv
    for i, a in enumerate(argv):
        if a in ("-m", "--markexpr") and i + 1 < len(argv) \
                and selects(argv[i + 1]):
            return True
        if (a.startswith("-m") or a.startswith("--markexpr=")) \
                and selects(a):
            return True
    return False


MESH_LANE = _wants_mesh_lane()
if not TPU_SESSION or MESH_LANE:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --- tier-1 wall-clock guard -------------------------------------------
#
# The quick lane (-m 'not slow') must stay inside the driver's 870 s
# timeout; PR 2 split the slow tests out to get it there. This guard
# fails the SESSION when the quick lane exceeds its budget, so a slow
# test creeping into the quick lane is a red build, not a silent drift
# back toward the timeout. Tune/disable with JEPSEN_TPU_TIER1_BUDGET_S
# (0 disables).

import time as _time_mod  # noqa: E402

TIER1_BUDGET_S = float(os.environ.get("JEPSEN_TPU_TIER1_BUDGET_S", "870"))


def _is_quick_lane(config) -> bool:
    expr = config.getoption("markexpr", default="") or ""
    return "not slow" in expr


def pytest_configure(config):
    config._jepsen_session_t0 = _time_mod.monotonic()
    if TIER1_BUDGET_S > 0 and _is_quick_lane(config):
        # A WEDGED session never reaches sessionfinish — the driver's
        # outer `timeout` kills it with no diagnostics. Arm faulthandler
        # to dump every thread's stack at the budget mark, so CI logs
        # show where the wedge is instead of nothing (doc/robustness.md).
        import faulthandler
        try:
            faulthandler.dump_traceback_later(
                TIER1_BUDGET_S, file=sys.__stderr__)
            config._jepsen_dump_armed = True
        except Exception:  # noqa: BLE001 — diagnostics never break a run
            pass


_TEST_DURATIONS: dict = {}


def pytest_runtest_logreport(report):
    # accumulate per-test wall time (setup+call+teardown) so an
    # over-budget session can NAME the creep instead of only dumping
    # thread stacks — a slow-but-finished trip used to leave no trail
    _TEST_DURATIONS[report.nodeid] = (
        _TEST_DURATIONS.get(report.nodeid, 0.0)
        + getattr(report, "duration", 0.0))


def _dump_slowest(file, n: int = 10) -> None:
    worst = sorted(_TEST_DURATIONS.items(), key=lambda kv: -kv[1])[:n]
    if not worst:
        return
    print(f"\n==== slowest {len(worst)} tests this session ====",
          file=file)
    for nodeid, secs in worst:
        print(f"{secs:8.2f}s  {nodeid}", file=file)


def pytest_sessionfinish(session, exitstatus):
    if getattr(session.config, "_jepsen_dump_armed", False):
        import faulthandler
        faulthandler.cancel_dump_traceback_later()
    if TIER1_BUDGET_S <= 0 or not _is_quick_lane(session.config):
        return
    elapsed = _time_mod.monotonic() - session.config._jepsen_session_t0
    if elapsed > TIER1_BUDGET_S:
        import pytest
        # over budget but not wedged: name the slowest tests (the usual
        # culprits) and dump what is still running (a lingering thread
        # is the other cause of creep), then fail the session
        _dump_slowest(sys.__stderr__)
        from jepsen_tpu.telemetry import dump_thread_stacks
        dump_thread_stacks(sys.__stderr__)
        # pytest.exit from sessionfinish is the supported way to force
        # the exit status (wrap_session catches exit.Exception here)
        pytest.exit(
            f"quick lane took {elapsed:.0f}s, over its "
            f"{TIER1_BUDGET_S:.0f}s tier-1 budget — move the slow "
            "test(s) above to the slow lane (pytest.mark.slow); see "
            "doc/robustness.md", returncode=1)


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def _native_ingest_build_guard():
    """Tier-1 guard for the host ingest spine: when a compiler is
    present, the C extension must BUILD and pass its differential
    probe — a silent fallback to the Python twins would let native
    regressions (or a probe divergence) ship unnoticed behind green
    tests. No compiler (g++ genuinely absent) still degrades softly;
    every other failure is loud."""
    import shutil

    if shutil.which("g++") is None:
        yield
        return
    from jepsen_tpu.history_ir import ingest
    from jepsen_tpu.native import columnar_c
    try:
        so = columnar_c.build()
    except Exception as e:  # noqa: BLE001 — rethrown as the loud signal
        pytest.exit("native ingest guard: columnar_ext.c failed to "
                    f"compile with g++ present: {e!r}", returncode=1)
    m = columnar_c.mod()
    if m is None or not hasattr(m, "ingest_chunk"):
        pytest.exit(f"native ingest guard: built {so} but the module "
                    "did not load or lacks the spine entry points",
                    returncode=1)
    if ingest.native_mod() is None:
        pytest.exit("native ingest guard: extension built but the "
                    "differential probe condemned it (see "
                    "jepsen.history_ir log) — tier-1 must not run on "
                    "a silently-diverged native path", returncode=1)
    yield


@pytest.fixture(autouse=True, scope="session")
def _hermetic_fs_cache(tmp_path_factory):
    """fs_cache writes (the pallas probe-verdict sidecar above all —
    ops/pallas_matrix persists per-backend probe results there) land in
    a session temp dir, never the user's real ~/.jepsen-tpu/cache:
    tests must neither pollute nor depend on developer-machine state.
    Per-test JEPSEN_CACHE_DIR monkeypatches still override."""
    prev = os.environ.get("JEPSEN_CACHE_DIR")
    os.environ["JEPSEN_CACHE_DIR"] = str(tmp_path_factory.mktemp("fs-cache"))
    yield
    if prev is None:
        os.environ.pop("JEPSEN_CACHE_DIR", None)
    else:
        os.environ["JEPSEN_CACHE_DIR"] = prev


def run_fake(suite_test_fn, **opts):
    """Shared fake-mode lifecycle harness for suite tests: builds the
    suite's test map in --fake mode (in-memory doubles over the dummy
    remote) and runs the full core.run lifecycle into a throwaway store."""
    import tempfile

    with tempfile.TemporaryDirectory() as tmp:
        t = suite_test_fn({"fake": True, "time_limit": 1.0,
                           "store_dir": tmp, "no_perf": True,
                           "accelerator": "cpu", **opts})
        from jepsen_tpu import core
        return core.run(t)
