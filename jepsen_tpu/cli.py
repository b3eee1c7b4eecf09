"""CLI runner (reference: jepsen/src/jepsen/cli.clj).

Subcommands: ``test`` (run + exit by validity), ``analyze`` (re-check a
stored history with fresh checker code — analysis is re-entrant,
cli.clj:399-427), ``serve`` (web UI), ``test-all`` (sweeps). Exit codes
mirror cli.clj:129-139: 0 pass / 1 invalid / 2 unknown / 254 bad args /
255 crash. Node and "--concurrency 3n" parsing per cli.clj:150-202.
"""
from __future__ import annotations

import argparse
import logging
import sys
from typing import Callable

logger = logging.getLogger("jepsen.cli")

EXIT_OK = 0
EXIT_INVALID = 1
EXIT_UNKNOWN = 2
EXIT_BAD_ARGS = 254
EXIT_CRASH = 255


from jepsen_tpu.utils import parse_concurrency  # noqa: E402  (re-export)


def parse_nodes(opts) -> list[str]:
    """Merges --node, --nodes, --nodes-file (cli.clj:167-202)."""
    nodes: list[str] = []
    if getattr(opts, "nodes", None):
        nodes.extend(x for x in opts.nodes.split(",") if x)
    if getattr(opts, "node", None):
        nodes.extend(opts.node)
    if getattr(opts, "nodes_file", None):
        with open(opts.nodes_file) as f:
            nodes.extend(line.strip() for line in f if line.strip())
    return nodes or ["n1", "n2", "n3", "n4", "n5"]


def add_test_opts(p: argparse.ArgumentParser) -> None:
    """Shared test option spec (cli.clj:64-111)."""
    p.add_argument("--nodes", help="comma-separated node list")
    p.add_argument("--node", action="append", help="a node to test (repeatable)")
    p.add_argument("--nodes-file", help="file with one node per line")
    p.add_argument("--username", default="root")
    p.add_argument("--password")
    p.add_argument("--port", type=int)
    p.add_argument("--ssh-private-key", dest="ssh_private_key")
    p.add_argument("--no-ssh", action="store_true",
                   help="use the dummy remote (no cluster needed)")
    p.add_argument("--concurrency", default="1n",
                   help="number of workers; '3n' = 3 per node")
    p.add_argument("--time-limit", type=float, default=60.0)
    p.add_argument("--test-count", type=int, default=1)
    p.add_argument("--leave-db-running", action="store_true")
    p.add_argument("--accelerator", default="auto",
                   choices=["auto", "cpu", "tpu"],
                   help="checker backend (the TPU switch)")
    p.add_argument("--store-dir", default="store")
    # unified telemetry (doc/observability.md): spans, metrics, profiles
    p.add_argument("--trace", action="store_true",
                   help="causal trace: stream a Perfetto trace.json of "
                        "the whole run (workers, nemesis, checker "
                        "ladder, checkpoints) plus the per-client span "
                        "log trace.jsonl")
    p.add_argument("--flight-recorder-events", type=int, default=None,
                   dest="flight_recorder_events",
                   help="flight-recorder ring capacity (default 4096; "
                        "0 disables; the ring dumps to "
                        "flight-recorder.jsonl on stalls and crashes)")
    p.add_argument("--metrics-interval", type=float, default=None,
                   help="seconds between background metrics flushes into "
                        "the store dir (default 10; 0 = final export "
                        "only, negative = metrics off)")
    p.add_argument("--profile", action="store_true",
                   help="capture a jax.profiler device trace of the "
                        "checker phase into the run's profile/ dir")
    # per-op deadline (doc/robustness.md): a hung client invoke becomes
    # a bounded, indeterminate :info instead of wedging the run
    p.add_argument("--op-timeout", type=float, default=None,
                   dest="op_timeout",
                   help="seconds before an in-flight op is reaped to an "
                        "indeterminate :info and its worker replaced "
                        "(default 600; 0 disables; per-op timeout_s and "
                        "JEPSEN_TPU_OP_TIMEOUT_S also apply)")
    # preflight (doc/static-analysis.md): static test-map validation
    # before any node/db contact; the escape hatch restores the old
    # behavior bit-identically
    p.add_argument("--no-preflight", action="store_true",
                   dest="no_preflight",
                   help="skip preflight validation of the test map "
                        "(generator op surface, nemesis healability, "
                        "knob type/range checks)")


def test_opts_to_test(opts, base_test: dict) -> dict:
    nodes = parse_nodes(opts)
    test = dict(base_test)
    test["nodes"] = nodes
    test["concurrency"] = parse_concurrency(opts.concurrency, len(nodes))
    test["time_limit"] = opts.time_limit
    test["leave_db_running"] = bool(opts.leave_db_running)
    test["store_dir"] = opts.store_dir
    test["accelerator"] = opts.accelerator
    # telemetry opts ride along in the test map so every suite gets
    # spans/metrics/profiles with no suite-side code (core.run wires them)
    test["trace"] = bool(getattr(opts, "trace", False) or test.get("trace"))
    interval = getattr(opts, "metrics_interval", None)
    if interval is None:  # flag omitted: the base test's setting wins
        interval = test.get("metrics_interval", 10.0)
    test["metrics_interval"] = max(interval, 0.0)
    if interval < 0:
        test["metrics"] = False
    test["profile"] = bool(getattr(opts, "profile", False)
                           or test.get("profile"))
    if getattr(opts, "flight_recorder_events", None) is not None:
        # 0 disables the always-on flight recorder for this run
        test["flight_recorder_events"] = opts.flight_recorder_events
    if getattr(opts, "op_timeout", None) is not None:
        # 0 disables (the interpreter treats falsy as no deadline)
        test["op_timeout_s"] = opts.op_timeout
    if getattr(opts, "no_preflight", False):
        test["preflight"] = False
    ssh = dict(test.get("ssh") or {})
    ssh.update({
        "username": opts.username,
        "password": opts.password,
        "port": opts.port,
        "private_key_path": opts.ssh_private_key,
        "dummy": bool(opts.no_ssh) or ssh.get("dummy", False),
    })
    test["ssh"] = ssh
    return test


def validity_exit_code(test: dict) -> int:
    valid = (test.get("results") or {}).get("valid?")
    if valid is True:
        return EXIT_OK
    if valid == "unknown":
        return EXIT_UNKNOWN
    return EXIT_INVALID


def single_test_cmd(
    test_fn: Callable[[argparse.Namespace], dict],
    opt_fn: Callable[[argparse.ArgumentParser], None] | None = None,
    name: str = "jepsen-tpu",
) -> Callable[[list[str] | None], int]:
    """Builds a main() with test/analyze/serve subcommands around a
    test-map constructor (cli.clj:352-427 single-test-cmd)."""

    def main(argv: list[str] | None = None) -> int:
        parser = argparse.ArgumentParser(prog=name)
        sub = parser.add_subparsers(dest="command", required=True)

        p_test = sub.add_parser("test", help="run a test")
        add_test_opts(p_test)
        if opt_fn:
            opt_fn(p_test)

        p_an = sub.add_parser("analyze", help="re-check a stored history")
        p_an.add_argument("--test-name")
        p_an.add_argument("--timestamp", help="defaults to latest run")
        p_an.add_argument("--recover", action="store_true",
                          help="recover a crashed run's partial history "
                               "from its write-ahead journal "
                               "(history.wal.jsonl), check it, and mark "
                               "the results incomplete")
        p_an.add_argument("--no-live-reuse", action="store_true",
                          dest="no_live_reuse",
                          help="re-check from scratch even when the live "
                               "checker daemon left a fresh final "
                               "incremental verdict (live-status.json) "
                               "for this run")
        p_an.add_argument("--no-resume-check", action="store_true",
                          dest="no_resume_check",
                          help="re-check from zero even when an "
                               "interrupted check left a valid durable "
                               "checkpoint (check.ckpt) for this run "
                               "(doc/robustness.md)")
        add_test_opts(p_an)  # analyze takes the same opts (cli.clj:399-427)
        if opt_fn:
            opt_fn(p_an)

        p_heal = sub.add_parser(
            "heal", help="replay a crashed run's unhealed faults "
                         "(faults.jsonl) to restore net/clock state")
        p_heal.add_argument("dir", nargs="?",
                            help="store dir, or one run's directory "
                                 "(store/<name>/<timestamp>); defaults "
                                 "to --store-dir's latest run")
        p_heal.add_argument("--test-name")
        p_heal.add_argument("--timestamp", help="defaults to latest run")
        p_heal.add_argument("--store-dir", default="store")

        p_ex = sub.add_parser(
            "explain", help="re-derive anomaly forensics for a stored "
                            "run: localize the first anomaly, shrink a "
                            "minimal witness, write anomaly.json + "
                            "witness-timeline.html "
                            "(doc/observability.md)")
        p_ex.add_argument("dir", nargs="?",
                          help="one run's directory "
                               "(store/<name>/<timestamp>) or a store "
                               "dir; defaults to --store-dir's latest "
                               "run")
        p_ex.add_argument("--test-name")
        p_ex.add_argument("--timestamp", help="defaults to latest run")
        p_ex.add_argument("--store-dir", default="store")
        p_ex.add_argument("--shrink-budget", type=int, default=None,
                          dest="explain_shrink_budget",
                          help="max witness-shrink candidate checks "
                               "(default 128)")
        p_ex.add_argument("--max-witness-ops", type=int, default=None,
                          dest="explain_max_witness_ops",
                          help="stop shrinking once the witness is this "
                               "small (default 16)")

        p_tr = sub.add_parser(
            "trace", help="re-derive a stored run's causal trace from "
                          "its artifacts (WAL/history + faults.jsonl + "
                          "late.jsonl + telemetry events) into a "
                          "Perfetto-loadable trace.json "
                          "(doc/observability.md)")
        p_tr.add_argument("dir", nargs="?",
                          help="one run's directory "
                               "(store/<name>/<timestamp>) or a store "
                               "dir; defaults to --store-dir's latest "
                               "run")
        p_tr.add_argument("--test-name")
        p_tr.add_argument("--timestamp", help="defaults to latest run")
        p_tr.add_argument("--store-dir", default="store")
        p_tr.add_argument("--out", help="target path (default: the "
                                        "run's trace.json, or "
                                        "trace-derived.json when a "
                                        "live trace already exists)")

        p_serve = sub.add_parser("serve", help="serve the web UI")
        p_serve.add_argument("--host", default="0.0.0.0")
        p_serve.add_argument("-p", "--port", type=int, default=8080)
        p_serve.add_argument("--store-dir", default="store")

        p_live = sub.add_parser(
            "live", help="online checker daemon: tail active runs' "
                         "write-ahead journals and serve streaming "
                         "verdicts (doc/observability.md)")
        p_live.add_argument("dirs", nargs="*",
                            help="store root and/or individual run "
                                 "directories (store/<name>/<ts>); "
                                 "defaults to --store-dir")
        p_live.add_argument("--store-dir", default="store")
        p_live.add_argument("--poll", dest="live_poll_s", default=None,
                            help="seconds between WAL polls (default 1)")
        p_live.add_argument("--lag-budget-ops", dest="live_lag_budget_ops",
                            default=None,
                            help="lag budget in ops; beyond it a run's "
                                 "status flags over_lag_budget")
        p_live.add_argument("--max-runs", dest="live_max_runs",
                            default=None,
                            help="admission cap on concurrently tracked "
                                 "runs (default 16)")
        p_live.add_argument("--check-budget", dest="live_check_budget_s",
                            default=None,
                            help="per-poll verdict budget in predicted "
                                 "CPU seconds (cost-model admission)")
        p_live.add_argument("--accelerator", default="auto",
                            choices=["auto", "cpu", "tpu"])
        p_live.add_argument("--once", action="store_true",
                            help="poll until every tracked run "
                                 "finalizes, then exit")
        p_live.add_argument("--timeout", type=float, default=0.0,
                            help="with --once: give up after this many "
                                 "seconds (0 = wait forever)")

        p_ship = sub.add_parser(
            "ship", help="ship a run's WAL to a fleet ingest receiver "
                         "over HTTP, resume-token checked "
                         "(doc/observability.md \"Fleet plane\")")
        p_ship.add_argument("dir", help="one run's directory "
                                        "(store/<name>/<timestamp>)")
        p_ship.add_argument("--to", default=None, action="append",
                            help="receiver base URL; repeat (or comma-"
                                 "separate) for failover targets "
                                 "(default: fleet_receivers knob / "
                                 "JEPSEN_TPU_FLEET_RECEIVERS, else "
                                 "http://127.0.0.1:<fleet_port>)")
        p_ship.add_argument("--poll", dest="ship_poll_s", type=float,
                            default=0.2,
                            help="seconds between WAL polls when idle")
        p_ship.add_argument("--timeout", type=float, default=300.0,
                            help="give up after this many seconds")

        p_fleet = sub.add_parser(
            "fleet", help="fleet daemon: HTTP WAL ingest + pooled live "
                          "checking + /fleet dashboard aggregate "
                          "(doc/observability.md \"Fleet plane\")")
        p_fleet.add_argument("--store-dir", default="store",
                             help="ingest store root (shipped runs land "
                                  "here)")
        p_fleet.add_argument("--host", default="127.0.0.1")
        p_fleet.add_argument("-p", "--port", dest="fleet_port",
                             default=None,
                             help="ingest/status port (default 8091; "
                                  "env twin JEPSEN_TPU_FLEET_PORT)")
        p_fleet.add_argument("--ingest-budget",
                             dest="fleet_ingest_budget_s", default=None,
                             help="per-poll verdict budget in predicted "
                                  "CPU seconds (env twin "
                                  "JEPSEN_TPU_FLEET_INGEST_BUDGET_S)")
        p_fleet.add_argument("--max-runs", dest="fleet_max_runs",
                             default=None,
                             help="admission cap on concurrently "
                                  "tracked runs (env twin "
                                  "JEPSEN_TPU_FLEET_MAX_RUNS)")
        p_fleet.add_argument("--lease-ttl", dest="fleet_lease_ttl_s",
                             default=None,
                             help="run-lease TTL in seconds for leased "
                                  "checking; 0 disables leasing (env "
                                  "twin JEPSEN_TPU_FLEET_LEASE_TTL_S)")
        p_fleet.add_argument("--disk-headroom",
                             dest="fleet_disk_headroom_mb", default=None,
                             help="free-disk floor in MB below which "
                                  "the receiver sheds chunks with 429 "
                                  "(env twin "
                                  "JEPSEN_TPU_FLEET_DISK_HEADROOM_MB)")
        p_fleet.add_argument("--poll", dest="fleet_poll_s", type=float,
                             default=None,
                             help="seconds between pool polls")
        p_fleet.add_argument("--once", action="store_true",
                             help="poll until every tracked run "
                                  "finalizes, then exit")
        p_fleet.add_argument("--timeout", type=float, default=0.0,
                             help="with --once: give up after this "
                                  "many seconds (0 = wait forever)")

        p_chaos = sub.add_parser(
            "fleet-chaos", help="self-chaos harness: producers + "
                                "receiver + a two-host leased pool "
                                "under SIGKILL/SIGSTOP/torn-TCP/ENOSPC "
                                "injection; asserts the HA invariants "
                                "(doc/robustness.md \"Fleet HA\")")
        p_chaos.add_argument("--store-dir", default="store",
                             help="harness workspace; the report lands "
                                  "at <store>/fleet-chaos.json")
        p_chaos.add_argument("--runs", type=int, default=4,
                             help="producer runs to ship under chaos")
        p_chaos.add_argument("--ops", type=int, default=160,
                             help="history ops per run")
        p_chaos.add_argument("--seed", type=int, default=0,
                             help="seeds the chaos schedule and every "
                                  "producer history")
        p_chaos.add_argument("--lease-ttl", dest="fleet_lease_ttl_s",
                             type=float, default=1.0,
                             help="pool hosts' lease TTL (short: more "
                                  "adoption churn)")
        p_chaos.add_argument("--timeout", type=float, default=180.0,
                             help="overall harness deadline in seconds")

        p_hunt = sub.add_parser(
            "hunt", help="coverage-guided nemesis schedule fuzzer: "
                         "thousands of short fake-mode trials verdicted "
                         "through the live fleet path; anomalies ddmin-"
                         "minimize into hunt/<id>/ artifacts "
                         "(doc/robustness.md \"Schedule fuzzing\")")
        p_hunt.add_argument("--store-dir", default="store",
                            help="hunt workspace; artifacts land under "
                                 "<store>/hunt/<id>/")
        p_hunt.add_argument("--trials", dest="fuzz_trials", default=None,
                            help="trial budget (default 400; env twin "
                                 "JEPSEN_TPU_FUZZ_TRIALS)")
        p_hunt.add_argument("--pool-workers", dest="fuzz_pool_workers",
                            default=None,
                            help="trial pool processes; 0/1 = inline "
                                 "(env twin JEPSEN_TPU_FUZZ_POOL_WORKERS)")
        p_hunt.add_argument("--trial-ops", dest="fuzz_trial_ops",
                            default=None,
                            help="client ops per trial (default 120; env "
                                 "twin JEPSEN_TPU_FUZZ_TRIAL_OPS)")
        p_hunt.add_argument("--seed", dest="fuzz_seed", default=None,
                            help="hunt seed: fully determines the search "
                                 "(env twin JEPSEN_TPU_FUZZ_SEED)")
        p_hunt.add_argument("--blind", action="store_true",
                            help="disable coverage guidance (the "
                                 "random baseline guided search is "
                                 "measured against)")
        p_hunt.add_argument("--no-stop-on-first", action="store_true",
                            help="spend the whole trial budget even "
                                 "after an anomaly lands")
        p_hunt.add_argument("--demo-bug", action="store_true",
                            help="plant the canned interleaving-gated "
                                 "anomaly into every trial's target")
        p_hunt.add_argument("--accelerator", default="cpu",
                            choices=["auto", "cpu", "tpu"])
        p_hunt.add_argument("--replay", metavar="ID", default=None,
                            help="re-run a landed hunt/<ID> artifact and "
                                 "verify the bit-identical reproduction")
        p_hunt.add_argument("--list", action="store_true",
                            help="list landed anomalies and exit")

        p_pre = sub.add_parser(
            "preflight", help="validate the test map without running it "
                              "(doc/static-analysis.md)")
        add_test_opts(p_pre)
        if opt_fn:
            opt_fn(p_pre)
        p_pre.add_argument("--format", choices=["text", "json"],
                           default="text")

        p_lint = sub.add_parser(
            "lint", help="run the concurrency/JAX/native-C invariant "
                         "linter; collects .py and .c/.cpp files "
                         "(doc/static-analysis.md)")
        p_lint.add_argument("paths", nargs="*", default=["jepsen_tpu"])
        p_lint.add_argument("--format", choices=["text", "json"],
                            default="text")
        p_lint.add_argument("--baseline",
                            help="waiver file (default: lint-baseline.txt "
                                 "next to the linted package)")
        p_lint.add_argument("--no-baseline", action="store_true",
                            help="report baselined findings too")
        p_lint.add_argument("--update-baseline", action="store_true",
                            help="rewrite the baseline from the current "
                                 "findings")
        p_lint.add_argument("--rule", action="append", dest="rules",
                            help="restrict to a rule (repeatable; globs "
                                 "allowed: --rule 'jtn-*' runs just the "
                                 "native C rules)")

        p_fuzz = sub.add_parser(
            "fuzz-native",
            help="differential WAL-parser fuzz harness: seeded, "
                 "grammar-aware byte mutants through the native "
                 "ingest_chunk (chunked + whole-buffer) vs the Python "
                 "tolerant parser, byte-exact agreement asserted on "
                 "every exec; runs under the ASan+UBSan build when "
                 "available (doc/static-analysis.md \"Native code\")")
        p_fuzz.add_argument("--execs", type=int, default=100_000,
                            help="mutant executions (default 100000)")
        p_fuzz.add_argument("--seed", type=int, default=0,
                            help="master seed: fully determines the "
                                 "mutant stream")
        p_fuzz.add_argument("--no-san", action="store_true",
                            help="run against the plain -O3 build even "
                                 "when the sanitizer lane is available")
        p_fuzz.add_argument("--store-dir", default="store",
                            help="divergence artifacts land at "
                                 "<store>/fuzz-native/")

        try:
            opts = parser.parse_args(argv)
        except SystemExit as e:
            return EXIT_BAD_ARGS if e.code not in (0, None) else 0
        from jepsen_tpu import compile_cache
        compile_cache.enable()

        try:
            if opts.command == "test":
                from jepsen_tpu import core
                from jepsen_tpu.analysis.preflight import PreflightFailed
                code = EXIT_OK
                for i in range(opts.test_count):
                    try:
                        test = test_fn(opts)
                    except (ValueError, KeyError) as e:
                        print(f"bad arguments: {e}", file=sys.stderr)
                        return EXIT_BAD_ARGS
                    try:
                        result = core.run(test)
                    except PreflightFailed as e:
                        for d in e.diagnostics:
                            print(d.render(), file=sys.stderr)
                        print("preflight rejected the test before any "
                              "node was touched (--no-preflight skips)",
                              file=sys.stderr)
                        return EXIT_BAD_ARGS
                    code = validity_exit_code(result)
                    if code != EXIT_OK:
                        break
                return code
            if opts.command == "analyze":
                return analyze_cmd(opts, test_fn)
            if opts.command == "heal":
                return heal_cmd(opts)
            if opts.command == "explain":
                return explain_cmd(opts)
            if opts.command == "trace":
                return trace_cmd(opts)
            if opts.command == "preflight":
                return preflight_cmd(opts, test_fn)
            if opts.command == "lint":
                return lint_cmd(opts)
            if opts.command == "fuzz-native":
                return fuzz_native_cmd(opts)
            if opts.command == "serve":
                from jepsen_tpu.web import serve
                serve(opts.store_dir, opts.host, opts.port)
                return EXIT_OK
            if opts.command == "live":
                return live_cmd(opts)
            if opts.command == "ship":
                return ship_cmd(opts)
            if opts.command == "fleet":
                return fleet_cmd(opts)
            if opts.command == "fleet-chaos":
                return fleet_chaos_cmd(opts)
            if opts.command == "hunt":
                return hunt_cmd(opts)
            return EXIT_BAD_ARGS
        except KeyboardInterrupt:
            return EXIT_CRASH
        except Exception:  # noqa: BLE001
            logger.exception("test crashed")
            return EXIT_CRASH

    return main


def _resolve_run(opts) -> tuple[str, str] | None:
    """(test-name, timestamp) from --test-name/--timestamp, defaulting
    to the latest stored run. None when nothing matches."""
    from jepsen_tpu import store
    if getattr(opts, "test_name", None):
        name = opts.test_name
        if getattr(opts, "timestamp", None):
            return name, opts.timestamp
        runs = store.tests(name, opts.store_dir).get(name) or {}
        if not runs:
            print(f"no stored runs for test {name!r}", file=sys.stderr)
            return None
        return name, sorted(runs)[-1]
    found = store.latest(opts.store_dir)
    if found is None:
        print("no stored tests found", file=sys.stderr)
        return None
    return found[0], found[1]


def live_cmd(opts) -> int:
    """``jepsen-tpu live``: runs the online checker daemon over a store
    root and/or explicit run directories (doc/observability.md, "Live
    checking")."""
    from pathlib import Path

    from jepsen_tpu.live import daemon as live_daemon

    store_root = opts.store_dir
    run_dirs: list = []
    for d in getattr(opts, "dirs", None) or ():
        p = Path(d)
        # a run dir holds (or held) a WAL / history; anything else is a
        # store root (last one wins, mirroring heal_cmd's dir handling)
        if (p / live_daemon.WAL_NAME).exists() or \
                (p / "history.jsonl").exists() or \
                (p / "test.json").exists():
            run_dirs.append(p)
        else:
            store_root = str(p)
    kw = {
        "poll_s": opts.live_poll_s,
        "lag_budget_ops": opts.live_lag_budget_ops,
        "max_runs": opts.live_max_runs,
        "check_budget_s": opts.live_check_budget_s,
        "accelerator": opts.accelerator,
    }
    if getattr(opts, "once", False):
        daemon = live_daemon.LiveDaemon(store_root=store_root,
                                        run_dirs=run_dirs, **kw)
        timeout = opts.timeout if opts.timeout and opts.timeout > 0 \
            else 3600.0
        statuses = daemon.run_until_idle(timeout_s=timeout)
        daemon.stop()
        for label, s in sorted(statuses.items()):
            print(f"{label}: {s['state']} valid_so_far="
                  f"{s['valid_so_far']} first_anomaly_op="
                  f"{s['first_anomaly_op']} lag_ops={s['lag_ops']}")
        worst = EXIT_OK
        for s in statuses.values():
            if s.get("valid_so_far") is False:
                worst = max(worst, EXIT_INVALID)
            elif s.get("valid_so_far") not in (True, False):
                worst = max(worst, EXIT_UNKNOWN)
        return worst
    live_daemon.serve(store_root, run_dirs=run_dirs, **kw)
    return EXIT_OK


def ship_cmd(opts) -> int:
    """``jepsen-tpu ship``: streams one run dir's WAL to a fleet
    ingest receiver, resume-token checked, finalizing with the
    authoritative history once the run completes
    (doc/observability.md "Fleet plane")."""
    from pathlib import Path

    from jepsen_tpu.fleet import (DEFAULT_FLEET_PORT, fleet_knob,
                                  fleet_receivers)
    from jepsen_tpu.fleet.ship import Shipper

    run_dir = Path(opts.dir)
    # --to repeats (or comma-separates) into a failover list; with none
    # given, the fleet_receivers knob/env twin decides, and the local
    # fleet_port receiver is the last resort (doc/robustness.md
    # "Fleet HA")
    bases: list[str] = []
    for item in opts.to or ():
        bases.extend(fleet_receivers(item))
    if not bases:
        bases = fleet_receivers()
    if not bases:
        port = int(fleet_knob("fleet_port", None,
                              DEFAULT_FLEET_PORT, 0.0))
        bases = [f"http://127.0.0.1:{port}"]
    sh = Shipper(run_dir, bases, poll_s=opts.ship_poll_s)
    ok = sh.run(timeout_s=opts.timeout)
    print(f"{sh.key}: shipped {sh.bytes_sent} byte(s) in "
          f"{sh.chunks_sent} chunk(s), {sh.resets} reset(s), "
          f"{sh.failovers} failover(s), finalized={sh.finalized}")
    return EXIT_OK if ok else EXIT_CRASH


def fleet_cmd(opts) -> int:
    """``jepsen-tpu fleet``: the pool side — HTTP WAL ingest, one live
    daemon over the ingest store, mesh heal probes, and the aggregated
    fleet-status plane (doc/observability.md "Fleet plane")."""
    from jepsen_tpu.fleet import scheduler as fleet_scheduler
    from jepsen_tpu.live.daemon import DEFAULT_POLL_S

    kw = {
        "host": opts.host,
        "port": opts.fleet_port,
        "ingest_budget_s": opts.fleet_ingest_budget_s,
        "max_runs": opts.fleet_max_runs,
        "lease_ttl_s": opts.fleet_lease_ttl_s,
        "disk_headroom_mb": opts.fleet_disk_headroom_mb,
        "poll_s": (opts.fleet_poll_s if opts.fleet_poll_s is not None
                   else DEFAULT_POLL_S),
    }
    if getattr(opts, "once", False):
        fd = fleet_scheduler.FleetDaemon(opts.store_dir, **kw)
        timeout = opts.timeout if opts.timeout and opts.timeout > 0 \
            else 3600.0
        payload = fd.run_until_idle(timeout_s=timeout)
        runs = payload.get("runs", {})
        print(f"fleet: {runs.get('final', 0)} run(s) settled, "
              f"{runs.get('invalid', 0)} invalid, worst lag "
              f"{payload.get('worst_lag_ops', 0)} ops")
        return EXIT_INVALID if runs.get("invalid", 0) else EXIT_OK
    fleet_scheduler.serve(opts.store_dir, **kw)
    return EXIT_OK


def fleet_chaos_cmd(opts) -> int:
    """``jepsen-tpu fleet-chaos``: the fleet-HA self-chaos harness
    (doc/robustness.md "Fleet HA"). Exits EXIT_OK only when every
    invariant held — zero double-checked runs, zero lost/duplicated
    WAL bytes, fleet verdicts bit-identical to local analyze."""
    import json as _json

    from jepsen_tpu.fleet.chaos import run_fleet_chaos

    report = run_fleet_chaos(opts.store_dir, runs=opts.runs,
                             n_ops=opts.ops, seed=opts.seed,
                             lease_ttl_s=opts.fleet_lease_ttl_s,
                             timeout_s=opts.timeout)
    print(_json.dumps(report, indent=2))
    return EXIT_OK if report["ok"] else EXIT_INVALID


def hunt_cmd(opts) -> int:
    """``jepsen-tpu hunt``: the coverage-guided schedule fuzzer
    (doc/robustness.md "Schedule fuzzing"). Exit codes mirror ``test``:
    a landed anomaly is EXIT_INVALID; ``--replay`` exits EXIT_OK only
    on a bit-identical reproduction."""
    import json as _json

    from jepsen_tpu.fuzz import hunt as hunt_mod

    if getattr(opts, "list", False):
        rows = hunt_mod.list_hunts(opts.store_dir)
        for r in rows:
            print(f"{r['id']}: seed={r['seed']} n_ops={r['n_ops']} "
                  f"windows={r['windows']}")
        if not rows:
            print("no landed anomalies")
        return EXIT_OK
    if opts.replay:
        try:
            out = hunt_mod.replay(opts.store_dir, opts.replay)
        except (OSError, ValueError) as e:
            print(f"replay failed to load hunt/{opts.replay}: {e}",
                  file=sys.stderr)
            return EXIT_BAD_ARGS
        print(_json.dumps(out, indent=2))
        return (EXIT_OK if out["identical"] and out["reproduced"]
                else EXIT_INVALID)
    hunter = hunt_mod.Hunter(
        opts.store_dir,
        trials=opts.fuzz_trials,
        pool_workers=opts.fuzz_pool_workers,
        trial_ops=opts.fuzz_trial_ops,
        seed=opts.fuzz_seed,
        guided=not getattr(opts, "blind", False),
        bug_spec=(hunt_mod.DEMO_BUG_SPEC
                  if getattr(opts, "demo_bug", False) else None),
        accelerator=opts.accelerator,
        stop_on_first=not getattr(opts, "no_stop_on_first", False))
    summary = hunter.run()
    print(_json.dumps(summary, indent=2))
    for hid in summary.get("hunt_ids", ()):
        print(f"reproduce with: jepsen-tpu hunt --store-dir "
              f"{opts.store_dir} --replay {hid}"
              + (" (--demo-bug artifact)" if hunter.bug_spec else ""))
    return EXIT_INVALID if summary["anomalies"] else EXIT_OK


def analyze_cmd(opts, test_fn) -> int:
    """Re-runs checkers over a stored history (cli.clj:399-427). With
    ``--recover``, a crashed run (no history.jsonl) is rebuilt from its
    write-ahead journal: the partial history is persisted via save_1,
    checked normally, and its results carry ``incomplete: true``
    (doc/robustness.md)."""
    from jepsen_tpu import core, store
    run = _resolve_run(opts)
    if run is None:
        return EXIT_BAD_ARGS
    name, ts = run
    stored = store.load_test(name, ts, opts.store_dir)
    stored["store_dir"] = opts.store_dir
    if getattr(opts, "recover", False):
        from jepsen_tpu import journal as journal_mod
        wal = store.path(stored, journal_mod.WAL_NAME)
        existing = stored.get("history") or []
        if wal.exists():
            ops, truncated = journal_mod.read_wal(wal)
            # a crash DURING save_1 can leave a torn history.jsonl next
            # to the complete journal: the journal wins whenever it
            # holds more ops than what the (tolerant) history load saw
            if len(ops) > len(existing):
                print(f"recovered {len(ops)} op(s) from {wal}"
                      + (" (torn final line dropped)" if truncated
                         else "")
                      + (f"; replacing {len(existing)}-op torn history"
                         if existing else ""))
                stored["history"] = ops
                stored["wal_recovered"] = True
                if truncated:
                    stored["wal_truncated_tail"] = True
                # persist the recovered history so the run is
                # re-analyzable through the normal path from here on
                store.save_1(stored)
            else:
                print(f"history.jsonl already holds {len(existing)} "
                      f"op(s), journal {len(ops)}; nothing to recover")
        elif not existing:
            print(f"no history and no journal at {wal}", file=sys.stderr)
            return EXIT_BAD_ARGS
    # fresh checker from the suite's constructor
    fresh = test_fn(opts)
    stored["checker"] = fresh.get("checker")
    # a live-daemon-tracked run leaves its final incremental verdict in
    # live-status.json; analyze reuses it when fresh (same op count)
    # unless --no-live-reuse re-checks from scratch
    stored["live_reuse"] = not getattr(opts, "no_live_reuse", False)
    # an interrupted check leaves a durable check.ckpt; the checker
    # auto-resumes a valid one unless --no-resume-check opts out
    if getattr(opts, "no_resume_check", False):
        stored["resume_check"] = False
    test = core.analyze(stored)
    core.log_results(test)
    print(f"valid?: {(test.get('results') or {}).get('valid?')}")
    return validity_exit_code(test)


def preflight_cmd(opts, test_fn) -> int:
    """``jepsen-tpu preflight``: builds the test map exactly as ``test``
    would and runs the static checks, printing structured diagnostics.
    Exit 0 when clean (warnings included), EXIT_BAD_ARGS on errors."""
    from jepsen_tpu import core
    from jepsen_tpu.analysis import diagnostics as diag_mod
    from jepsen_tpu.analysis import preflight as preflight_mod
    try:
        test = test_fn(opts)
    except (ValueError, KeyError) as e:
        print(f"bad arguments: {e}", file=sys.stderr)
        return EXIT_BAD_ARGS
    test = core.prepare_test(test)
    diags = preflight_mod.preflight(test)
    if getattr(opts, "format", "text") == "json":
        sys.stdout.write(diag_mod.render_json(diags))
    else:
        for d in diags:
            print(d.render())
    errors = [d for d in diags if d.severity == diag_mod.ERROR]
    if errors:
        print(f"preflight: {len(errors)} error(s), "
              f"{len(diags) - len(errors)} other diagnostic(s)",
              file=sys.stderr)
        return EXIT_BAD_ARGS
    if getattr(opts, "format", "text") == "text":
        print(f"preflight clean ({len(diags)} non-fatal diagnostic(s))"
              if diags else "preflight clean")
    return EXIT_OK


def lint_cmd(opts) -> int:
    """``jepsen-tpu lint [paths...]``: the invariant linter. Exit 0 when
    no non-baselined finding remains."""
    from jepsen_tpu.analysis import lint as lint_mod
    baseline: object = getattr(opts, "baseline", None)
    if getattr(opts, "no_baseline", False):
        baseline = False
    try:
        report = lint_mod.lint_paths(opts.paths, baseline=baseline,
                                     rules=getattr(opts, "rules", None))
    except ValueError as e:
        print(f"lint: {e}", file=sys.stderr)
        return EXIT_BAD_ARGS
    if getattr(opts, "update_baseline", False):
        if getattr(opts, "rules", None):
            # a rule-restricted run only sees that rule's findings — a
            # rewrite from it would silently drop every OTHER rule's
            # waivers (and their why-comments) from the baseline
            print("lint: --update-baseline cannot be combined with "
                  "--rule (it would discard the other rules' waivers); "
                  "run it over the full rule set", file=sys.stderr)
            return EXIT_BAD_ARGS
        from pathlib import Path
        bpath = (Path(opts.baseline) if getattr(opts, "baseline", None)
                 else lint_mod._guess_root(opts.paths)
                 / lint_mod.BASELINE_NAME)
        lint_mod.write_baseline(bpath, report.findings + report.baselined)
        print(f"baseline written: {bpath} "
              f"({len(report.findings) + len(report.baselined)} entries)")
        return EXIT_OK
    if getattr(opts, "format", "text") == "json":
        sys.stdout.write(lint_mod.render_report_json(report))
    else:
        print(lint_mod.render_text(report))
    return EXIT_OK if report.exit_code == 0 else 1


def fuzz_native_cmd(opts) -> int:
    """``jepsen-tpu fuzz-native``: the differential WAL-parser fuzz
    harness (doc/static-analysis.md "Native code"). By default the run
    happens under the ASan+UBSan build: when this process doesn't have
    libasan preloaded (it can't be dlopen'd late — GCC's runtime aborts
    the process), the command re-execs itself once in a child with
    ``columnar_c.san_env()``. Exit: 0 clean, 1 divergence found, 2 when
    no native build is loadable (nothing to differentiate)."""
    import shutil
    import subprocess as sp

    from jepsen_tpu.native import columnar_c

    want_san = not getattr(opts, "no_san", False)
    if want_san and not columnar_c._asan_mapped():
        env = columnar_c.san_env()
        built = False
        if env is not None and shutil.which("g++"):
            try:
                columnar_c.build(san=True)
                built = True
            except Exception:  # noqa: BLE001 — fall through to plain
                logger.warning("sanitizer build failed", exc_info=True)
        if built:
            print("fuzz-native: re-exec under the ASan+UBSan build "
                  "(LD_PRELOAD libasan)")
            sys.stdout.flush()
            cmd = [sys.executable, "-m", "jepsen_tpu.cli", "fuzz-native",
                   "--execs", str(opts.execs), "--seed", str(opts.seed),
                   "--store-dir", opts.store_dir]
            return sp.run(cmd, env=env).returncode
        print("fuzz-native: sanitizer lane unavailable (no g++/libasan "
              "or san build failed); running against the plain -O3 "
              "build", file=sys.stderr)
        from jepsen_tpu.history_ir import ingest
        ingest.fallback_count("san-unavailable")
        want_san = False

    from jepsen_tpu.fuzz import native as fuzz_native
    res = fuzz_native.run_fuzz(opts.execs, seed=opts.seed, san=want_san,
                               store_dir=opts.store_dir, progress=print)
    if res["status"] == "no-native":
        print("fuzz-native: no native build loadable in this process; "
              "nothing to differentiate", file=sys.stderr)
        return EXIT_UNKNOWN
    variant = "san" if res["san"] else "plain"
    print(f"fuzz-native: {res['execs']} execs "
          f"({res['execs_per_s']:,.0f}/s, variant={variant}, "
          f"seed={opts.seed}) — {res['ops_parsed']} ops parsed, "
          f"{res['torn_lines']} torn lines, "
          f"{res['divergences']} divergence(s)")
    cov = ", ".join(f"{k}:{v}" for k, v in
                    sorted(res["operator_coverage"].items()))
    print(f"  operator coverage: {cov}")
    if res["divergences"]:
        for a in res["artifacts"]:
            print(f"  divergence artifact: {a}", file=sys.stderr)
        return EXIT_INVALID
    return EXIT_OK


def explain_cmd(opts) -> int:
    """``jepsen-tpu explain``: offline anomaly forensics for a stored
    run — localization + minimal witness + artifacts, re-derived from
    history.jsonl alone (doc/observability.md "Anomaly forensics").
    Exit codes follow ``validity_exit_code``'s convention: EXIT_OK when
    the run is valid (nothing to explain), EXIT_INVALID when forensics
    were derived and written, EXIT_UNKNOWN for a run explain cannot
    judge (no usable history, or a workload with no forensics),
    EXIT_BAD_ARGS when no run could be resolved at all."""
    from pathlib import Path

    from jepsen_tpu.checker import explain as explain_mod

    run_dir = None
    if getattr(opts, "dir", None):
        d = Path(opts.dir)
        if (d / "history.jsonl").exists() or (d / "test.json").exists():
            run_dir = d  # a single run's directory
        else:
            opts.store_dir = str(d)  # a store dir: fall through to latest
    if run_dir is None:
        run = _resolve_run(opts)
        if run is None:
            return EXIT_BAD_ARGS
        name, ts = run
        run_dir = Path(opts.store_dir) / name / ts
    summary = explain_mod.explain_run(
        run_dir,
        shrink_budget=getattr(opts, "explain_shrink_budget", None),
        max_witness_ops=getattr(opts, "explain_max_witness_ops", None))
    if summary is None:
        print(f"no usable history at {run_dir}", file=sys.stderr)
        return EXIT_UNKNOWN
    if summary.get("valid") is True:
        print(f"{run_dir}: history is valid — nothing to explain")
        return EXIT_OK
    if "unsupported" in summary:
        print(f"{run_dir}: no forensics for workload "
              f"{summary['unsupported']!r} (register and list-append "
              "histories are supported)", file=sys.stderr)
        return EXIT_UNKNOWN
    if "first_anomaly_op" in summary:
        print(f"{run_dir}: first anomaly at op "
              f"{summary['first_anomaly_op']} — witness of "
              f"{summary['witness_ops']} op(s) via {summary['backend']}; "
              f"wrote {', '.join(summary.get('artifacts') or [])}")
    else:
        print(f"{run_dir}: valid?={summary.get('valid')} anomalies="
              f"{summary.get('anomaly_types')}; wrote "
              f"{', '.join(summary.get('artifacts') or [])}")
    return EXIT_INVALID if summary.get("valid") is False else EXIT_UNKNOWN


def trace_cmd(opts) -> int:
    """``jepsen-tpu trace``: offline causal-trace derivation for a
    stored run — old runs become traceable retroactively
    (doc/observability.md "Causal trace"). Prints the summary (span
    counts per track, slowest ops, demotion chain) and the written
    path. Exit 0 on success, EXIT_UNKNOWN when the run has no usable
    op artifact, EXIT_BAD_ARGS when no run resolves."""
    from pathlib import Path

    from jepsen_tpu.journal import WAL_NAME
    from jepsen_tpu.trace.derive import derive_run_trace, summarize_trace

    run_dir = None
    if getattr(opts, "dir", None):
        d = Path(opts.dir)
        if (d / "history.jsonl").exists() or (d / WAL_NAME).exists() \
                or (d / "test.json").exists():
            run_dir = d  # a single run's directory
        else:
            opts.store_dir = str(d)  # a store dir: fall through to latest
    if run_dir is None:
        run = _resolve_run(opts)
        if run is None:
            return EXIT_BAD_ARGS
        name, ts = run
        run_dir = Path(opts.store_dir) / name / ts
    out = derive_run_trace(run_dir, out=getattr(opts, "out", None))
    if out is None:
        print(f"no usable history or journal at {run_dir}",
              file=sys.stderr)
        return EXIT_UNKNOWN
    summary = summarize_trace(out)
    if summary:
        tracks = ", ".join(f"{t}: {n}"
                           for t, n in summary["tracks"].items())
        print(f"{out}: {summary['events']} event(s) across "
              f"{len(summary['tracks'])} track(s) [{tracks}]")
        for o in summary["slowest_ops"]:
            print(f"  slow: {o['name']} ({o['track']}) {o['dur_ms']} ms")
        if summary["demotions"]:
            print("  demotion chain: " + " -> ".join(summary["demotions"]))
    else:
        print(f"{out}: written (no events?)")
    print("load it at https://ui.perfetto.dev (or chrome://tracing)")
    return EXIT_OK


def heal_cmd(opts) -> int:
    """Replays a crashed run's unhealed faults (``cli heal``): reads the
    run's ``faults.jsonl``, applies the idempotent heal for each
    unhealed kind (net partitions flushed, netem cleared, clocks
    reset), and marks entries healed. Process kill/pause faults need
    the live db object and are reported unhealable offline
    (doc/robustness.md)."""
    import json as _json
    from pathlib import Path

    from jepsen_tpu import store
    from jepsen_tpu.nemesis import faults as faults_mod

    run_dir = None
    if getattr(opts, "dir", None):
        d = Path(opts.dir)
        if (d / faults_mod.FAULTS_NAME).exists() or (d / "test.json").exists():
            run_dir = d  # a single run's directory
        else:
            opts.store_dir = str(d)  # a store dir: fall through to latest
    if run_dir is None:
        run = _resolve_run(opts)
        if run is None:
            return EXIT_BAD_ARGS
        name, ts = run
        run_dir = Path(opts.store_dir) / name / ts
    reg_path = run_dir / faults_mod.FAULTS_NAME
    if not reg_path.exists():
        print(f"no fault registry at {reg_path}; nothing to heal")
        return EXIT_OK
    test: dict = {}
    try:
        with open(run_dir / "test.json") as f:
            test = _json.load(f)
    except (OSError, ValueError):
        logger.warning("no readable test.json in %s", run_dir)
    test.setdefault("nodes", [])
    test["store_dir"] = str(run_dir.parent.parent)
    registry = faults_mod.FaultRegistry(reg_path)
    try:
        unhealed = registry.unhealed()
        if not unhealed:
            print("no unhealed faults; cluster is clean")
            return EXIT_OK
        if not test["nodes"]:
            # healing over zero nodes would trivially "succeed" and
            # durably mark the faults healed without touching the
            # cluster — destroying the only record that healing is
            # still needed. Refuse instead.
            print(f"{len(unhealed)} unhealed fault(s) but no node list "
                  f"(missing/corrupt test.json in {run_dir}); refusing "
                  "to heal blind — pass a run dir with an intact "
                  "test.json or heal the cluster manually",
                  file=sys.stderr)
            return EXIT_UNKNOWN
        print(f"replaying {len(unhealed)} unhealed fault(s): "
              + ", ".join(sorted({str(r.get('kind')) for r in unhealed})))
        summary = faults_mod.replay_unhealed(test, registry)
        print(f"healed: {summary['healed']}  "
              f"unhealable: {summary['unhealable']}  "
              f"failed: {summary['failed']}")
        return (EXIT_OK if not summary["unhealable"] and not summary["failed"]
                else EXIT_UNKNOWN)
    finally:
        registry.close()
        from jepsen_tpu import control
        try:
            control.disconnect_all(test)
        except Exception:  # noqa: BLE001
            pass


def test_all_cmd(tests_fn: Callable[[argparse.Namespace], list], name="jepsen-tpu"):
    """Sweep runner (cli.clj:429-515): runs every workload, summarizes.
    Honors the module exit-code contract like single_test_cmd: bad
    arguments → EXIT_BAD_ARGS, a crash mid-sweep → EXIT_CRASH."""

    def main(argv: list[str] | None = None) -> int:
        parser = argparse.ArgumentParser(prog=f"{name} test-all")
        add_test_opts(parser)
        try:
            opts = parser.parse_args(argv)
        except SystemExit:
            return EXIT_BAD_ARGS
        from jepsen_tpu import compile_cache
        compile_cache.enable()
        try:
            from jepsen_tpu import core
            from jepsen_tpu.analysis.preflight import PreflightFailed
            worst = EXIT_OK
            # each round rebuilds the test maps — core.run mutates them
            # (cli.clj:429-515 runs every combination test-count times)
            for _ in range(getattr(opts, "test_count", 1) or 1):
                for test in tests_fn(opts):
                    try:
                        result = core.run(test)
                    except PreflightFailed as e:
                        for d in e.errors:
                            print(d.render(), file=sys.stderr)
                        logger.error("%s rejected by preflight",
                                     test.get("name"))
                        worst = max(worst, EXIT_BAD_ARGS)
                        continue
                    code = validity_exit_code(result)
                    worst = max(worst, code if code != EXIT_OK else worst)
                    logger.info("%s: %s", test.get("name"),
                                (result.get("results") or {}).get("valid?"))
            return worst
        except KeyboardInterrupt:
            return EXIT_CRASH
        except Exception:  # noqa: BLE001
            logger.exception("sweep crashed")
            return EXIT_CRASH

    return main


def noop_main(argv: list[str] | None = None) -> int:
    """`python -m jepsen_tpu.cli` — runs the noop test (smoke check)."""
    from jepsen_tpu.fakes import noop_test

    def build(opts):
        return test_opts_to_test(opts, noop_test())

    return single_test_cmd(build)(argv)


if __name__ == "__main__":
    sys.exit(noop_main())
