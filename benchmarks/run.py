"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and exits nonzero when any
module crashed (its ``<module>.ERROR`` row is still written). Modules:
  bench_fingerprint — paper §IV-C quality table
  bench_tuning      — paper §IV-D Fig. 5 (CherryPick/Arrow +- Perona)
                      + HPO engine (sequential vs vmapped) wall-clock
  bench_workflows   — paper §IV-E Table III (Lotaru) + Tarema groups
  bench_fleet       — fleet service throughput (loop vs micro-batched
                      vs sharded requests/s) + amortized-append check
  bench_optimizer   — §IV-D scenario-matrix replay: sequential numpy
                      searches vs the batched vmapped lane engine
  bench_kernels     — kernel-path microbenchmarks
  bench_roofline    — dry-run roofline summary (deliverable g)

The tuning module's rows are written to ``BENCH_tuning.json``, the
fleet module's to ``BENCH_fleet.json`` and the optimizer module's to
``BENCH_optimizer.json`` so the perf trajectories are tracked across
PRs.

Every tracked payload is stamped with provenance — ``git_sha``,
``dirty``, and hostname-free hardware descriptors (``device_count``,
``cpu_cores``, ``backend``) — so history rows are comparable across
machines. ``--history PATH`` ingests the payloads into the append-only
``benchmarks.history.BenchHistory`` store; ``--gate`` additionally
runs the noise-aware regression gate (``benchmarks.gate``) over the
updated history, writes the markdown trend report, and exits nonzero
on confirmed regressions — the record->detect->enforce loop in one
command.

Usage: PYTHONPATH=src python -m benchmarks.run [--only <module-substr>]
``--quick`` shrinks workload counts; ``--smoke`` (the CI step) shrinks
them further so every module imports and runs in a few minutes (smoke
payloads ingest *tagged* and never anchor gate baselines).
"""

import argparse
import json
import os
import subprocess
import sys
import time
import traceback


def provenance() -> dict:
    """The comparability stamp every tracked payload carries: which
    code produced the numbers (git SHA + dirty working tree flag) and
    what hardware class ran them (device/core counts, jax backend —
    deliberately hostname-free)."""

    def _git(*argv):
        try:
            out = subprocess.run(
                ["git", *argv], capture_output=True, text=True,
                timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)))
            return out.stdout.strip() if out.returncode == 0 else ""
        except OSError:
            return ""

    import jax  # after any --devices XLA_FLAGS mutation

    return {
        "git_sha": _git("rev-parse", "--short=12", "HEAD")
        or "unknown",
        "dirty": bool(_git("status", "--porcelain")),
        "device_count": jax.device_count(),
        "cpu_cores": os.cpu_count() or 0,
        "backend": jax.default_backend(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--quick", action="store_true",
                    help="reduced workload counts")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal counts: the CI import-and-run check")
    ap.add_argument("--json-out", default="BENCH_tuning.json",
                    help="where to write the tuning rows as JSON")
    ap.add_argument("--fleet-json-out", default="BENCH_fleet.json",
                    help="where to write the fleet rows as JSON")
    ap.add_argument("--optimizer-json-out",
                    default="BENCH_optimizer.json",
                    help="where to write the optimizer rows as JSON")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual host devices (sets "
                         "--xla_force_host_platform_device_count "
                         "before jax initializes; exercises the "
                         "sharded/pipelined multi-device rows on CPU)")
    ap.add_argument("--history", default=None,
                    help="ingest the written payloads into this "
                         "BenchHistory .npz (appended, atomic)")
    ap.add_argument("--gate", action="store_true",
                    help="after ingesting (default history: "
                         "BENCH_history.npz), run the regression gate "
                         "+ trend report and exit nonzero on "
                         "confirmed regressions")
    ap.add_argument("--report", default="TREND_REPORT.md",
                    help="trend report path for --gate")
    args = ap.parse_args()
    quick = args.quick or args.smoke
    if args.devices > 0:
        # must land in XLA_FLAGS before the first jax import
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()

    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_fingerprint, bench_fleet,
                            bench_kernels, bench_optimizer,
                            bench_roofline, bench_tuning,
                            bench_workflows)
    from repro import obs

    n_workloads = (3 if args.smoke else 6) if quick else 18
    hpo_trials = (4 if args.smoke else 8) if quick else 32
    hpo_epochs = (4 if args.smoke else 8) if quick else 25
    fp_runs = 25 if args.smoke else 100
    fp_epochs = 15 if args.smoke else 100
    wf_runs = 4 if args.smoke else 10
    wf_epochs = 10 if args.smoke else 40
    modules = [
        ("fingerprint", lambda rows: bench_fingerprint.run(
            rows, runs_per_type=fp_runs, epochs=fp_epochs)),
        ("tuning", lambda rows: bench_tuning.run(
            rows, n_workloads=n_workloads, hpo_trials=hpo_trials,
            hpo_epochs=hpo_epochs)),
        ("workflows", lambda rows: bench_workflows.run(
            rows, runs_per_type=wf_runs, epochs=wf_epochs)),
        ("fleet", lambda rows: bench_fleet.run(rows, quick=quick)),
        ("optimizer", lambda rows: bench_optimizer.run(rows,
                                                       quick=quick)),
        ("kernels", lambda rows: bench_kernels.run(rows)),
        ("roofline", lambda rows: bench_roofline.run(rows)),
    ]
    json_out = {"tuning": args.json_out, "fleet": args.fleet_json_out,
                "optimizer": args.optimizer_json_out}

    rows = [("name", "us_per_call", "derived")]
    written = []
    prov = None
    for name, fn in modules:
        if args.only and args.only not in name:
            continue
        start = len(rows)
        t0 = time.time()
        params = None
        try:
            params = fn(rows)
            rows.append((f"{name}.wall_s", "", f"{time.time() - t0:.1f}"))
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            rows.append((f"{name}.ERROR", "", repr(e)))
        if name in json_out and json_out[name]:
            # record the module's actual workload parameters so quick
            # smoke numbers are never mistaken for the tracked
            # full-run trajectory (modules may return their own dict)
            if params is None and name == "tuning":
                params = {"hpo_trials": hpo_trials,
                          "hpo_epochs": hpo_epochs,
                          "n_workloads": n_workloads}
            if prov is None:
                prov = provenance()
            payload = {
                "module": name,
                "unix_time": time.time(),
                "quick": quick,
                "smoke": args.smoke,
                # provenance: which code / what hardware class —
                # history rows must be comparable across machines
                **prov,
                "params": params,
                # telemetry snapshot at write time (jit traces /
                # dispatches / compile seconds, daemon ladder + queue
                # latency, ...): each tracked perf trajectory carries
                # its own diagnostics
                "metrics": obs.registry().snapshot(),
                "rows": [{"name": n, "us_per_call": u, "derived": d}
                         for n, u, d in rows[start:]],
            }
            with open(json_out[name], "w") as f:
                json.dump(payload, f, indent=2)
                f.write("\n")
            written.append(json_out[name])
    for r in rows:
        print(",".join(str(x) for x in r))
    if args.smoke:
        # CI contract: every tracked BENCH_*.json written by the smoke
        # run must carry a non-empty telemetry snapshot and the
        # provenance stamp the history store keys comparability on
        for path in written:
            with open(path) as f:
                payload = json.load(f)
            assert payload.get("metrics"), (
                f"{path}: bench payload is missing its telemetry "
                "'metrics' snapshot")
            for key in ("git_sha", "dirty", "device_count",
                        "cpu_cores", "backend"):
                assert key in payload, (
                    f"{path}: bench payload is missing provenance "
                    f"field {key!r}")

    if (args.gate or args.history) and written:
        hist_path = args.history or "BENCH_history.npz"
        from benchmarks.history import BenchHistory

        hist = BenchHistory.load_or_new(hist_path)
        for path in written:
            with open(path) as f:
                hist.append(json.load(f))
        hist.save(hist_path)
        print(f"history: ingested {len(written)} payload(s) -> "
              f"{hist_path} ({len(hist)} runs, "
              f"{hist.n_samples} samples)")
        if args.gate:
            from benchmarks import gate, report

            findings = gate.evaluate_history(hist)
            if args.report:
                report.write_trend_report(args.report, hist, findings)
                print(f"gate: trend report -> {args.report}")
            failures = gate.gate_verdict(hist, findings)
            if failures:
                print(f"gate: FAIL — {len(failures)} confirmed "
                      "regression(s):", file=sys.stderr)
                for line in failures:
                    print(f"  {line}", file=sys.stderr)
                sys.exit(1)
            print("gate: PASS — no confirmed regressions")
    errors = [n for n, _, _ in rows[1:] if n.endswith(".ERROR")]
    if errors:
        print(f"FAIL — {len(errors)} module(s) crashed: "
              f"{', '.join(errors)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    # support `python benchmarks/run.py` (script dir on sys.path, repo
    # root not): make the `benchmarks` package importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    main()
