"""CLI entry point: ``python -m spark_rapids_tpu.tools <cmd> ...``.

Commands:

- ``profile <event-log>``: per-query timeline + bottleneck decomposition
  + operator ranking from a JSONL event log (rotated/.gz sets handled).
- ``autotune <event-log>``: rule-based conf recommendations with cited
  evidence; ``--json`` prints the ready-to-apply conf dict.
- ``trace <event-log>``: render the log as Chrome-trace/Perfetto JSON
  (load in chrome://tracing or ui.perfetto.dev); ``--check`` fails on
  transitions unattributed to any query.
- ``lint [path]``: static engine-invariant analysis (docs/lint.md);
  exits non-zero on any unsuppressed finding.
- ``audit <event-log>``: compiled-program audit over the stageProgram
  ledger (docs/audit.md) — forbidden primitives, baked constants,
  recompile storms, dtype widening, roofline cross-check; exits
  non-zero on any unsuppressed error finding.
- ``history ingest|report|regress|calibrate``: the persistent SQLite
  warehouse (docs/history.md) — ingest event logs and benchmark payloads,
  judge the latest run against the accumulated baseline (nonzero exit
  on regression), and fit the machine profile ``plan/cost.py`` uses to
  annotate plans with predicted cost.
"""

from __future__ import annotations

import argparse
import json
import sys


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m spark_rapids_tpu.tools",
        description="Offline diagnostics over spark_rapids_tpu event logs")
    sub = p.add_subparsers(dest="cmd", required=True)

    prof = sub.add_parser("profile",
                          help="timeline + bottleneck attribution report")
    prof.add_argument("log", help="JSONL event log path "
                                  "(rotated .N siblings read automatically)")
    prof.add_argument("--query", type=int, default=None,
                      help="only this query id")
    prof.add_argument("--samples", action="store_true",
                      help="list individual resource samples")
    prof.add_argument("--no-timeline", action="store_true",
                      help="skip the per-partition gantt")
    prof.add_argument("--json", action="store_true",
                      help="machine-readable output")

    at = sub.add_parser("autotune",
                        help="rule-based conf recommendations")
    at.add_argument("log")
    at.add_argument("--json", action="store_true",
                    help="print only the ready-to-apply conf dict")

    tr = sub.add_parser("trace",
                        help="Chrome-trace/Perfetto JSON timeline export")
    tr.add_argument("log", help="JSONL event log path (rotated .N "
                                "siblings read automatically)")
    tr.add_argument("--query", type=int, default=None,
                    help="only this query id")
    tr.add_argument("-o", "--out", default=None,
                    help="write the trace JSON here (default: stdout)")
    tr.add_argument("--check", action="store_true",
                    help="exit non-zero if any hostTransition/deviceSync "
                         "event is unattributed to a query")

    aud = sub.add_parser("audit",
                         help="compiled-program audit over the "
                              "stageProgram ledger")
    aud.add_argument("log", help="JSONL event log path (rotated .N "
                                 "siblings read automatically)")
    aud.add_argument("--json", action="store_true",
                     help="machine-readable output")
    aud.add_argument("--no-roofline", action="store_true",
                     help="skip the per-program roofline table")
    aud.add_argument("--storm-threshold", type=int, default=None,
                     help="distinct cache keys over one program "
                          "structure that count as a recompile storm")
    aud.add_argument("--min-peak-fraction", type=float, default=0.0,
                     help="flag programs achieving less than this "
                          "fraction of peak (0 = report-only)")
    aud.add_argument("--peak-flops", type=float, default=None,
                     help="accelerator peak FLOP/s for the roofline")
    aud.add_argument("--peak-bw", type=float, default=None,
                     help="accelerator peak bytes/s for the roofline")
    aud.add_argument("--baseline", default=None,
                     help="baseline JSON path (default: "
                          "<log dir>/.audit-baseline.json when present)")
    aud.add_argument("--write-baseline", action="store_true",
                     help="grandfather every active finding into the "
                          "baseline file and exit 0")

    hist = sub.add_parser("history",
                          help="persistent cross-run metrics warehouse")
    hsub = hist.add_subparsers(dest="action", required=True)
    h_ing = hsub.add_parser("ingest",
                            help="ingest event logs / benchmark payloads "
                                 "(files or directories, sniffed)")
    h_ing.add_argument("paths", nargs="+")
    h_ing.add_argument("--db", default=None,
                       help="warehouse path (default: the session "
                            "conf spark.rapids.history.path)")
    h_ing.add_argument("--label", default="",
                       help="free-form tag recorded on each run")
    h_ing.add_argument("--force", action="store_true",
                       help="always insert a new run, even when the "
                            "same path + content digest was already "
                            "ingested (default: update that run in "
                            "place)")
    h_rep = hsub.add_parser("report", help="warehouse inventory")
    h_rep.add_argument("--db", default=None)
    h_rep.add_argument("--json", action="store_true")
    h_reg = hsub.add_parser("regress",
                            help="latest run vs history baseline; "
                                 "exits non-zero on regression")
    h_reg.add_argument("--db", default=None)
    h_reg.add_argument("--min-runs", type=int, default=None,
                       help="baseline runs required for a verdict "
                            "(conf: spark.rapids.history.regress."
                            "minRuns)")
    h_reg.add_argument("--band-k", type=float, default=None,
                       help="MAD band multiplier (conf: spark.rapids."
                            "history.regress.madBands)")
    h_reg.add_argument("--threshold", type=float, default=None,
                       help="relative wrong-way floor (default 0.05)")
    h_reg.add_argument("--json", action="store_true")
    h_cal = hsub.add_parser("calibrate",
                            help="fit the machine profile from "
                                 "accumulated history")
    h_cal.add_argument("--db", default=None)
    h_cal.add_argument("-o", "--out", default=None,
                       help="write the profile JSON here "
                            "(default: stdout)")
    h_cal.add_argument("--json", action="store_true",
                       help="print the JSON artifact instead of the "
                            "rendered table")

    lint = sub.add_parser("lint",
                          help="static engine-invariant analysis")
    lint.add_argument("path", nargs="?", default=None,
                      help="tree to lint (default: the installed "
                           "spark_rapids_tpu package)")
    lint.add_argument("--format", choices=("text", "json"),
                      default="text", help="output format")
    lint.add_argument("--rule", default=None,
                      help="comma-separated rule ids to run "
                           "(default: all)")
    lint.add_argument("--baseline", default=None,
                      help="baseline JSON path (default: "
                           "<root>/../.lint-baseline.json when present)")
    lint.add_argument("--write-baseline", action="store_true",
                      help="grandfather every active finding into the "
                           "baseline file and exit 0")
    return p


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if args.cmd == "profile":
        from spark_rapids_tpu.tools.profile import (profiles_to_json,
                                                    render_report)
        from spark_rapids_tpu.tools.reader import load_profiles
        profiles, diag = load_profiles(args.log)
        if args.json:
            print(json.dumps(profiles_to_json(profiles, diag), indent=2))
        else:
            sys.stdout.write(render_report(
                profiles, diag, query_id=args.query,
                show_samples=args.samples,
                show_timeline=not args.no_timeline))
        return 0
    if args.cmd == "autotune":
        from spark_rapids_tpu.tools.autotune import (autotune,
                                                     render_recommendations,
                                                     to_conf_dict)
        from spark_rapids_tpu.tools.reader import load_profiles
        profiles, _diag = load_profiles(args.log)
        recs = autotune(profiles)
        if args.json:
            print(json.dumps(to_conf_dict(recs), indent=2))
        else:
            sys.stdout.write(render_recommendations(recs))
        return 0
    if args.cmd == "trace":
        from spark_rapids_tpu.tools.trace import render_trace, trace_from_log
        trace, unattributed, _diag = trace_from_log(args.log,
                                                    query_id=args.query)
        text = render_trace(trace)
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                f.write(text)
            print(f"wrote {len(trace['traceEvents'])} trace event(s) "
                  f"to {args.out}")
        else:
            print(text)
        if unattributed:
            print(f"!! {unattributed} hostTransition/deviceSync event(s) "
                  "unattributed to any query", file=sys.stderr)
            if args.check:
                return 1
        return 0
    if args.cmd == "audit":
        from spark_rapids_tpu.tools.audit import (render_audit, run_audit,
                                                  write_audit_baseline)
        from spark_rapids_tpu.tools.audit.passes import (
            DEFAULT_PEAK_BYTES_PER_S, DEFAULT_PEAK_FLOPS,
            DEFAULT_STORM_THRESHOLD, default_audit_baseline_path)
        report = run_audit(
            args.log,
            storm_threshold=(args.storm_threshold
                             if args.storm_threshold is not None
                             else DEFAULT_STORM_THRESHOLD),
            min_peak_fraction=args.min_peak_fraction,
            peak_flops=(args.peak_flops if args.peak_flops is not None
                        else DEFAULT_PEAK_FLOPS),
            peak_bw=(args.peak_bw if args.peak_bw is not None
                     else DEFAULT_PEAK_BYTES_PER_S),
            baseline_path=args.baseline)
        if args.write_baseline:
            path = args.baseline or default_audit_baseline_path(args.log)
            n = write_audit_baseline(path, report)
            print(f"wrote {n} baseline entr{'y' if n == 1 else 'ies'} "
                  f"to {path}")
            return 0
        if args.json:
            print(json.dumps(report.to_json(), indent=2))
        else:
            sys.stdout.write(render_audit(
                report, show_roofline=not args.no_roofline))
        return report.exit_code
    if args.cmd == "history":
        return _run_history(args)
    if args.cmd == "lint":
        from spark_rapids_tpu.tools.lint import (default_baseline_path,
                                                 default_rules,
                                                 render_text, run_lint,
                                                 write_baseline)
        rules = None
        if args.rule:
            wanted = {r.strip() for r in args.rule.split(",")}
            rules = [r for r in default_rules() if r.id in wanted]
            unknown = wanted - {r.id for r in rules}
            if unknown:
                print(f"unknown rule id(s): {', '.join(sorted(unknown))}",
                      file=sys.stderr)
                return 2
        report = run_lint(root=args.path, rules=rules,
                          baseline_path=args.baseline)
        if args.write_baseline:
            path = args.baseline or default_baseline_path(report.root)
            n = write_baseline(path, report)
            print(f"wrote {n} baseline entr{'y' if n == 1 else 'ies'} "
                  f"to {path}")
            return 0
        if args.format == "json":
            print(json.dumps(report.to_json(), indent=2))
        else:
            sys.stdout.write(render_text(report))
        return report.exit_code
    return 2


def _run_history(args) -> int:
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.tools.history import (HistoryWarehouse,
                                                calibrate, regress,
                                                render_profile,
                                                render_regress)
    # --db falls back to the registered warehouse conf
    if not args.db:
        args.db = C.default_conf().get(C.HISTORY_PATH.key)
    if not args.db:
        print("history: no warehouse: pass --db or set "
              f"{C.HISTORY_PATH.key}", file=sys.stderr)
        return 2
    if args.action == "ingest":
        with HistoryWarehouse(args.db) as wh:
            total = []
            for p in args.paths:
                total.extend(wh.ingest(p, label=args.label,
                                       force=args.force))
        for r in total:
            extra = (f"{r.get('queries', 0)} query(ies), "
                     f"{r.get('spans', 0)} span(s), "
                     f"{r.get('programs', 0)} program(s)"
                     if r["kind"] == "event_log"
                     else f"{r.get('metrics', 0)} metric(s)"
                     + (f" [FAILED RUN: {r['failure']}]"
                        if r.get("failure") else ""))
            verb = "updated (same content)" if r.get("updated") \
                else r["kind"]
            print(f"run {r['run_id']}: {verb} "
                  f"{r['source']} -> {extra}")
        return 0
    if args.action == "report":
        from spark_rapids_tpu.tools.history.warehouse import render_report
        with HistoryWarehouse(args.db) as wh:
            report = wh.report()
        if args.json:
            print(json.dumps(report, indent=2))
        else:
            sys.stdout.write(render_report(report))
        return 0
    if args.action == "regress":
        min_runs = args.min_runs if args.min_runs is not None \
            else int(C.HISTORY_REGRESS_MIN_RUNS.default)
        band_k = args.band_k if args.band_k is not None \
            else float(C.HISTORY_REGRESS_MAD_BANDS.default)
        kwargs = {"min_runs": min_runs, "band_k": band_k}
        if args.threshold is not None:
            kwargs["rel_threshold"] = args.threshold
        with HistoryWarehouse(args.db) as wh:
            result = regress(wh, **kwargs)
        if args.json:
            print(json.dumps(result, indent=2))
        else:
            sys.stdout.write(render_regress(result))
        return result["exit_code"]
    if args.action == "calibrate":
        with HistoryWarehouse(args.db) as wh:
            try:
                profile = calibrate(wh)
            except ValueError as e:
                print(f"calibrate: {e}", file=sys.stderr)
                return 2
        if args.out:
            with open(args.out, "w", encoding="utf-8") as f:
                json.dump(profile, f, indent=2, sort_keys=True)
                f.write("\n")
            print(f"wrote machine profile ({len(profile['stage_kinds'])} "
                  f"stage kind(s), residual bound "
                  f"±{profile['residual_bound'] * 100:.1f}%) to "
                  f"{args.out}")
        if args.json:
            print(json.dumps(profile, indent=2, sort_keys=True))
        elif not args.out:
            sys.stdout.write(render_profile(profile))
        return 0
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
