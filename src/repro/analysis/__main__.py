"""``python -m repro.analysis`` — tables and the live dashboard.

Subcommands:

* ``table``       render a grouped comparison table from a results CSV
                  (``ResultSet.to_csv``); ``--diff A.csv B.csv ...`` diffs
                  runs with ratio/delta columns
* ``dash``        serve the live dashboard over an event journal

Output is plain text/markdown/CSV on stdout — the table renderers are the
same code the Python API uses, so CLI output and ``compare(...)`` output are
identical token for token.
"""
from __future__ import annotations

import argparse
import os
import sys
from typing import Any

from .metrics import MetricFrame, _as_float
from .tables import AGGREGATORS, compare, compare_frames


def _coerce(label: str) -> Any:
    """CLI strings match numeric column labels by value (2 == "2")."""
    num = _as_float(label)
    if num is None:
        return label
    return int(num) if num == int(num) else num


def _resolve_baseline(baseline: str | None, col_labels: list[Any]) -> Any:
    if baseline is None:
        return None
    for cand in (baseline, _coerce(baseline)):
        if cand in col_labels:
            return cand
    raise SystemExit(
        f"error: baseline {baseline!r} is not a column: {col_labels}"
    )


def _render(table: Any, fmt: str) -> str:
    if fmt == "md":
        return table.to_markdown()
    if fmt == "csv":
        return table.to_csv()
    return str(table)


def cmd_table_diff(args: argparse.Namespace) -> int:
    if args.csv:
        raise SystemExit("error: --diff is exclusive with --csv")
    if len(args.diff) < 2:
        raise SystemExit("error: --diff needs at least two runs")
    if not args.rows:
        raise SystemExit("error: --rows is required with --diff")
    if not args.metric or len(args.metric) > 1:
        raise SystemExit("error: --diff compares exactly one --metric")
    missing = [p for p in args.diff if not os.path.exists(p)]
    if missing:
        raise SystemExit(f"error: no results CSV {missing[0]}")
    metric = args.metric[0]
    pairs = [(p, MetricFrame.from_results_csv(p)) for p in args.diff]
    table = compare_frames(
        pairs, rows=args.rows, metric=metric, agg=args.agg,
        title=args.title or f"{metric}: " + " vs ".join(args.diff),
    )
    if args.baseline:
        table.baseline = _resolve_baseline(args.baseline, table.col_labels)
    print(_render(table, args.format))
    return 0


def cmd_table(args: argparse.Namespace) -> int:
    if args.diff:
        return cmd_table_diff(args)
    if not args.csv:
        raise SystemExit("error: pass --csv PATH or --diff PATH PATH ...")
    if not args.rows:
        raise SystemExit("error: --rows is required with --csv")
    frame = MetricFrame.from_results_csv(args.csv)
    metric = None
    if args.metric and (args.cols or len(args.metric) == 1):
        metric = args.metric[0]
    table = compare(
        frame,
        rows=args.rows,
        cols=args.cols or None,
        metric=metric,
        agg=args.agg,
        title=args.title,
    )
    table.baseline = _resolve_baseline(args.baseline, table.col_labels)
    print(_render(table, args.format))
    return 0


def cmd_dash(args: argparse.Namespace) -> int:
    from .dash import serve_journal

    dash, prov = serve_journal(
        args.journal, host=args.host, port=args.port,
        follow=not args.no_follow, total=args.total,
    )
    print(f"dashboard: {dash.url}  (journal: {args.journal})")
    try:
        import time

        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        dash.stop()
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Analysis over Memento results and event journals.",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("table", help="grouped comparison table")
    t.add_argument("--csv", help="ResultSet.to_csv file to analyze")
    t.add_argument("--diff", nargs="+", metavar="CSV",
                   help="diff two or more ResultSet CSV files: one column "
                   "per run, ratio/delta vs the first")
    t.add_argument("--rows", nargs="+", help="param keys for table rows")
    t.add_argument("--cols", nargs="+", help="param keys for table columns "
                   "(default: one column per metric)")
    t.add_argument("--metric", nargs="+", help="metric name(s) to include")
    t.add_argument("--agg", default="mean", choices=sorted(AGGREGATORS),
                   help="cell aggregator (default: mean)")
    t.add_argument("--baseline", help="column label to diff the others against")
    t.add_argument("--title", default="")
    t.add_argument("--format", default="md", choices=("md", "csv", "text"))
    t.set_defaults(fn=cmd_table)

    d = sub.add_parser("dash", help="serve the live dashboard over a journal")
    d.add_argument("--journal", required=True, help="event journal (JSONL)")
    d.add_argument("--host", default="127.0.0.1")
    d.add_argument("--port", type=int, default=8321)
    d.add_argument("--total", type=int, help="expected task total (for ETA)")
    d.add_argument("--no-follow", action="store_true",
                   help="replay once, don't tail the journal")
    d.set_defaults(fn=cmd_dash)
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
