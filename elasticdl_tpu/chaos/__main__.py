"""Replay one churn trace against a real fleet and print its report.

``python -m elasticdl_tpu.chaos <trace-or-path> [--scale F]`` loads a
packaged trace by name (``--list`` prints them) or any trace JSON by
path, runs it through `ScenarioRunner`, and prints the scenario report
as ONE JSON line on stdout: per-job goodput, retention, relaunch and
preemption counters, with exact versions asserted at every probe point.
The runner raises (and dumps the flight recorder) on any broken
invariant, so reaching the JSON line IS the pass signal. CI replays
every packaged trace this way (.github/workflows/ci.yml
churn-scenario).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from elasticdl_tpu.chaos.scenario import (
    ScenarioRunner,
    TraceError,
    list_traces,
    load_trace,
)

NO_FAILOVER = (
    "trace has no kill_master event: no master failover was exercised"
)
# why a field of the report, or of a job's goodput block, may be null
NULL_REASONS = {
    "retention": (
        "trace sets baseline=false: no fault-free twin was run to "
        "provide the denominator"
    ),
    "baseline_images_per_sec": (
        "trace sets baseline=false: no fault-free twin was run"
    ),
    "time_to_adopt_secs": NO_FAILOVER,
    "failover_mode": NO_FAILOVER,
    "goodput_fraction": "no completed records in the clocked window",
    "gap_explained": "no raw-vs-goodput gap: zero records were recomputed",
}


def _annotate_nulls(record):
    """A null field gets a `<field>_skipped_reason` sibling, so a
    consumer can tell 'not applicable to this trace' from 'silently
    lost'."""
    for field in [k for k, v in record.items() if v is None]:
        record[f"{field}_skipped_reason"] = NULL_REASONS.get(
            field, "not measured in this mode"
        )
    return record


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m elasticdl_tpu.chaos", description=__doc__
    )
    parser.add_argument(
        "trace", nargs="?",
        help="packaged trace name, or a path to a trace JSON",
    )
    parser.add_argument(
        "--scale", type=float, default=1.0,
        help="multiplier on every job's record count (reported, so a "
        "shrunken run is not mistaken for a full one)",
    )
    parser.add_argument(
        "--list", action="store_true", help="print the packaged traces"
    )
    args = parser.parse_args(argv)
    if args.list:
        print("\n".join(list_traces()))
        return 0
    if not args.trace:
        parser.error("a trace name or path is required (--list names them)")
    try:
        trace = load_trace(args.trace)
    except TraceError as e:
        parser.error(str(e))
    print(
        f"chaos: {trace.name} (scale {args.scale:g}): {trace.description}",
        file=sys.stderr,
    )
    # the harness runs N worker processes on one host: they cannot
    # share a chip (scenario.py pins the workers to the CPU as well)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    report = ScenarioRunner(trace, scale=args.scale).run()
    for job in report["jobs"].values():
        _annotate_nulls(job["goodput"])
        # acceptance bar: whatever gap exists must be explained by the
        # recompute counter (identity by construction; guards against
        # a future accounting change silently breaking it)
        explained = job["goodput"].get("gap_explained")
        if explained is not None and abs(explained - 1.0) > 0.01:
            raise AssertionError(
                f"goodput gap not explained by recomputed records: "
                f"{explained}"
            )
    # master-failover headline (master/migration.py): hoist the anchor
    # job's time-to-adopt, so a master-failover trace reads as one
    # number and any other trace as an explained null
    anchor = report["jobs"].get(trace.jobs[0].tag) or {}
    failover = anchor.get("master_failover") or {}
    report["time_to_adopt_secs"] = failover.get("time_to_adopt_secs")
    report["failover_mode"] = failover.get("mode")
    print(json.dumps(_annotate_nulls(report)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
