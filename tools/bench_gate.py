#!/usr/bin/env python
"""Latency regression gate over bench rounds.

Compares the newest two `BENCH_*.json` artifacts (or two explicit
files) on their per-stage p99s — `extra.update_e2e.<stage>.p99_ms`,
`extra.wire_load.ingress.p99_ms`,
`extra.fanout_storm.merge_to_last_write_p99_ms`,
`extra.replica_storm.merge_to_remote_broadcast_p99_ms`, the adaptive
scheduler's `extra.mixed_load.governor_on.interactive_p99_ms`
(interactive merge→broadcast under concurrent hydration+compaction
with the lane arbiter + governor on), the minimal-work merge's
`extra.mixed_load.governor_on.microbatch_p99_ms` (per-flush wall time
with the run-merge fast path engaged) and
`extra.catchup_storm.cold_sync_p99_ms` (post-storm cold-joiner
SyncStep2 through the on-device catch-up pack), the overload control
plane's
`extra.scenario_suite.scenarios.overload_storm.phase_p99_ms.storm`
(gated as `overload_storm.interactive_p99`: interactive edit p99 while
the brownout ladder is at RED and shedding), the elastic fleet's
`...scenarios.diurnal_autoscale.phase_p99_ms.peak` (gated as
`diurnal_autoscale.interactive_p99`: peak-phase p99 while the
autoscaler scales the cell fleet under the load) and
`...diurnal_autoscale.autoscale.steady_footprint_ratio` (gated as
`diurnal_autoscale.steady_footprint_ratio`: mean active cells over the
steady trough / static fleet — a fleet that stops scaling back down
regresses this even with latency green), and the durability plane's
`extra.wal_load.append_p99_ms` +
`extra.wal_load.wal_on.merge_to_last_write_p99_ms` — and exits nonzero
when any stage regressed beyond the tolerance. Wired as an OPT-IN CI/verify step
(latency on shared CPU runners is noisy; the gate is for on-chip
rounds and deliberate local runs):

    python tools/bench_gate.py                 # newest two BENCH_*.json
    python tools/bench_gate.py --tolerance 0.5 # allow +50% per stage
    python tools/bench_gate.py --current BENCH_b.json --previous BENCH_a.json

Safety rails (exit 0 with a SKIP note, never a false alarm):
- an empty or single-round trajectory ("no prior round — gate skipped"),
- either file unreadable/unparseable,
- the two rounds ran on different backends (a CPU-fallback round must
  not be compared against an on-chip round),
- a stage present in only one round (new stages are informational).

A stage regresses when `current_p99 > previous_p99 * (1 + tolerance) +
floor_ms` — the absolute floor keeps micro-stage jitter (fractions of a
millisecond) from tripping the relative check.

Most stages are latencies (lower is better), but the gate is
direction-aware: throughput stages listed in `HIGHER_IS_BETTER` — the
wire-saturation pass's measured sustained `wire_saturation.frames_per_s`
and the headroom model's predicted
`wire_saturation.headroom_frames_per_s` (docs/guides/observability.md,
"profiling & cost attribution") — regress when the CURRENT value drops
below `previous * (1 - tolerance)`; the ms floor does not apply to
frames/s.

One check looks at the CURRENT round alone (it doesn't need a prior
round, so it runs even on a fresh trajectory): the scenario-suite SLO
verdict (`extra.scenario_suite.verdict`, from the loadgen burn-rate
harness). A `fail`/`error` verdict fails the gate — a breached SLO is a
regression even when every raw p99 moved inside tolerance.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# throughput stages: a DROP is the regression. Everything else in
# stage_p99s is a latency (or a ratio gated like one) where growth is.
HIGHER_IS_BETTER = frozenset(
    {
        "wire_saturation.frames_per_s",
        "wire_saturation.sustained_frames_per_s",
        "wire_saturation.headroom_frames_per_s",
    }
)


def stage_unit(stage: str) -> str:
    return "frames/s" if stage in HIGHER_IS_BETTER else "ms"


def _artifact_key(path: str) -> "tuple[float, int, str]":
    """Newest-last ordering by mtime (a fresh `BENCH_next.json` from the
    documented workflow MUST outrank older numbered rounds), tie-broken
    by the BENCH_r<N> round number for same-second writes."""
    match = re.search(r"BENCH_r(\d+)", os.path.basename(path))
    round_no = int(match.group(1)) if match else -1
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        mtime = 0.0
    return (mtime, round_no, path)


def find_artifacts(directory: str) -> "list[str]":
    return sorted(glob.glob(os.path.join(directory, "BENCH_*.json")), key=_artifact_key)


def load_round(path: str) -> "dict | None":
    """Parse one artifact. Artifacts come in two shapes: the bench's
    own JSON line, or the driver's wrapper with the real payload under
    "parsed"."""
    try:
        with open(path) as fh:
            data = json.load(fh)
    except Exception:
        return None
    if isinstance(data, dict) and "parsed" in data and isinstance(data["parsed"], dict):
        data = data["parsed"]
    return data if isinstance(data, dict) else None


def stage_p99s(payload: dict) -> "dict[str, float]":
    """Flatten every gated p99 out of one round's extra section."""
    extra = payload.get("extra") or {}
    stages: "dict[str, float]" = {}
    update_e2e = extra.get("update_e2e")
    if isinstance(update_e2e, dict):
        for stage, stats in update_e2e.items():
            if not isinstance(stats, dict):
                continue  # scalar siblings are not stages
            p99 = stats.get("p99_ms")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages[f"update_e2e.{stage}"] = float(p99)
    wire = extra.get("wire_load")
    if isinstance(wire, dict):
        ingress = wire.get("ingress")
        if isinstance(ingress, dict) and isinstance(
            ingress.get("p99_ms"), (int, float)
        ):
            stages["wire_load.ingress"] = float(ingress["p99_ms"])
    fanout = extra.get("fanout_storm")
    if isinstance(fanout, dict):
        p99 = fanout.get("merge_to_last_write_p99_ms")
        if isinstance(p99, (int, float)) and not isinstance(p99, bool):
            stages["fanout_storm.merge_to_last_write"] = float(p99)
    replica = extra.get("replica_storm")
    if isinstance(replica, dict):
        p99 = replica.get("merge_to_remote_broadcast_p99_ms")
        if isinstance(p99, (int, float)) and not isinstance(p99, bool):
            stages["replica_storm.merge_to_remote_broadcast"] = float(p99)
    mixed = extra.get("mixed_load")
    if isinstance(mixed, dict):
        governor_on = mixed.get("governor_on")
        if isinstance(governor_on, dict):
            p99 = governor_on.get("interactive_p99_ms")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["mixed_load.interactive_p99"] = float(p99)
            # per-microbatch flush wall time under the mixed storm: the
            # minimal-work run merge keeps sequential columns off the
            # full-row integrate, so a regression here means the fast
            # path stopped engaging (or got slower than the scan)
            p99 = governor_on.get("microbatch_p99_ms")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["mixed_load.microbatch_p99"] = float(p99)
    storm = extra.get("catchup_storm")
    if isinstance(storm, dict):
        # post-storm cold-joiner SyncStep2 latency: the on-device
        # catch-up pack replaces the host serve-log walk, so a
        # regression here means cold joins fell back to host encodes
        p99 = storm.get("cold_sync_p99_ms")
        if isinstance(p99, (int, float)) and not isinstance(p99, bool):
            stages["catchup_storm.cold_sync_p99"] = float(p99)
    suite = extra.get("scenario_suite")
    if isinstance(suite, dict):
        # shed-mode interactive latency: the overload_storm scenario's
        # storm-phase p99 is measured WHILE the ladder is at RED and
        # shedding — a regression here means brownout mode stopped
        # protecting the interactive path
        storm = (suite.get("scenarios") or {}).get("overload_storm")
        if isinstance(storm, dict):
            p99 = (storm.get("phase_p99_ms") or {}).get("storm")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["overload_storm.interactive_p99"] = float(p99)
        # multi-device interactive latency: the multi_device_storm
        # scenario's storm-phase p99 is measured while one mega-doc
        # skews a chip hot and the rebalancer migrates docs off it —
        # a regression here means hot-doc skew started bleeding into
        # the small-doc interactive path again
        storm_md = (suite.get("scenarios") or {}).get("multi_device_storm")
        if isinstance(storm_md, dict):
            p99 = (storm_md.get("phase_p99_ms") or {}).get("storm")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["multi_device_storm.interactive_p99"] = float(p99)
        # hot-doc fan-out latency: the mega_audience scenario's
        # fanout-phase p99 is measured while a watermark-crossing read
        # audience is spread over follower cells — a regression here
        # means audience growth started bleeding back into the owner's
        # write→observe path (the flat-fan-out promise of
        # docs/guides/hot-doc-replication.md)
        mega = (suite.get("scenarios") or {}).get("mega_audience")
        if isinstance(mega, dict):
            p99 = (mega.get("phase_p99_ms") or {}).get("fanout")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["mega_audience.fanout_p99"] = float(p99)
        # edge-tier interactive latency: the edge_fanout scenario's
        # fanout-phase p99 is measured writer->edge->cell->edge->reader
        # under a door-admitted join storm — a regression here means
        # the split front door stopped being a constant tax
        # elastic-fleet stages (docs/guides/elastic-fleet.md): the
        # diurnal_autoscale peak-phase p99 is measured while the
        # controller scales the cell fleet under it — a regression
        # means elasticity started taxing the interactive path — and
        # the steady-trough footprint ratio (mean active cells during
        # `night` / static fleet, dimensionless but gated through the
        # same relative check) catches a fleet that stopped scaling
        # back down
        diurnal = (suite.get("scenarios") or {}).get("diurnal_autoscale")
        if isinstance(diurnal, dict):
            p99 = (diurnal.get("phase_p99_ms") or {}).get("peak")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["diurnal_autoscale.interactive_p99"] = float(p99)
            autoscale = diurnal.get("autoscale")
            if isinstance(autoscale, dict):
                ratio = autoscale.get("steady_footprint_ratio")
                if isinstance(ratio, (int, float)) and not isinstance(
                    ratio, bool
                ):
                    stages["diurnal_autoscale.steady_footprint_ratio"] = float(
                        ratio
                    )
        edge = (suite.get("scenarios") or {}).get("edge_fanout")
        if isinstance(edge, dict):
            p99 = (edge.get("phase_p99_ms") or {}).get("fanout")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["edge_fanout.interactive_p99"] = float(p99)
            # cross-tier trace latency: the fleet plane's edge→cell→edge
            # e2e p99 (extra.fleet, fed by relay trace propagation) — a
            # regression here means the relay hop or the device close
            # path grew a tail the interactive p99 alone can miss
            fleet = edge.get("fleet")
            if isinstance(fleet, dict):
                cross = fleet.get("cross_tier_e2e_ms")
                if isinstance(cross, dict):
                    p99 = cross.get("p99_ms")
                    if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                        stages["edge_fanout.cross_tier_e2e_p99"] = float(p99)
    wire_sat = extra.get("wire_saturation")
    if isinstance(wire_sat, dict):
        # higher-is-better throughput stages (HIGHER_IS_BETTER): the
        # measured saturation wall of the direct-drive ingress ramp and
        # the cost ledger's predicted sustainable rate — either one
        # dropping means the per-frame host path got more expensive
        for key, stage in (
            ("frames_per_s", "wire_saturation.frames_per_s"),
            ("sustained_frames_per_s", "wire_saturation.sustained_frames_per_s"),
            ("headroom_frames_per_s", "wire_saturation.headroom_frames_per_s"),
        ):
            value = wire_sat.get(key)
            if (
                isinstance(value, (int, float))
                and not isinstance(value, bool)
                and value > 0
            ):
                stages[stage] = float(value)
    wal = extra.get("wal_load")
    if isinstance(wal, dict):
        append_p99 = wal.get("append_p99_ms")
        if isinstance(append_p99, (int, float)) and not isinstance(append_p99, bool):
            stages["wal_load.append"] = float(append_p99)
        wal_on = wal.get("wal_on")
        if isinstance(wal_on, dict):
            p99 = wal_on.get("merge_to_last_write_p99_ms")
            if isinstance(p99, (int, float)) and not isinstance(p99, bool):
                stages["wal_load.merge_to_last_write_wal_on"] = float(p99)
    return stages


def backend_of(payload: dict) -> "str | None":
    extra = payload.get("extra") or {}
    return extra.get("backend")


def current_round_checks(payload: dict) -> "tuple[list[str], list[str]]":
    """Checks on the newest round alone -> (failures, notes)."""
    failures: "list[str]" = []
    notes: "list[str]" = []
    extra = payload.get("extra") or {}
    suite = extra.get("scenario_suite")
    if isinstance(suite, dict):
        verdict = suite.get("verdict")
        scenarios = suite.get("scenarios") or {}
        detail = ", ".join(
            f"{name}={s.get('verdict')}" for name, s in sorted(scenarios.items())
        )
        if verdict == "pass":
            notes.append(f"OK   scenario_suite: pass ({detail})")
        elif verdict in ("fail", "error"):
            breached = [
                f"{name}:{target}"
                for name, s in sorted(scenarios.items())
                for target in (s.get("breached") or [])
            ]
            failures.append(
                f"scenario_suite verdict {verdict!r}"
                + (f" (breached: {', '.join(breached)})" if breached else f" ({detail})")
            )
        else:
            notes.append(f"NOTE scenario_suite: verdict {verdict!r}")
    wire_sat = extra.get("wire_saturation")
    if isinstance(wire_sat, dict) and "headroom_within_2x" in wire_sat:
        ratio = wire_sat.get("headroom_ratio")
        if wire_sat.get("headroom_within_2x"):
            notes.append(
                f"OK   wire_saturation: headroom model within 2x of the "
                f"measured saturation (ratio {ratio})"
            )
        else:
            # informational, not a failure: the 2x band check is owned
            # by the bench pass + tests; shared-runner noise must not
            # turn it into a gate false alarm
            notes.append(
                f"WARN wire_saturation: headroom model OUTSIDE the 2x "
                f"band (ratio {ratio}) — the cost ledger's loop-site "
                "partition may have drifted from the real loop thread"
            )
    return failures, notes


def compare(
    previous: dict,
    current: dict,
    tolerance: float,
    floor_ms: float,
) -> "tuple[list[str], list[str]]":
    """-> (regressions, notes)."""
    notes: "list[str]" = []
    prev_backend, cur_backend = backend_of(previous), backend_of(current)
    if prev_backend != cur_backend:
        notes.append(
            f"SKIP: backend changed ({prev_backend!r} -> {cur_backend!r}); "
            "cross-backend latencies are not comparable"
        )
        return [], notes
    prev_stages = stage_p99s(previous)
    cur_stages = stage_p99s(current)
    if not prev_stages or not cur_stages:
        notes.append("SKIP: per-stage p99 data missing from one or both rounds")
        return [], notes
    regressions: "list[str]" = []
    for stage in sorted(cur_stages):
        unit = stage_unit(stage)
        if stage not in prev_stages:
            notes.append(f"NEW  {stage}: {cur_stages[stage]:.3f}{unit} (no baseline)")
            continue
        prev, cur = prev_stages[stage], cur_stages[stage]
        verdict = "OK  "
        if stage in HIGHER_IS_BETTER:
            # throughput: the budget is a FLOOR, and the ms slack does
            # not apply — tolerance alone absorbs run-to-run jitter
            budget = prev * (1.0 - tolerance)
            if cur < budget:
                verdict = "FAIL"
                regressions.append(
                    f"{stage}: {prev:.3f}{unit} -> {cur:.3f}{unit} "
                    f"(floor {budget:.3f}{unit} at -{tolerance:.0%})"
                )
        else:
            budget = prev * (1.0 + tolerance) + floor_ms
            if cur > budget:
                verdict = "FAIL"
                regressions.append(
                    f"{stage}: {prev:.3f}{unit} -> {cur:.3f}{unit} "
                    f"(budget {budget:.3f}{unit} at +{tolerance:.0%} +{floor_ms:g}ms)"
                )
        notes.append(
            f"{verdict} {stage}: {prev:.3f}{unit} -> {cur:.3f}{unit}"
            f" ({'+' if cur >= prev else ''}{(cur - prev):.3f})"
        )
    return regressions, notes


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(
        description="Gate on per-stage p99 regressions between bench rounds."
    )
    parser.add_argument(
        "--tolerance",
        type=float,
        default=float(os.environ.get("BENCH_GATE_TOLERANCE", 0.25)),
        help="allowed relative growth per stage (default 0.25 = +25%%)",
    )
    parser.add_argument(
        "--floor-ms",
        type=float,
        default=float(os.environ.get("BENCH_GATE_FLOOR_MS", 0.25)),
        help="absolute slack added to every budget (default 0.25ms)",
    )
    parser.add_argument("--current", help="explicit current-round artifact")
    parser.add_argument("--previous", help="explicit previous-round artifact")
    parser.add_argument(
        "--dir", default=_REPO_DIR, help="directory holding BENCH_*.json"
    )
    args = parser.parse_args(argv)

    if bool(args.current) != bool(args.previous):
        # a half-pinned comparison would silently fall through to the
        # newest-two scan and gate a pair the user did not ask about
        parser.error("--current and --previous must be given together")
    prev_path: "str | None" = None
    if args.current and args.previous:
        prev_path, cur_path = args.previous, args.current
    else:
        artifacts = find_artifacts(args.dir)
        if not artifacts:
            # an empty trajectory is a fresh start, not an error — but
            # say so explicitly rather than silently passing
            print(f"no prior round — gate skipped (no BENCH_*.json under {args.dir})")
            return 0
        cur_path = artifacts[-1]
        if len(artifacts) >= 2:
            prev_path = artifacts[-2]

    current = load_round(cur_path)
    if current is None:
        print(f"SKIP: could not parse {os.path.basename(cur_path)}")
        return 0

    # current-round checks run regardless of trajectory depth: the
    # scenario-suite SLO verdict is a property of THIS round, not a
    # comparison
    failures, cur_notes = current_round_checks(current)

    if prev_path is None:
        print(
            f"bench_gate: {os.path.basename(cur_path)} "
            "(no prior round — pairwise p99 gate skipped)"
        )
        notes = cur_notes
        regressions: "list[str]" = []
    else:
        previous = load_round(prev_path)
        if previous is None:
            print(
                f"bench_gate: {os.path.basename(cur_path)} "
                f"(previous round unreadable — pairwise p99 gate skipped)"
            )
            notes = cur_notes
            regressions = []
        else:
            print(
                f"bench_gate: {os.path.basename(prev_path)} -> "
                f"{os.path.basename(cur_path)}"
            )
            regressions, notes = compare(
                previous, current, args.tolerance, args.floor_ms
            )
            notes = notes + cur_notes
    for note in notes:
        print(f"  {note}")
    problems = regressions + failures
    if problems:
        print(f"REGRESSION: {len(problems)} check(s) failed")
        for problem in problems:
            print(f"  FAIL {problem}")
        return 1
    print("PASS: no stage regressed beyond tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
