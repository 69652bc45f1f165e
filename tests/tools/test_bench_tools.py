"""Bench tooling: gate trajectory handling and SLO-verdict gating.

Covers the observability-loop plumbing around the scenario harness:
`tools/bench_gate.py` must exit cleanly on an empty/fresh trajectory
and gate on the scenario-suite SLO verdict when present; `bench.py`
and `tools/bench_capture.py` must not measure, or cite, without a chip.
"""

import importlib.util
import json
import os
import subprocess
import sys

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(name: str, relpath: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO_DIR, relpath)
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


bench_gate = _load("_test_bench_gate", "tools/bench_gate.py")


def _write(path, payload, mtime=None):
    with open(path, "w") as fh:
        json.dump(payload, fh)
    if mtime is not None:
        os.utime(path, (mtime, mtime))


def _artifact(suite_verdict=None, stages=None, breached=()):
    extra = {"backend": "cpu"}
    if stages is not None:
        extra["update_e2e"] = {
            stage: {"p99_ms": p99, "p50_ms": p99 / 2, "count": 100}
            for stage, p99 in stages.items()
        }
    if suite_verdict is not None:
        extra["scenario_suite"] = {
            "verdict": suite_verdict,
            "scenarios": {
                "smoke": {"verdict": suite_verdict, "breached": list(breached)}
            },
        }
    return {"metric": "m", "value": 1.0, "unit": "x", "extra": extra}


# -- trajectory handling -------------------------------------------------------


def test_gate_empty_trajectory_skips_cleanly(tmp_path, capsys):
    assert bench_gate.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "no prior round" in out
    assert "gate skipped" in out


def test_gate_missing_directory_skips_cleanly(tmp_path, capsys):
    assert bench_gate.main(["--dir", str(tmp_path / "nope")]) == 0
    assert "no prior round" in capsys.readouterr().out


def test_gate_single_artifact_passes_without_prior_round(tmp_path, capsys):
    _write(tmp_path / "BENCH_r01.json", _artifact(suite_verdict="pass"))
    assert bench_gate.main(["--dir", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "pairwise p99 gate skipped" in out
    assert "scenario_suite: pass" in out


def test_gate_unparseable_current_skips(tmp_path, capsys):
    (tmp_path / "BENCH_r01.json").write_text("{not json")
    assert bench_gate.main(["--dir", str(tmp_path)]) == 0
    assert "SKIP" in capsys.readouterr().out


# -- scenario-suite SLO verdict gating ----------------------------------------


def test_gate_fails_on_scenario_suite_verdict(tmp_path, capsys):
    """A breached scenario SLO fails the round even with no prior round
    to compare p99s against."""
    _write(
        tmp_path / "BENCH_r01.json",
        _artifact(suite_verdict="fail", breached=["burst:latency"]),
    )
    assert bench_gate.main(["--dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "scenario_suite verdict 'fail'" in out
    assert "smoke:burst:latency" in out


def test_gate_fails_on_scenario_suite_error(tmp_path):
    _write(tmp_path / "BENCH_r01.json", _artifact(suite_verdict="error"))
    assert bench_gate.main(["--dir", str(tmp_path)]) == 1


def test_gate_scenario_verdict_gates_alongside_pairwise(tmp_path, capsys):
    """Verdict fail + healthy p99s still fails; healthy verdict + healthy
    p99s passes."""
    _write(
        tmp_path / "BENCH_r01.json",
        _artifact(suite_verdict="pass", stages={"total": 10.0}),
        mtime=1_000_000,
    )
    _write(
        tmp_path / "BENCH_r02.json",
        _artifact(suite_verdict="fail", stages={"total": 10.0}),
        mtime=2_000_000,
    )
    assert bench_gate.main(["--dir", str(tmp_path)]) == 1
    _write(
        tmp_path / "BENCH_r02.json",
        _artifact(suite_verdict="pass", stages={"total": 10.0}),
        mtime=2_000_000,
    )
    assert bench_gate.main(["--dir", str(tmp_path)]) == 0


def test_gate_pairwise_regression_still_detected(tmp_path, capsys):
    _write(
        tmp_path / "BENCH_r01.json",
        _artifact(stages={"total": 10.0}),
        mtime=1_000_000,
    )
    _write(
        tmp_path / "BENCH_r02.json",
        _artifact(stages={"total": 20.0}),
        mtime=2_000_000,
    )
    assert bench_gate.main(["--dir", str(tmp_path)]) == 1
    assert "REGRESSION" in capsys.readouterr().out


def test_gate_extracts_overload_storm_interactive_p99():
    """The overload_storm storm-phase p99 (interactive latency while
    the ladder sheds) is a gated stage, compared across rounds like any
    other."""
    payload = _artifact()
    payload["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {
            "overload_storm": {
                "verdict": "pass",
                "breached": [],
                "phase_p99_ms": {"calm": 2.0, "storm": 5.0, "recover": 2.0},
            }
        },
    }
    stages = bench_gate.stage_p99s(payload)
    assert stages["overload_storm.interactive_p99"] == 5.0
    # a regressed storm p99 fails the pairwise compare
    current = json.loads(json.dumps(payload))
    current["extra"]["scenario_suite"]["scenarios"]["overload_storm"][
        "phase_p99_ms"
    ]["storm"] = 50.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any("overload_storm.interactive_p99" in r for r in regressions)


def test_gate_extracts_multi_device_storm_interactive_p99():
    """The multi_device_storm storm-phase p99 (small-doc interactive
    latency while one mega-doc skews a chip hot and the rebalancer
    migrates docs off it) is a gated stage — hot-doc skew must not
    bleed back into the interactive path across rounds."""
    payload = _artifact()
    payload["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {
            "multi_device_storm": {
                "verdict": "pass",
                "breached": [],
                "phase_p99_ms": {
                    "steady": 4.0, "storm": 9.0, "rebalanced": 4.0,
                },
            }
        },
    }
    stages = bench_gate.stage_p99s(payload)
    assert stages["multi_device_storm.interactive_p99"] == 9.0
    current = json.loads(json.dumps(payload))
    current["extra"]["scenario_suite"]["scenarios"]["multi_device_storm"][
        "phase_p99_ms"
    ]["storm"] = 90.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any("multi_device_storm.interactive_p99" in r for r in regressions)


def test_gate_extracts_edge_fanout_interactive_p99():
    """The edge_fanout fanout-phase p99 (cross-edge interactive latency
    through the relay lane) is a gated stage — the split front door
    must stay a constant tax across rounds."""
    payload = _artifact()
    payload["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {
            "edge_fanout": {
                "verdict": "pass",
                "breached": [],
                "phase_p99_ms": {"steady": 3.0, "fanout": 8.0, "cool": 3.0},
            }
        },
    }
    stages = bench_gate.stage_p99s(payload)
    assert stages["edge_fanout.interactive_p99"] == 8.0
    current = json.loads(json.dumps(payload))
    current["extra"]["scenario_suite"]["scenarios"]["edge_fanout"][
        "phase_p99_ms"
    ]["fanout"] = 80.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any("edge_fanout.interactive_p99" in r for r in regressions)


def test_gate_extracts_edge_fanout_cross_tier_e2e_p99():
    """The fleet plane's edge→cell→edge trace p99 (extra.fleet) is a
    gated stage — the relay hop growing a tail the interactive p99
    misses must still fail the round. Absent fleet evidence (older
    rounds) stays informational, never an error."""
    payload = _artifact()
    payload["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {
            "edge_fanout": {
                "verdict": "pass",
                "breached": [],
                "phase_p99_ms": {"fanout": 8.0},
                "fleet": {
                    "peers": 4,
                    "stale_peers": 0,
                    "digests_ingested": 40,
                    "cross_tier_e2e_ms": {
                        "p50_ms": 10.0,
                        "p99_ms": 40.0,
                        "count": 64,
                    },
                },
            }
        },
    }
    stages = bench_gate.stage_p99s(payload)
    assert stages["edge_fanout.cross_tier_e2e_p99"] == 40.0
    current = json.loads(json.dumps(payload))
    current["extra"]["scenario_suite"]["scenarios"]["edge_fanout"]["fleet"][
        "cross_tier_e2e_ms"
    ]["p99_ms"] = 400.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any("edge_fanout.cross_tier_e2e_p99" in r for r in regressions)
    # old rounds without fleet evidence: the stage is simply absent
    old = _artifact()
    old["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {"edge_fanout": {"verdict": "pass", "phase_p99_ms": {}}},
    }
    assert "edge_fanout.cross_tier_e2e_p99" not in bench_gate.stage_p99s(old)


def test_gate_extracts_diurnal_autoscale_stages():
    """diurnal_autoscale gates TWO stages: the peak-phase p99 (latency
    while the controller scales the fleet under load) and the
    steady-trough footprint ratio (mean active cells over `night` /
    static fleet — dimensionless, but a fleet that stops scaling back
    down regresses it through the same relative compare). Rounds
    predating the autoscale evidence simply lack the ratio stage."""
    payload = _artifact()
    payload["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {
            "diurnal_autoscale": {
                "verdict": "pass",
                "breached": [],
                "phase_p99_ms": {"trough": 2.0, "peak": 12.0, "night": 2.0},
                "autoscale": {
                    "fleet_cells": 4,
                    "steady_footprint_ratio": 0.25,
                    "scale_ups": 3,
                    "scale_downs": 3,
                },
            }
        },
    }
    stages = bench_gate.stage_p99s(payload)
    assert stages["diurnal_autoscale.interactive_p99"] == 12.0
    assert stages["diurnal_autoscale.steady_footprint_ratio"] == 0.25
    # peak p99 regression fails the round
    current = json.loads(json.dumps(payload))
    current["extra"]["scenario_suite"]["scenarios"]["diurnal_autoscale"][
        "phase_p99_ms"
    ]["peak"] = 120.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any("diurnal_autoscale.interactive_p99" in r for r in regressions)
    # a fleet that stopped scaling down fails even with latency green
    current = json.loads(json.dumps(payload))
    current["extra"]["scenario_suite"]["scenarios"]["diurnal_autoscale"][
        "autoscale"
    ]["steady_footprint_ratio"] = 1.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any(
        "diurnal_autoscale.steady_footprint_ratio" in r for r in regressions
    )
    # pre-autoscale rounds: no ratio stage, no false alarm
    old = _artifact()
    old["extra"]["scenario_suite"] = {
        "verdict": "pass",
        "scenarios": {
            "diurnal_autoscale": {"verdict": "pass", "phase_p99_ms": {}}
        },
    }
    assert (
        "diurnal_autoscale.steady_footprint_ratio"
        not in bench_gate.stage_p99s(old)
    )


def test_gate_wire_saturation_stages_are_higher_is_better():
    """The wire-saturation throughput stages gate in the OPPOSITE
    direction from every latency stage: a frames/s DROP beyond
    tolerance fails the round, growth never does, and the ms jitter
    floor does not apply to frames/s."""
    payload = _artifact()
    payload["extra"]["wire_saturation"] = {
        "frames_per_s": 8000.0,
        "headroom_frames_per_s": 9000.0,
        "headroom_ratio": 1.125,
        "headroom_within_2x": True,
        "top_costs": [{"site": "frame_decode", "type": "Sync"}],
    }
    stages = bench_gate.stage_p99s(payload)
    assert stages["wire_saturation.frames_per_s"] == 8000.0
    assert stages["wire_saturation.headroom_frames_per_s"] == 9000.0
    assert "wire_saturation.frames_per_s" in bench_gate.HIGHER_IS_BETTER

    # a throughput DROP beyond tolerance regresses
    current = json.loads(json.dumps(payload))
    current["extra"]["wire_saturation"]["frames_per_s"] = 4000.0
    regressions, notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert any("wire_saturation.frames_per_s" in r for r in regressions)
    assert any("frames/s" in r for r in regressions)  # unit-aware note
    # headroom did not drop — it must not be flagged
    assert not any(
        "wire_saturation.headroom_frames_per_s" in r for r in regressions
    )

    # throughput GROWTH (which would fail a lower-is-better compare at
    # the same tolerance) passes clean
    current = json.loads(json.dumps(payload))
    current["extra"]["wire_saturation"]["frames_per_s"] = 16000.0
    current["extra"]["wire_saturation"]["headroom_frames_per_s"] = 18000.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert not any("wire_saturation" in r for r in regressions)

    # a drop INSIDE tolerance stays green (no ms floor shenanigans)
    current = json.loads(json.dumps(payload))
    current["extra"]["wire_saturation"]["frames_per_s"] = 7000.0
    regressions, _notes = bench_gate.compare(
        payload, current, tolerance=0.25, floor_ms=0.25
    )
    assert not any("wire_saturation.frames_per_s" in r for r in regressions)


def test_gate_wire_saturation_headroom_band_note(capsys, tmp_path):
    """The 2x headroom-band check is informational on the current round:
    inside the band notes OK, outside warns — never a gate failure (the
    band is owned by the bench pass + its tests; shared-runner noise
    must not become a false alarm)."""
    payload = _artifact(suite_verdict="pass")
    payload["extra"]["wire_saturation"] = {
        "frames_per_s": 8000.0,
        "headroom_frames_per_s": 9000.0,
        "headroom_ratio": 1.125,
        "headroom_within_2x": True,
    }
    failures, notes = bench_gate.current_round_checks(payload)
    assert not failures
    assert any("within 2x" in note for note in notes)

    payload["extra"]["wire_saturation"]["headroom_ratio"] = 5.0
    payload["extra"]["wire_saturation"]["headroom_within_2x"] = False
    failures, notes = bench_gate.current_round_checks(payload)
    assert not failures
    assert any("OUTSIDE the 2x" in note for note in notes)


# -- no chip: nothing measured, nothing cited ----------------------------------


def test_bench_exits_nonzero_off_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO_DIR, "bench.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""  # no JSON line: no number to mistake
    assert "no TPU" in proc.stderr and "nothing measured" in proc.stderr
