"""The ``benchmarks/run.py --smoke --json=PATH`` artifact is what CI
uploads as the machine-readable perf trajectory — if its schema drifts (or
the writer silently stops emitting rows), the upload goes stale without
any test noticing. Two layers:

* a fast in-process test drives ``run.main()`` over a stub section and
  validates the full artifact schema (keys, row types, flag echo,
  timings);
* a ``slow``-lane test runs the REAL ``--smoke`` leg in a subprocess and
  checks every smoke section produced rows — the exact artifact CI
  uploads.
"""

import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _validate_schema(payload, expect_sections=None):
    """The BENCH_*.json contract: top-level keys, row shape, non-empty
    results, a timing per executed section."""
    assert set(payload) == {"flags", "sections", "timings_seconds", "results"}
    assert isinstance(payload["flags"], dict)
    assert isinstance(payload["sections"], list) and payload["sections"]
    assert isinstance(payload["timings_seconds"], dict)
    assert set(payload["timings_seconds"]) == set(payload["sections"])
    for t in payload["timings_seconds"].values():
        assert isinstance(t, (int, float)) and t >= 0
    assert isinstance(payload["results"], list) and payload["results"]
    emitted_sections = set()
    for row in payload["results"]:
        assert set(row) == {"section", "metric", "value"}, row
        assert isinstance(row["section"], str) and row["section"]
        assert isinstance(row["metric"], str) and row["metric"]
        assert isinstance(row["value"], (int, float, str, bool)), row
        emitted_sections.add(row["section"])
    if expect_sections is not None:
        for name in expect_sections:
            assert any(s == name or s.startswith(name) for s in emitted_sections), (
                f"section {name!r} emitted no rows; emitted: {sorted(emitted_sections)}")


class _StubSection:
    """Stands in for a bench module: emits a few typed rows."""

    @staticmethod
    def main():
        from benchmarks.common import emit

        emit("stub", "int_metric", 3)
        emit("stub", "float_metric", 1.25)
        emit("stub", "str_metric", "a|b")


def test_json_artifact_schema_fast(tmp_path, monkeypatch):
    import benchmarks.run as run
    from benchmarks import common

    path = tmp_path / "bench.json"
    monkeypatch.setattr(run, "SECTIONS", {"stub": _StubSection})
    monkeypatch.setattr(run, "SMOKE_SECTIONS", ("stub",))
    monkeypatch.setattr(common, "RESULTS", [])
    monkeypatch.setattr(common, "OPTIONS", {})
    monkeypatch.setattr(sys, "argv",
                        ["run.py", "--smoke", "--window=8", f"--json={path}"])
    run.main()
    payload = json.loads(path.read_text())
    _validate_schema(payload, expect_sections=["stub"])
    assert payload["flags"]["smoke"] == "1"
    assert payload["flags"]["window"] == "8"
    assert payload["sections"] == ["stub"]
    assert len(payload["results"]) == 3


def test_json_flag_requires_path(monkeypatch):
    import benchmarks.run as run

    monkeypatch.setattr(sys, "argv", ["run.py", "--json="])
    with pytest.raises(SystemExit, match="--json expects a path"):
        run.main()


def _bench_env():
    env = dict(os.environ)
    # the runner's __main__ turns JAX's persistent compilation cache on;
    # tests keep it off
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["PYTHONPATH"] = os.path.join(REPO_ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


# CLI section name -> emitted row-section prefix, where they differ (the
# paper-figure sections emit under their table/figure name).
EMITTED_PREFIX = {"depcheck": "table2_depcheck", "window_size": "fig29_window"}


def _emitted_names(cli_sections):
    return [EMITTED_PREFIX.get(n, n) for n in cli_sections]


def test_smoke_sections_cover_dependency_engine():
    """The smoke set must keep exercising the scoreboard counters: the
    depcheck probe-vs-scan section and the window_size large-window leg."""
    import benchmarks.run as run

    assert "depcheck" in run.SMOKE_SECTIONS
    assert "window_size" in run.SMOKE_SECTIONS


@pytest.mark.slow  # runs the real smoke benchmark leg (~1-2 min)
def test_smoke_json_artifact_real(tmp_path):
    """End-to-end: the exact command CI runs must produce a schema-valid,
    non-empty artifact covering every smoke section — including the
    scoreboard dependency-engine counters the artifact now carries."""
    import benchmarks.run as run

    path = tmp_path / "bench-smoke.json"
    # subprocess budget stays below the slow lane's --timeout=300 per-test
    # ceiling (ci.yml), so a hung benchmark fails through TimeoutExpired
    # with captured stderr instead of pytest-timeout killing the test
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", f"--json={path}"],
        cwd=REPO_ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=270,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(path.read_text())
    _validate_schema(payload,
                     expect_sections=_emitted_names(run.SMOKE_SECTIONS))
    assert payload["sections"] == list(run.SMOKE_SECTIONS)
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    # probe-vs-pairwise accounting (Table II honesty) and its gates. The
    # w64 crossover and the 2.0x-growth gate are emitted but asserted
    # with margin here: w64 wins by only ~1.5x under smoke-sized iters,
    # so a loaded CI runner could flip it with no code regression — the
    # w128 win (>2x margin) and a 3x growth ceiling (window x4) are the
    # noise-robust forms of the same claims.
    assert ("table2_depcheck", "scoreboard_beats_scan_w64") in metrics
    assert metrics[("table2_depcheck", "scoreboard_beats_scan_w128")] == 1
    assert metrics[("table2_depcheck", "scoreboard_growth_64_to_256")] < 3.0
    assert ("table2_depcheck", "w256_s10_scoreboard_ns") in metrics
    # the window=256 configuration through the real sim + dyn streams
    assert any(s == "fig29_window" and "w256" in m for s, m in metrics)
    assert ("fig29_window", "ant_w256_probes_per_insert") in metrics
    assert ("fig29_window", "instanas_w256_plan_us_per_task") in metrics
    # the device section rides in the same smoke run: its executor
    # equivalence and one-dispatch gates must hold on THIS host too
    _assert_device_gates(payload)


@pytest.mark.slow  # runs the real --window=256 smoke leg (~1-2 min)
def test_smoke_json_artifact_w256_leg(tmp_path):
    """The second CI bench command: every --window-consuming section must
    accept a 256-wide window and still emit a schema-valid artifact (the
    dependency-engine sections sweep window sizes internally and are
    covered by the first leg)."""
    path = tmp_path / "bench-smoke-w256.json"
    sections = ["device", "frontier", "serving"]
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", "--window=256",
         *sections, f"--json={path}"],
        cwd=REPO_ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=270,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(path.read_text())
    _validate_schema(payload, expect_sections=_emitted_names(sections))
    assert payload["flags"]["window"] == "256"


# Structural gates the committed device artifact must hold (1 = pass):
# every executor mode bit-identical to serial with ONE dispatch per
# stream, the ready-queue session draining the recurring workload with
# O(1) host syncs, and the forced-Pallas leg actually taking the fast
# path. No timing gates — walls and speedups are host-load-dependent and
# only warned on by benchmarks/compare.py.
DEVICE_GATES = {
    "device_sim_cheetah": ("device_wave_matches_serial",
                           "device_frontier_matches_serial",
                           "device_loop_matches_serial"),
    "device_dyn_routing": ("device_wave_matches_serial",
                           "device_frontier_matches_serial",
                           "device_loop_matches_serial"),
    "device_session_recurring": ("session_matches_serial",
                                 "loop_session_matches_serial",
                                 "loop_session_host_syncs_O1",
                                 "session_fewer_dispatches_than_per_stream"),
    "device_loop_pallas": ("interpreter_matches_serial", "pallas_used",
                           "pallas_matches_serial",
                           "pallas_matches_interpreter"),
}


def _assert_device_gates(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    for section, gates in DEVICE_GATES.items():
        for gate in gates:
            assert metrics.get((section, gate)) == 1, (
                f"device gate {section},{gate} failed: "
                f"{ {m: v for (s, m), v in metrics.items() if s == section} }")
    for leg in ("device_sim_cheetah", "device_dyn_routing"):
        for mode in ("wave", "frontier", "loop"):
            assert metrics[(leg, f"device_{mode}_dispatches")] == 1, (
                f"{leg} device_{mode} must advance the whole stream in ONE "
                f"dispatch, got {metrics[(leg, f'device_{mode}_dispatches')]}")
        assert metrics[(leg, "device_loop_executor")] in (
            "interpreter", "pallas")
    # the evidence behind the O(1) verdict, not just the bit
    assert metrics[("device_session_recurring", "loop_session_host_syncs")] <= 2
    assert metrics[("device_session_recurring",
                    "loop_session_loop_dispatches")] >= 1


def test_committed_bench_device_json():
    """The repo-root BENCH_device.json (regenerated by the CI device bench
    step) must stay schema-valid with every executor-equivalence and
    one-dispatch gate green."""
    path = os.path.join(REPO_ROOT, "BENCH_device.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=["device"])
    assert payload["sections"] == ["device"]
    assert payload["flags"].get("smoke") == "1"
    _assert_device_gates(payload)


def _assert_depcheck_gates(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    # noise-robust forms only (see test_smoke_json_artifact_real): the
    # w128/w256 wins carry >2x margin; growth gets a 3x ceiling.
    assert metrics[("table2_depcheck", "scoreboard_beats_scan_w128")] == 1
    assert metrics[("table2_depcheck", "scoreboard_beats_scan_w256")] == 1
    assert metrics[("table2_depcheck", "scoreboard_sublinear_64_to_256")] == 1
    assert metrics[("table2_depcheck", "scoreboard_growth_64_to_256")] < 3.0
    assert ("table2_depcheck", "w256_s10_scoreboard_ns") in metrics
    assert ("table2_depcheck", "w256_s10_probes_per_insert") in metrics


def test_committed_bench_depcheck_json():
    """The repo-root BENCH_depcheck.json must stay schema-valid with the
    dependency-engine scaling gates green."""
    path = os.path.join(REPO_ROOT, "BENCH_depcheck.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=["table2_depcheck"])
    assert payload["sections"] == ["depcheck"]
    assert payload["flags"].get("smoke") == "1"
    _assert_depcheck_gates(payload)


# Structural gates the committed frontier artifact must hold (1 = pass):
# no timing gates — speedups are host-load-dependent — only the plan-shape
# and overlap-structure claims: syncs << dispatches (§II-D), more than one
# group genuinely in flight, and frontier plans at least as dense as waves.
FRONTIER_COMPARE_GATES = ("frontier_fewer_syncs_than_dispatches",
                          "frontier_overlap_used")


def _assert_frontier_gates(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    for section in ("frontier_sim_cheetah", "frontier_dyn_dynamic_routing"):
        for gate in FRONTIER_COMPARE_GATES:
            assert metrics.get((section, gate)) == 1, (
                f"frontier gate {section},{gate} failed: "
                f"{ {m: v for (s, m), v in metrics.items() if s == section} }")
        # the evidence behind the verdicts
        assert metrics[(section, "frontier_blocking_syncs")] * 4 <= \
            metrics[(section, "frontier_dispatches")]
        assert metrics[(section, "frontier_max_inflight_groups")] > 1
        assert (section, "frontier_vs_best_barrier") in metrics
    assert metrics.get(
        ("frontier_sim_cheetah", "frontier_density_beats_wave")) == 1
    assert ("frontier_sim_cheetah", "frontier_plan_active_fraction") in metrics
    assert ("frontier_sim_cheetah", "wave_plan_active_fraction") in metrics


def test_committed_bench_frontier_json():
    """The repo-root BENCH_frontier.json (regenerated by the CI bench-smoke
    step) must stay schema-valid with the sync-overhead and plan-density
    gates green."""
    path = os.path.join(REPO_ROOT, "BENCH_frontier.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=["frontier"])
    assert payload["sections"] == ["frontier"]
    assert payload["flags"].get("smoke") == "1"
    _assert_frontier_gates(payload)


# Structural gates the committed serving artifact must hold: the live
# session beats continuous batching on p95, and the mesh-sharded window
# leg (DESIGN §12) sustains >=2.5x single-window capacity at equal-or-
# better tail latency, with the win attributable to retrace elimination.
# The d2d and overlap gates pin the transfer layer: the device-to-device
# path bit-identical to serial/staged with zero mesh-transfer host syncs
# and a mode-invariant byte audit, and the overlapped drain pump at
# sequential-or-better capacity while genuinely overlapping shards.
MESH_GATES = ("mesh_n4_beats_single_2p5x", "mesh_n4_p95_within_single",
              "mesh_n4_fewer_compiles",
              "mesh_d2d_matches_serial", "mesh_d2d_matches_staged",
              "mesh_d2d_transfer_host_syncs_O1",
              "mesh_d2d_bytes_matches_staged",
              "mesh_overlap_capacity_within_sequential",
              "mesh_overlap_p95_within_sequential",
              "mesh_overlap_drains_used")


def _assert_serving_gates(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    assert metrics.get(("serving", "session_beats_batch_p95")) == 1, (
        f"serving p95 gate failed: "
        f"{ {m: v for (s, m), v in metrics.items() if s == 'serving'} }")
    for gate in MESH_GATES:
        assert metrics.get(("mesh_scaling", gate)) == 1, (
            f"mesh gate {gate!r} failed: "
            f"{ {m: v for (s, m), v in metrics.items() if s == 'mesh_scaling'} }")
    # the evidence behind the verdicts: capacity ratio, cross-device edge
    # count, and per-shard host-sync accounting must all be carried
    assert metrics[("mesh_scaling", "mesh_n4_capacity_ratio")] >= 2.5
    assert ("mesh_scaling", "cross_shard_edges") in metrics
    assert ("mesh_scaling", "sub_epoch_barriers") in metrics
    assert metrics[("mesh_scaling", "n_devices")] >= 1
    for i in range(4):
        assert ("mesh_scaling", f"shard{i}_host_syncs") in metrics
        assert ("mesh_scaling", f"shard{i}_compiled_programs") in metrics
    # the transfer-layer evidence: the serving leg's link must have
    # selected d2d on forced host devices, the overlapped pump must have
    # had >1 shard in flight, and the d2d differential must carry its
    # host-sync and byte columns (the staged control shows the nonzero
    # sync count d2d eliminates)
    assert metrics[("mesh_scaling", "transfer_mode")] == "d2d"
    assert metrics[("mesh_scaling", "drain_overlap")] > 1
    assert metrics[("mesh_scaling", "d2d_mesh_transfer_host_syncs")] == 0
    assert metrics[("mesh_scaling", "staged_mesh_transfer_host_syncs")] > 0
    assert metrics[("mesh_scaling", "d2d_transfer_bytes")] == \
        metrics[("mesh_scaling", "staged_transfer_bytes")] > 0
    assert metrics[("mesh_scaling", "d2d_moves")] > 0


def test_committed_bench_serving_json():
    """The repo-root BENCH_serving.json (regenerated by the CI multi-device
    lane under forced host devices) must stay schema-valid with the
    serving-p95 and mesh-scaling gates green."""
    path = os.path.join(REPO_ROOT, "BENCH_serving.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=["serving", "mesh_scaling"])
    assert payload["sections"] == ["serving", "mesh_scaling"]
    assert payload["flags"].get("smoke") == "1"
    _assert_serving_gates(payload)


def _assert_window_size_metrics(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    # the full window sweep must be present for the smoke env/net pair,
    # with the scoreboard evidence columns (probes vs budgeted checks)
    from benchmarks.bench_window_size import WINDOWS

    for name in ("ant", "instanas"):
        for w in WINDOWS:
            for col in ("plan_us_per_task", "probes_per_insert",
                        "checks_per_insert"):
                assert ("fig29_window", f"{name}_w{w}_{col}") in metrics, (
                    f"missing fig29_window,{name}_w{w}_{col}")
        assert ("fig29_window", f"{name}_w256_pairwise_us_per_task") in metrics
    assert ("fig29_window", "sim_mean_gain") in metrics
    assert ("fig29_window", "sim_mean_gain_w256") in metrics


def test_committed_bench_window_size_json():
    """The repo-root BENCH_window_size.json must stay schema-valid and
    keep carrying the large-window scoreboard evidence columns."""
    path = os.path.join(REPO_ROOT, "BENCH_window_size.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=_emitted_names(["window_size"]))
    assert payload["sections"] == ["window_size"]
    assert payload["flags"].get("smoke") == "1"
    _assert_window_size_metrics(payload)


# Structural gates the committed QoS artifact must hold (DESIGN §13): the
# preempting plane keeps the interactive-class tail within 2x the unloaded
# floor at no aggregate-throughput cost, preemption never changes a token,
# aging un-starves a flooded-out low-priority tenant, priority buckets
# reorder the window's READY head, and the mixed-priority hazard stream
# stays bit-identical through the loop lowering and the mesh session.
QOS_GATES = ("qos_high_p99_within_2x_unloaded",
             "qos_throughput_within_fairness",
             "qos_tokens_matches_fairness",
             "qos_aging_beats_flood_drain",
             "qos_priority_beats_fifo",
             "qos_loop_matches_serial",
             "qos_mesh_matches_serial")


def _assert_qos_gates(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    for gate in QOS_GATES:
        assert metrics.get(("qos", gate)) == 1, (
            f"qos gate {gate!r} failed: "
            f"{ {m: v for (s, m), v in metrics.items() if s == 'qos'} }")
    # the evidence behind the verdicts: pooled per-class tails, the paired
    # median ratios the timing gates judge, and a real preemption count
    for col in ("unloaded_high_p99_ms", "fairness_high_p99_ms",
                "fairness_high_p99_9_ms", "qos_high_p99_ms",
                "qos_high_p99_9_ms", "qos_high_p99_vs_unloaded_median_ratio",
                "qos_vs_fairness_tokens_median_ratio"):
        assert ("qos", col) in metrics, f"missing qos,{col}"
    assert metrics[("qos", "qos_preemptions")] >= 1
    assert metrics[("qos", "n_devices")] >= 1


def test_committed_bench_qos_json():
    """The repo-root BENCH_qos.json (regenerated by the CI multi-device
    lane under forced host devices) must stay schema-valid with every
    QoS-plane gate green."""
    path = os.path.join(REPO_ROOT, "BENCH_qos.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=["qos"])
    assert payload["sections"] == ["qos"]
    assert payload["flags"].get("smoke") == "1"
    _assert_qos_gates(payload)


# -- benchmarks/compare.py: the committed-vs-fresh trajectory driver -------

def _payload(rows):
    return {"flags": {"smoke": "1"}, "sections": ["s"],
            "timings_seconds": {"s": 0.1},
            "results": [{"section": "s", "metric": m, "value": v}
                        for m, v in rows]}


def test_compare_gate_regression_fails():
    from benchmarks.compare import compare_payloads

    committed = _payload([("loop_matches_serial", 1), ("wall_s", 1.0)])
    fresh = _payload([("loop_matches_serial", 0), ("wall_s", 1.0)])
    failures, warnings, infos = compare_payloads(committed, fresh)
    assert any("gate regressed" in f for f in failures)
    assert not warnings


def test_compare_missing_metric_fails():
    from benchmarks.compare import compare_payloads

    committed = _payload([("dispatches", 1), ("wall_s", 1.0)])
    fresh = _payload([("wall_s", 1.0)])
    failures, _, _ = compare_payloads(committed, fresh)
    assert any("missing from fresh run" in f for f in failures)


def test_compare_numeric_drift_warns_not_fails():
    from benchmarks.compare import compare_payloads

    committed = _payload([("wall_s", 1.0), ("dispatches", 4)])
    fresh = _payload([("wall_s", 10.0), ("dispatches", 4)])
    failures, warnings, _ = compare_payloads(committed, fresh, rtol=0.5)
    assert not failures
    assert any("numeric drift" in w for w in warnings)
    # within tolerance -> clean
    failures, warnings, _ = compare_payloads(
        _payload([("wall_s", 1.0)]), _payload([("wall_s", 1.2)]), rtol=0.5)
    assert not failures and not warnings


def test_compare_gate_detection_is_name_based():
    """A counter that happens to equal 1 (host_syncs) is numeric, never a
    gate: 1 -> 0 on it must not fail; a new metric and a 0 -> 1 gate flip
    are info."""
    from benchmarks.compare import compare_payloads, is_gate

    assert is_gate("loop_matches_serial", 1)
    assert is_gate("pallas_used", 0)
    assert not is_gate("host_syncs", 1)
    assert not is_gate("active_fraction", 1.0)
    committed = _payload([("host_syncs", 1), ("beats_scan", 0)])
    fresh = _payload([("host_syncs", 0), ("beats_scan", 1),
                      ("new_col", 7)])
    failures, warnings, infos = compare_payloads(committed, fresh)
    assert not failures
    # host_syncs 1 -> 0 is numeric drift (a warning), never a gate failure
    assert warnings == [
        "numeric drift beyond rtol=0.5: s,host_syncs committed=1 fresh=0"]
    assert any("gate improved" in i for i in infos)
    assert any("new metric" in i for i in infos)


def test_compare_main_exit_codes(tmp_path):
    from benchmarks import compare

    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(_payload([("x_matches_serial", 1)])))
    b.write_text(json.dumps(_payload([("x_matches_serial", 1)])))
    assert compare.main([str(a), str(b)]) == 0
    b.write_text(json.dumps(_payload([("x_matches_serial", 0)])))
    assert compare.main([str(a), str(b), "--rtol=0.9"]) == 1
    with pytest.raises(SystemExit, match="usage"):
        compare.main([str(a)])
    with pytest.raises(SystemExit, match="unknown flag"):
        compare.main([str(a), str(b), "--bogus"])


# The lifetime gates the soak section must hold (1 = pass); asserted both
# on the committed artifact and on the live slow-lane run.
SOAK_GATES = ("slab_flat", "plan_cache_bounded", "rows_recycled", "compacted",
              "rss_bounded", "p95_stable", "bookkeeping_bounded",
              "matches_serial", "counterfactual_grows")


def _assert_soak_gates(payload):
    metrics = {(r["section"], r["metric"]): r["value"]
               for r in payload["results"]}
    for gate in SOAK_GATES:
        assert metrics.get(("soak", gate)) == 1, (
            f"soak gate {gate!r} failed: "
            f"{ {m: v for (s, m), v in metrics.items() if s == 'soak'} }")
    # the artifact carries the evidence, not just the verdicts
    assert ("soak", "slab_bytes_per_phase") in metrics
    assert ("soak", "counterfactual_slab_bytes_per_phase") in metrics
    assert metrics[("soak", "arena_recycled_rows")] > 0
    assert metrics[("soak", "arena_compactions")] >= 1


def test_committed_bench_soak_json():
    """The repo-root BENCH_soak.json (regenerated by the CI soak step) must
    stay schema-valid with every lifetime gate green — committing an
    artifact with a failed gate is committing a known leak."""
    path = os.path.join(REPO_ROOT, "BENCH_soak.json")
    with open(path) as fh:
        payload = json.load(fh)
    _validate_schema(payload, expect_sections=["soak"])
    assert payload["sections"] == ["soak"]
    assert payload["flags"].get("smoke") == "1"
    _assert_soak_gates(payload)


@pytest.mark.slow  # runs the real soak smoke leg (~30s)
def test_smoke_soak_json_artifact_real(tmp_path):
    """End-to-end: the exact CI soak command must produce a schema-valid
    artifact with every lifetime gate green on THIS host."""
    path = tmp_path / "bench-soak.json"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--smoke", "soak",
         f"--json={path}"],
        cwd=REPO_ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=270,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    payload = json.loads(path.read_text())
    _validate_schema(payload, expect_sections=["soak"])
    _assert_soak_gates(payload)
