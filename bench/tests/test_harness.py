"""The harness: cells added as data files run to their last line (in the
dry mode, which is no measurement), the timed path refuses the CPU, and a
checkout without the program prints no result."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys

import pytest

from _tiny_cells import BENCH, ROOT, dry_run, make_root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench-root"))


@pytest.mark.parametrize("workload,metric", [
    ("tiny-closed", "output_tokens_per_s"),
    ("tiny-closed-local", "output_tokens_per_s"),
    ("tiny-rollout", "env_steps_per_s"),
])
def test_cell_added_as_data_runs_to_its_last_line(tiny_root, capsys,
                                                  workload, metric):
    rc, result, err = dry_run(tiny_root, workload, 2**31 + 12345, capsys)
    assert rc == 0, err
    assert result["dry_run"].startswith("CPU dry run")
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {metric, "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "checks"
    last_err = err.strip().splitlines()
    assert last_err[-1] == "correct: True"
    for name, check in result["checks"].items():
        assert f"check {name}: " in err


def test_timed_path_refuses_the_cpu(capsys):
    from bench import run

    rc = run.main(["--workload", "danube-decode-saturated", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no TPU" in err


def test_unknown_workload_is_refused(capsys):
    from bench import run

    rc = run.main(["--workload", "no-such-cell", "--seed", "1",
                   "--seconds", "1", "--trace", "0"], dry=True)
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "unknown workload" in err


def test_checkout_without_the_program_prints_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cmd = spec["command"] + ["--workload", spec["workloads"][0]["name"],
                             "--seed", "5", "--seconds", "1", "--trace", "0"]
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(tmp_path)}
    proc = subprocess.run([sys.executable] + cmd[1:], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_calibrate_passes_rate_and_control_to_the_run(monkeypatch):
    from bench import calibrate, run

    seen = {}
    monkeypatch.setattr(run, "main", lambda argv, **kw: seen.update(argv=argv, **kw) or 0)
    assert calibrate.main(["--control", "--workload", "w",
                           "--seed", "1", "--seconds", "3"]) == 0
    assert seen == {"argv": ["--workload", "w", "--seed", "1", "--seconds", "3"],
                    "control": True}
