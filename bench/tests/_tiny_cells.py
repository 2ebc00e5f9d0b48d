"""A copy of the benchmark with cells added as data files only, at sizes a
CPU test can run: the tests drive the harness through it in its dry mode.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  head_dim=16, d_ff=128, vocab=256)


def _dump(obj, path: pathlib.Path) -> None:
    path.write_text(json.dumps(obj, indent=1) + "\n")


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    """Copy ``bench/`` and ``BENCHMARK.json`` into ``tmp`` and add three
    cells (closed-loop serving of a two-layer decoder, once as configured
    and once with sliding-window attention and an untied head; a 64-env
    rollout) by adding files and entries only."""
    shutil.copytree(BENCH, tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((BENCH / "configs" / "h2o-danube-3-4b.json").read_text())
    conf["name"] = conf["model"]["name"] = "tiny-danube"
    conf["model"].update(TINY_MODEL)
    conf["server"].update(max_slots=4, max_len=64)
    _dump(conf, tmp / "bench" / "configs" / "tiny-danube.json")
    conf["name"] = conf["model"]["name"] = "tiny-danube-local"
    conf["model"].update(pattern_unit=["attn_local"], window=8,
                         tied_embeddings=False)
    _dump(conf, tmp / "bench" / "configs" / "tiny-danube-local.json")
    sim = json.loads((BENCH / "configs" / "cheetah-2048.json").read_text())
    sim["name"] = "tiny-cheetah"
    sim["n_envs"] = 64
    _dump(sim, tmp / "bench" / "configs" / "tiny-cheetah.json")
    buckets = [[8, 0.5], [16, 0.5]]
    _dump({"kind": "closed", "clients": 4, "pool": 64, "prompt_buckets": buckets,
           "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 12},
           "check_requests": 4},
          tmp / "bench" / "traffic" / "tiny-closed.json")
    _dump({"kind": "rollout", "group_size": 8, "warmup_steps": 2},
          tmp / "bench" / "traffic" / "tiny-rollout.json")
    spec["configs"] += [
        {"name": "tiny-danube", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/tiny-danube.json"},
        {"name": "tiny-danube-local", "source": "test", "reduced": [],
         "why": "test", "file": "bench/configs/tiny-danube-local.json"},
        {"name": "tiny-cheetah", "source": "test", "reduced": [], "why": "test",
         "file": "bench/configs/tiny-cheetah.json"}]
    # name: (configuration, mix, the committed cell whose metrics it reports)
    cells = {"tiny-closed": ("tiny-danube", "tiny-closed",
                             "danube-decode-saturated"),
             "tiny-closed-local": ("tiny-danube-local", "tiny-closed",
                                   "danube-decode-saturated"),
             "tiny-rollout": ("tiny-cheetah", "tiny-rollout",
                              "cheetah-g32-rollout")}
    for name, (config, mix, like) in cells.items():
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": mix, "chips": 1, "why": "test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
    _dump(spec, tmp / "BENCHMARK.json")
    return tmp


def dry_run(root: pathlib.Path, workload: str, seed: int, capsys,
            seconds: float = 2.0, control: bool = False):
    """Run a cell in the harness's dry mode (with ``control``, the check's
    control in the program's place); returns (exit code, the last stdout
    line parsed, stderr)."""
    from bench import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0"],
                  root=root, dry=True, control=control)
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err
