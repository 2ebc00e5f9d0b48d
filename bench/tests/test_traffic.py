"""The traffic generator repeats exactly from its seed, and every seed
offers the same work in another order."""

from __future__ import annotations

import json

import numpy as np
import pytest

from _tiny_cells import BENCH

from bench import traffic

#: A closed mix beside the committed ones: the code-completion shape
#: (prompts 256-2048, outputs median 13).
CODE = {"kind": "closed", "clients": 16, "pool": 400,
        "prompt_buckets": [[256, 0.15], [512, 0.25], [1024, 0.35],
                           [2048, 0.25]],
        "output": {"median": 13, "sigma": 0.8, "min": 2, "max": 128}}
MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json")
               if json.loads(p.read_text())["kind"] in traffic.SERVING_KINDS
               ) + ["code"]


def _mix(name):
    if name == "code":
        return CODE
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_mix_repeats_from_its_seed(name):
    mix = _mix(name)
    a = traffic.serving_requests(mix, 2**31 + 7, 32000)
    b = traffic.serving_requests(mix, 2**31 + 7, 32000)
    assert len(a) == len(b) == mix["pool"]
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert x.max_new == y.max_new


@pytest.mark.parametrize("name", MIXES)
def test_seeds_offer_the_same_work_in_another_order(name):
    mix = _mix(name)
    a = traffic.serving_requests(mix, 1, 32000)
    b = traffic.serving_requests(mix, 2, 32000)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_sizes_follow_the_mix(name):
    mix = _mix(name)
    reqs = traffic.serving_requests(mix, 3, 32000)
    n = len(reqs)
    lens = [len(r.prompt) for r in reqs]
    for length, weight in mix["prompt_buckets"]:
        assert abs(lens.count(length) - weight * n) <= 1
    outs = [r.max_new for r in reqs]
    assert min(outs) >= mix["output"]["min"]
    assert max(outs) <= mix["output"]["max"]
    assert abs(np.median(outs) - mix["output"]["median"]) <= 1
    assert all(0 <= t < 32000 for r in reqs for t in r.prompt)
