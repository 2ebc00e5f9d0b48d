"""Spreads of a cell's runs, as the bounds in ``BENCHMARK.json`` are set.

    python3 bench/bounds.py <set-a files...> -- <set-b files...>

Each file holds a run's output; its last line is the result. For every
metric it prints each set's median and spread (interquartile distance
over the median, by ``statistics.quantiles``), the wider spread, and
five times it: the bound, before the 1% floor and the 25% cap; and the
mean of the two sets' spreads without each set's run farthest from its
median, which a bound has to stay above twice of.
"""

from __future__ import annotations

import json
import pathlib
import statistics
import sys
from typing import Dict, List

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from bench.common import spread  # noqa: E402


def _metrics(paths: List[str]) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = {}
    for p in paths:
        with open(p) as f:
            result = json.loads(f.read().strip().splitlines()[-1])
        for name, m in result["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def _trimmed(values: List[float]) -> List[float]:
    """The set without its run farthest from the median."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return values[:far] + values[far + 1:]


def main(argv: List[str]) -> int:
    if "--" not in argv:
        print(__doc__, file=sys.stderr)
        return 2
    cut = argv.index("--")
    sets = [_metrics(argv[:cut]), _metrics(argv[cut + 1:])]
    for name in sorted(sets[0]):
        a, b = sets[0][name], sets[1].get(name, [])
        sa, sb = spread(a), spread(b)
        print(json.dumps({"metric": name, "median_a": statistics.median(a),
                          "median_b": statistics.median(b), "spread_a": sa,
                          "spread_b": sb, "five_times_wider": 5 * max(sa, sb),
                          "mean_trimmed_spread": (spread(_trimmed(a))
                                                  + spread(_trimmed(b))) / 2}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
