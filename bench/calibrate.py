"""Runs that set the benchmark's limits, each as ``bench/run.py`` makes it.

    python3 bench/calibrate.py --control <run.py arguments>

``--control`` puts the check's control, the reference computed in the
precision below the configuration's, in the program's place: ``correct``
is decided from the control's reading, which has to come out false, and
the program's own reading goes into ``notes``. The two readings are the
two ends that each limit is set between.

Run it once per seed, each in its own process.
"""

from __future__ import annotations

import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from bench import run  # noqa: E402


def main(argv) -> int:
    control = bool(argv) and argv[0] == "--control"
    return run.main(argv[1:] if control else argv, control=control)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
