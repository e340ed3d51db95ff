#!/usr/bin/env python3
"""Run a cell with its control in the program's place: ``correct`` must
come out false.

    python3 bench/control.py --control bf16 --workload rmat-ic-wc.build --seed 3 --seconds 10 --trace 0

Controls (PERF.md gives what each read on the chip):

* ``bf16`` — build cells: the program samples with the edge
  probabilities rounded to bfloat16, the precision below the float32 that
  the configuration states; the reference keeps float32;
* ``cap<N>`` — build cells: the program runs with level cap N (the
  program's default is 64), which cuts deep RRR sets short;

Otherwise the same run as ``bench/run.py``, on the chip.
"""
from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--control" not in argv:
        print("control: --control bf16|cap<N> is required",
              file=sys.stderr)
        return 2
    i = argv.index("--control")
    control = argv[i + 1]
    del argv[i:i + 2]
    from bench import run
    return run.main(argv, control=control)


if __name__ == "__main__":
    sys.exit(main())
