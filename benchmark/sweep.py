#!/usr/bin/env python3
"""One point of a rate sweep: a cell's stream offered at a stated rate.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --seconds <s> --rate <rows/s>

Cells offer load at a rate fixed in their files; this is how the rate was
found (PERF.md): the configuration's ``desk.sustained_rows_per_s`` is the
highest of a few fixed rates at which ``dialogues_per_s`` still equals the
offered rate and the latency tail does not grow over the run.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402

if __name__ == "__main__":
    i = sys.argv.index("--rate")
    rate = float(sys.argv[i + 1])
    sys.exit(run.main(sys.argv[1:i] + sys.argv[i + 2:], offered_rate=rate))
