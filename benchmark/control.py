#!/usr/bin/env python3
"""The control: a cell run one precision below what its configuration states.

    python3 benchmark/control.py --workload <cell> --seeds <n,n,..> --seconds <s> [--sound]

It is ``run.py``'s own ``run_cell``, for each seed in turn in one process
(set-up is long and the programs stay loaded). The explainer is served
through the lower precision of its family (``build`` of
``explainers/<model_type>.py`` given ``weights`` "int8": the program's own
weight-only int8 path where the configuration states bfloat16), so the tokens
compared are the control's; the classifier's reference, computed in bfloat16
where the configuration states float32, is put in the program's place. All
of it goes through the same ``check.verdict`` with the same limits, the one
a configuration states for itself among them, and every result line carries
``"control": {"correct": false, ..}``.

``--sound`` runs the program as the configuration states instead: several
seeds' readings of a sound run for the price of one set-up. The benchmark's
runs never come here; the limits in ``check.py``, and the one a
configuration states, were set between the two kinds of reading (PERF.md
section 6).
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sound", action="store_true")
    args = ap.parse_args(argv)
    spec, cell, cfg, mix = run.open_cell(args.workload)
    if not args.sound:
        cfg["desk"]["explain"]["weights"] = "int8"
    for seed in (int(s) for s in args.seeds.split(",")):
        run.report(run.run_cell(
            spec, cell, cfg, mix, seed=seed, seconds=args.seconds,
            trace=False, t_start=time.time(), control=not args.sound,
            scratch=os.environ.get("TMPDIR") or None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
