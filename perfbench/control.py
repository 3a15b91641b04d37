"""Readings that set the limit of a cell's correctness check.

    python3 perfbench/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3 --control-seeds 4,5,6

In one process: for each of ``--seeds``, one run of the cell as
``run.py`` makes it (set-up, the window at the cell's own load, then
the check); for each of ``--control-seeds``, the same run with the
configuration's ``control`` in the program's place, judged by the same
check. One JSON line per run. The program's readings over a dozen seeds
give the limit's lower end; the control's, its upper end, and the
control's runs have to come out not correct (``checks/<cell>.json``).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from perfbench import run as run_mod  # noqa: E402
from perfbench import spec  # noqa: E402


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), args.workload)
    runs = ([(s, False) for s in _seeds(args.seeds)]
            + [(s, True) for s in _seeds(args.control_seeds)])
    for seed, control in runs:
        res = run_mod.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), control=control)
        print(json.dumps({"seed": seed, "control": control,
                          "correct": res["correct"],
                          "checks": res["checks"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
