#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload delaunay_n17.offline \\
        --seconds 1 --seeds 1,2,3 --mode control

Runs the cell once per seed, in this one process (programs compile or
load once),
each run as ``bench/run.py`` makes it, and prints one JSON line per seed
with the numbers the reference compared. ``--mode sound`` runs the
program as it is: its largest reading over a dozen seeds or more is the
limit's lower reading. ``--mode control`` (``bench/lib/faults.py``
``no_refine``) and the other faults (``half_batch``, ``half_finest``,
``unrefined_finest``, ``altered``) run the program with the guarantee
broken: the control's smallest reading is the upper one, and the
faults' readings show which number catches each. The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]    # the faults patch the program

from bench import run as R          # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", default="sound",
                    choices=("sound", "control", "half_batch", "altered",
                             "half_finest", "unrefined_finest"))
    args = ap.parse_args(argv)
    from bench.lib import faults
    if args.mode == "sound":
        planted = contextlib.nullcontext
    else:
        planted = faults.FAULTS["no_refine" if args.mode == "control"
                                else args.mode]
    for seed in (int(s) for s in args.seeds.split(",")):
        out = io.StringIO()
        with planted(), contextlib.redirect_stdout(out):
            R.main(["--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seconds), "--trace", "0"])
        res = json.loads(out.getvalue().strip().splitlines()[-1])
        print(json.dumps({"mode": args.mode, "workload": args.workload,
                          "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          "check": res["check"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
