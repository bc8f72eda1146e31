"""The correctness control of a cell, and the program's readings beside it.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, in one process: the cell's set-up, a short window at the
cell's own load, then the compared numbers twice: the program against the
float32 HIGHEST reference (the lower reading), and the same reference one
precision step lower, three bf16 passes per matmul, put in the program's
place (the control; the upper reading). One JSON line per seed. The
benchmark's own runs never run this; the limits in ``workloads/<cell>.json``
were set from its readings (``PERF.md``). Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench import run
    run.prepare()
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             control=True,
                             log=lambda s: print(s, file=sys.stderr))
        except run.NoDevice as e:
            print(f"bench: {e}", file=sys.stderr)
            return 1
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "program": {k: v["value"] for k, v in r["checks"].items()},
            "control": {k: run._number(v["value"])
                        for k, v in r["control"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
