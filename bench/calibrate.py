"""Readings for the correctness limits: for each seed, one short run of
a cell at its own sizes and load, with the numbers the check compares
for the program and, on the same requests, for the fp8 control, which
the same limits judge (it has to come out not correct).

    python3 bench/calibrate.py --workload <cell> --seconds 20 --control 3 \
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12

All seeds run in one process, so the programs compile once.  Prints one
JSON line per seed and a summary line last.  The benchmark's own runs
never run the control.
"""

from __future__ import annotations

import time

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# the chip's runtime logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds, the first, also read the "
                         "control")
    args = ap.parse_args(argv)
    from bench import harness
    rows = []
    for i, seed in enumerate(args.seeds):
        line = harness.run_cell(args.workload, seed, args.seconds, False,
                                t_start=time.perf_counter(),
                                control=i < args.control)
        row = {"seed": seed, "correct": line["correct"],
               **{k: c["value"] for k, c in line["checks"].items()},
               "readings": line["readings"],
               "control": line.get("control"),
               "attempted": line["attempted"], "failed": line["failed"],
               "setup_s": line["metrics"]["setup_s"]["value"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows),
               "all_correct": all(r["correct"] for r in rows)}
    for name in harness.load_cell(args.workload).limits:
        summary[f"program_max.{name}"] = max(r[name] for r in rows)
        ctl = [r["control"][name] for r in rows if r["control"]]
        if ctl:
            summary[f"control_min.{name}"] = min(ctl)
    ctl = [r["control"]["correct"] for r in rows if r["control"]]
    if ctl:
        summary["control_ever_correct"] = any(ctl)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
