"""Run one cell of the benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell that ``BENCHMARK.json`` names, makes its weights and
traffic from ``--seed``, warms every program shape the traffic reaches
(set-up), measures for ``--seconds``, drains, compares what was served
with the plain reference, and prints one JSON line last on stdout.
With ``--trace 0`` the line holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of part
of the window.  With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the chip's runtime logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_logs"))
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        line = harness.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"bench: {e}; refusing to run elsewhere", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
