"""Spreads of two sets of runs of one cell, and the bound they support.

    python3 bench/bounds.py set1.jsonl set2.jsonl

Each file holds the result lines of one set (one JSON object per line,
as ``run.py`` prints them last).  For each metric it prints both sets'
medians and spreads (interquartile distance over the median, with
``statistics.quantiles``), and five times the wider spread, kept
between 1% and 25%: the bound that the benchmark's rules ask for.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def load(path: str) -> list:
    return [json.loads(line) for line in Path(path).read_text().splitlines()
            if line.strip().startswith("{")]


def main(argv) -> int:
    from bench import stats
    sets = [load(p) for p in argv]
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    for m in names:
        vals = [[r["metrics"][m]["value"] for r in runs if m in r["metrics"]]
                for runs in sets]
        spreads = [stats.spread(v) for v in vals if len(v) >= 2]
        widest = max(spreads) if spreads else float("nan")
        print(json.dumps({
            "metric": m,
            "medians": [statistics.median(v) for v in vals if v],
            "spreads": spreads, "widest": widest,
            "bound": min(0.25, max(0.01, 5 * widest))}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
