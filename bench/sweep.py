"""Find a cell's knee: the highest offered rate at which the admit queue
does not grow over the window.  One process sets the cell up once and
measures one window per rate.

    python3 bench/sweep.py --workload <cell> --seconds 30 --rates 0.5 1 1.5

For each rate it prints the end-to-end numbers and the mean admit-queue
depth over the first and the last third of the window.  The cell's mix
then fixes its rate at about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    from bench import config, harness, traffic, weights
    t_start = time.perf_counter()
    cell = harness.load_cell(args.workload)
    harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    s = config.shapes_from(cell.raw)
    srv = harness.build_server(s, cell.mix, weights.make(s, args.seed))
    # one multiset of work for every rate, so every program is warmed by
    # the first: the highest rate's traffic, its arrivals spread out
    top = max(args.rates)
    base = traffic.generate(dict(cell.mix, rate_per_s=top), args.seed,
                            s.vocab, args.seconds)
    runner = harness.Runner(cell, s, base, srv, args.seed)
    starts = [it.start_s for it in base.items]
    for i, rate in enumerate(args.rates):
        for it, st in zip(base.items, starts):
            it.start_s = st * top / rate
        if i == 0:
            runner.warm()
        else:
            srv.engine.flush_prefix_cache()
            srv.engine.reset_history()
            if base.system_tokens and cell.mix["tenants"].get("warm"):
                runner._park_system_prompts()
        rec = runner.window(args.seconds)
        e2e = harness.end_to_end(rec, time.perf_counter() - t_start)
        steps = [st for st in rec["steps"] if st.t1 <= rec["end"]]
        third = max(1, len(steps) // 3)
        q = [st.queue for st in steps]
        sent = [r for r in rec["recs"] if rec["t0"] <= r.sched < rec["end"]]
        print(json.dumps({
            "rate": rate, **{k: v for k, v in e2e.items() if k != "setup_s"},
            "queue_first_third": sum(q[:third]) / third,
            "queue_last_third": sum(q[-third:]) / third,
            "sent": len(sent), "failed": sum(r.failed for r in sent),
            "kv_peak_pages": rec["kv_peak_pages"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
