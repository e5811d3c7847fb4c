"""Reduction of a profiler trace to the numbers the per-layer readers
need, and the helpers they share.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
The traced window is the host span ``bench.traced`` that the harness
opens right after the trace starts and closes right before it stops,
between two engine steps.  Inside it, on each device plane:

* busy time: the union of the intervals of the ops on the ``XLA Ops``
  line, clipped to the window (a ``while`` op spans the ops of its
  body; the top ops and the kernel time count leaf ops only);
* per-module time: events of the ``XLA Modules`` line, by name without
  the program id (``jit_paged_decode_step``, ``jit_paged_prefill``,
  ``jit_paged_prefill_at``, ...);
* kernel time: ops whose HLO instruction is the paged-attention Pallas
  kernel (``%paged_attention_pallas.N = ... custom-call(...)``);
* idle gaps: the stretches between busy intervals, each labelled by the
  innermost of the program's ``serve.*`` host spans around its middle,
  or where there is none, the innermost of the benchmark's ``bench.*``.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the paged-attention Pallas kernel's HLO instruction name
KERNEL = re.compile(r"^paged_attention")
TOP = 10


def _base(name: str) -> str:
    """Module name without its program id: ``jit_f(12)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name).strip()


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Tuple[int, int]], lo: int, hi: int):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _instruction(name: str) -> str:
    """An op event's HLO instruction name: ``%fusion.3 = bf16[..] ...``
    -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def load(path) -> "object":
    import jax
    files = sorted(glob.glob(str(Path(path) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return jax.profiler.ProfileData.from_file(files[-1])


def reduce_profile(pd) -> dict:
    """The numbers of one trace; see the module docstring."""
    host_spans: List[Tuple[int, int, str]] = []
    window: Optional[Tuple[int, int]] = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "serve.")):
                    a = int(ev.start_ns)
                    b = a + int(ev.duration_ns)
                    if ev.name == WINDOW_SPAN:
                        window = (a, b)
                    else:
                        host_spans.append((a, b, ev.name))
    if window is None:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo, hi = window
    window_s = (hi - lo) / 1e9
    modules: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"count": 0, "seconds": 0.0})
    ops: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    kernel_s, kernel_calls = 0.0, 0
    gaps: List[Tuple[float, str]] = []
    n_dev = 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        evs = sorted(((int(ev.start_ns), int(ev.start_ns) + int(ev.duration_ns),
                       _instruction(ev.name)) for ev in lines[OPS_LINE].events),
                     key=lambda e: (e[0], e[0] - e[1]))
        iv = []
        for i, (a, b, instr) in enumerate(evs):
            if b <= lo or a >= hi:
                continue
            iv.append((a, b))
            if i + 1 < len(evs) and evs[i + 1][1] <= b:
                continue       # a loop or call around later ops: not a leaf
            ops[instr] += (b - a) / 1e9
            if KERNEL.search(instr):
                kernel_s += (min(b, hi) - max(a, lo)) / 1e9
                kernel_calls += 1
        busy = _clip(_union(iv), lo, hi)
        busy_total += sum(b - a for a, b in busy) / 1e9
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                a = int(ev.start_ns)
                b = a + int(ev.duration_ns)
                if b <= lo or a >= hi:
                    continue
                m = modules[_base(ev.name)]
                m["count"] += 1
                m["seconds"] += (b - a) / 1e9
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) / 1e9, _label(host_spans, (a + b) // 2)))
    gaps.sort(reverse=True)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": busy_total / n_dev if n_dev else 0.0,
        "devices": n_dev,
        "modules": dict(modules),
        "kernel_s": kernel_s / n_dev if n_dev else 0.0,
        "kernel_calls": kernel_calls,
        "top_ops": [[name, secs] for name, secs in top_ops],
        "idle_gaps": [[label, secs] for secs, label in gaps[:TOP]],
    }


def _label(spans: List[Tuple[int, int, str]], t: int) -> str:
    """The innermost ``serve.*`` host span around time ``t``, else the
    innermost ``bench.*`` one."""
    best = None
    for a, b, name in spans:
        if not a <= t < b:
            continue
        key = (not name.startswith("serve."), b - a)
        if best is None or key < best[0]:
            best = (key, name)
    return best[1] if best else "outside bench spans"


def reduce(trace_dir, window_record: dict) -> dict:
    out = reduce_profile(load(trace_dir))
    out["traced"] = window_record["traced"]
    return out


# ---- helpers the per-layer readers share


def peak(record) -> Optional[dict]:
    """The chip's peaks; None off the chip, an error on an unknown chip."""
    if not record.get("on_chip", True):
        return None
    from bench import peaks
    return peaks.peaks(record["device_kind"])


def traced_steps(record) -> list:
    """Engine steps that ran wholly inside the traced window and served
    at least one row."""
    t0, t1 = record["window"]["traced"]
    return [st for st in record["window"]["steps"]
            if st.rows and st.t0 >= t0 and st.t1 <= t1]


def traced_prefills(record) -> List[Tuple[int, int]]:
    """(start, tokens) of each prefill the engine logged inside the
    traced window: ``start`` is the shared prefix it attended over."""
    w = record["window"]
    t0, t1 = w["traced"]
    out = []
    for line in w["engine_log"]:
        parts = line.split()
        if len(parts) < 3 or parts[1] != "prefill":
            continue
        t = float(parts[0]) - w["wall0"]
        if not t0 <= t <= t1:
            continue
        kv = dict(p.split("=", 1) for p in parts[2:] if "=" in p)
        out.append((int(kv.get("shared", 0)), int(kv["tokens"])))
    return out
