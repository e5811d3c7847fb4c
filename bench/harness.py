"""One run of one cell: set-up, the measured window, the drain, the
correctness check and the result line.

Everything that belongs to a configuration, a traffic mix or a
per-layer metric is found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``mixes/<traffic>.json`` and
``metrics/<metric>.py``, and a configuration's model family by the name
its file gives it, ``families/<family>.py``.  This file names none of
them.

The window drives the program's ``Server.submit`` / ``Server.step``.
The harness owns the loop: before each ``step()`` it submits every
request now due, after it stamps every token that appeared, on the host
clock.  ``step()`` ends by copying the logits to the host, so the device
has finished when the stamp is taken.
"""

from __future__ import annotations

import gc
import heapq
import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
METRICS = BENCH / "metrics"

#: in-flight requests still unfinished this long after the window
#: closes count as failed
DRAIN_S = 60.0
#: sampled requests compared with the reference in each run
CHECK_REQUESTS = 6
#: the traced part of a ``--trace 1`` window, seconds, starting at this
#: share of the window; it stays open past ``TRACE_SECONDS`` until a
#: prefill has run inside it, so that every per-layer reader finds its
#: program there, and closes at ``TRACE_MAX_SECONDS`` whatever came
TRACE_SECONDS = 4.0
TRACE_MAX_SECONDS = 20.0
TRACE_AT = 0.25
#: first request id of the warm-up requests, far above the window's
WARM_IDS = 1 << 40


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ cell


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    raw: dict            # the configuration file
    mix: dict            # the traffic file
    limits: dict         # the correctness limits: limits/<cell>.json
    metrics: dict        # end_to_end and per_layer entries that apply


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    raw = json.loads((root / conf["file"]).read_text())
    from bench import traffic
    here = root / BENCH.relative_to(ROOT)
    mix = traffic.load_mix(w["traffic"], here / "mixes")
    return Cell(
        name=workload, config=w["config"], traffic=w["traffic"],
        chips=w["chips"], raw=raw, mix=mix,
        limits=json.loads((here / "limits" / f"{workload}.json").read_text()),
        metrics={
            "end_to_end": [m for m in spec["end_to_end"]
                           if _applies(m, workload)],
            "per_layer": [m for m in spec["per_layer"]
                          if _applies(m, workload)],
        })


def load_reader(name: str) -> Callable:
    """``metrics/<name>.py``'s ``read(record)``, by file name."""
    path = METRICS / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------- chips


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(count: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {devices[0].platform!r}")
    if len(devices) < count:
        raise NoChip(f"the cell needs {count} chips, JAX sees "
                     f"{len(devices)}")
    return devices[:count]


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    if set, else the checkout's ``.jax_cache/``), keeping every program:
    the cell's small ones too, so that a second run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


class CompileCounter:
    """Counts backend compiles (persistent-cache misses) while on."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, name, secs, **kw) -> None:
        if self.on and name == self.EVENT:
            self.count += 1


# --------------------------------------------------------------- program


def build_server(s, mix: dict, params):
    from repro.models import build_model
    from repro.runtime import Server, ServerConfig
    import jax
    from bench import config
    model = build_model(config.family(s).arch_config(s))
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise RuntimeError("bench weights do not match the program's "
                           "parameter layout")
    cfg = ServerConfig(max_batch=s.max_batch, max_seq=s.max_seq,
                       tokens_per_page=s.page_tokens,
                       kv_pool_pages=s.pool_pages, kv_mode="paged",
                       prefix_sharing=True, **mix.get("server", {}))
    return Server(model, params, cfg)


def free_server(srv) -> None:
    import jax
    srv.close()
    eng = srv.engine
    for a in jax.tree.leaves((eng.params, eng.kv.store)):
        if not a.is_deleted():
            a.delete()
    gc.collect()


# ------------------------------------------------------------- requests


@dataclass(eq=False)
class Rec:
    """One request of the window, with its host stamps."""
    req: object
    item: object
    turn: int
    sched: float                 # scheduled send, perf_counter
    client: int = -1
    times: List[float] = field(default_factory=list)
    failed: bool = False


@dataclass
class Step:
    t0: float
    t1: float
    rows: int                    # rows that got a token
    lens: List[int]              # their attention lengths this step
    queue: int = 0               # requests waiting for a slot after it


def bucket(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


def warm_plan(t, s):
    """The program shapes the traffic reaches with every turn's previous
    turn parked: ``("full", width)`` and ``("suffix", width, table
    bucket)``; and the prompt-length chains that reach them all."""
    pages = lambda n: -(-n // s.page_tokens)  # noqa: E731
    keys: set = set()
    if t.system_tokens:
        keys.add(("full", t.system_tokens))
    chains = []
    for it in t.items:
        lens = t.prompt_lengths(it)
        new = set()
        for k, P in enumerate(lens):
            start = lens[k - 1] if k else t.system_tokens
            new.add(("suffix", P - start, bucket(pages(P))) if start
                    else ("full", P))
            if k == 0 and start:
                new.add(("full", P))       # the tenant's donor was evicted
        if new - keys:
            chains.append(lens)
            keys |= new
    return keys, chains


class Runner:
    """The cell's server and traffic, driven through warm-up and one
    measured window."""

    def __init__(self, cell: Cell, shapes, traffic, srv, seed: int) -> None:
        self.cell, self.s, self.traffic, self.srv = cell, shapes, traffic, srv
        self.seed = seed
        self._ids = 0
        self._warm_ids = WARM_IDS

    # ---- requests

    def _request(self, prompt, out: int, sampled: bool, tenant, rid: int):
        from repro.runtime import Request
        knobs = self.traffic.mix.get("sampling", {}) if sampled else {}
        return Request(
            prompt=np.asarray(prompt, np.int32), max_new_tokens=int(out),
            request_id=rid,
            tenant=f"t{tenant}" if tenant is not None else "serving",
            temperature=knobs.get("temperature", 0.0),
            top_k=knobs.get("top_k", 0), top_p=knobs.get("top_p", 1.0),
            seed=(self.seed * 1_000_003 + rid) & ((1 << 63) - 1))

    def _warm_request(self, prompt, out: int = 1, tenant=None):
        self._warm_ids += 1
        return self._request(prompt, out, False, tenant, self._warm_ids)

    def _drain(self) -> None:
        self.srv.drain(timeout=1200.0)

    # ---- warm-up

    def warm(self) -> Dict[str, int]:
        """Run every program shape the window's traffic can reach once:
        each item's chain of prompts with one-token answers (full
        prefills, suffix prefills over the previous turn's parked pages,
        the copy-on-write of a partial page, the decode step at each
        table width), then the decode widths that answers grow into.
        Returns how many shapes of each kind were warmed."""
        t, s = self.traffic, self.s
        rng = np.random.default_rng(self.seed & ((1 << 63) - 1))
        pages = lambda n: -(-n // s.page_tokens)  # noqa: E731
        keys, chains = warm_plan(t, s)
        for w in range(0, len(chains), s.max_batch):
            wave = chains[w:w + s.max_batch]
            tenants = rng.integers(0, max(1, t.mix.get("tenants", {})
                                          .get("count", 1)), len(wave))
            if t.system_tokens:
                self._park_system_prompts()
                prompts = [t.system_prompt(int(x)) for x in tenants]
            else:
                prompts = [np.zeros(0, np.int32) for _ in wave]
            for k in range(max(len(lens) for lens in wave)):
                for i, lens in enumerate(wave):
                    if k < len(lens):
                        prompts[i] = np.concatenate([
                            prompts[i], rng.integers(
                                0, s.vocab, lens[k] - len(prompts[i]))
                            .astype(np.int32)])
                        self.srv.submit(self._warm_request(
                            prompts[i], tenant=int(tenants[i])))
                self._drain()
        fulls = sorted(k[1] for k in keys if k[0] == "full")
        if t.system_tokens:
            # first turns of a tenant with no parked donor prefill whole
            self.srv.engine.flush_prefix_cache()
            for P in fulls:
                self.srv.submit(self._warm_request(
                    rng.integers(0, s.vocab, P).astype(np.int32)))
            self._drain()
        # decode table widths: every bucket a context can reach, each
        # reached alone by a warmed prompt that decodes into it
        lo = min(t.prompt_lengths(it)[0] for it in t.items)
        hi = max(P + it.turns[k].out for it in t.items
                 for k, P in enumerate(t.prompt_lengths(it)))
        widths = sorted({bucket(pages(n)) for n in range(lo + 1, hi + 1)})
        for b in widths:
            need = (b // 2) * s.page_tokens + 1
            cands = [P for P in fulls if P < b * s.page_tokens]
            if not cands:
                continue
            P = max(cands)
            out = max(1, need - P)
            if P + out <= s.max_seq:
                self.srv.submit(self._warm_request(
                    rng.integers(0, s.vocab, P).astype(np.int32), out=out))
                self._drain()
        eng = self.srv.engine
        eng.flush_prefix_cache()
        eng.reset_history()
        if t.system_tokens and t.mix["tenants"].get("warm"):
            self._park_system_prompts()
        return {"full_prefill_widths": len(fulls),
                "suffix_prefill_shapes": sum(k[0] == "suffix" for k in keys),
                "decode_widths": len(widths)}

    def _park_system_prompts(self) -> None:
        """Each tenant's system prompt served once with a one-token
        answer, so it is parked as a prefix donor."""
        t = self.traffic
        for tenant in range(t.mix["tenants"]["count"]):
            self.srv.submit(self._warm_request(t.system_prompt(tenant),
                                               tenant=tenant))
        self._drain()

    # ---- window

    def window(self, seconds: float, trace_dir: Optional[Path] = None):
        """Measure for ``seconds``; returns the run record."""
        import jax
        t, srv, s = self.traffic, self.srv, self.s
        eng = srv.engine
        stats0 = eng.serving_stats()
        history: Dict[int, np.ndarray] = {}
        pending: list = []            # (sched, seq, item, turn, client)
        seq = 0
        recs: List[Rec] = []
        inflight: Dict[int, Rec] = {}
        steps: List[Step] = []
        kv_peak = eng.kv.live_pages()
        t0 = time.perf_counter()
        wall0 = time.time() - t0
        end = t0 + seconds
        if t.loop == "open":
            for it in t.items:
                heapq.heappush(pending, (t0 + it.start_s, seq, it, 0, -1))
                seq += 1
        else:
            pool = iter(t.items)
            for c in range(t.mix["clients"]):
                heapq.heappush(pending, (t0, seq, next(pool), 0, c))
                seq += 1
        trace_at = t0 + TRACE_AT * seconds
        traced = None                 # [perf t0, perf t1, annotation]
        lateness: List[float] = []

        def submit(sched, it, k, client):
            if k == 0:
                prompt = (np.concatenate([t.system_prompt(it.tenant),
                                          t.user_tokens(it, 0)])
                          if t.system_tokens else t.user_tokens(it, 0))
            else:
                prompt = np.concatenate([history[it.index],
                                         t.user_tokens(it, k)])
            self._ids += 1
            turn = it.turns[k]
            r = self._request(prompt, turn.out, turn.sampled, it.tenant,
                              self._ids)
            r.arrived_at = sched + wall0
            rec = Rec(req=r, item=it, turn=k, sched=sched, client=client)
            recs.append(rec)
            inflight[r.request_id] = rec
            lateness.append(time.perf_counter() - sched)
            srv.submit(r)

        def finish(rec: Rec, now: float) -> None:
            nonlocal seq
            del inflight[rec.req.request_id]
            it, k = rec.item, rec.turn
            if t.loop == "open":
                if k + 1 < len(it.turns):
                    history[it.index] = np.concatenate(
                        [rec.req.prompt, np.asarray(rec.req.tokens, np.int32)])
                    heapq.heappush(pending, (now + it.think_s[k + 1], seq,
                                             it, k + 1, -1))
                    seq += 1
            else:
                nxt = next(pool, None)
                if nxt is not None:
                    heapq.heappush(pending, (now, seq, nxt, 0, rec.client))
                    seq += 1

        def prefills() -> int:
            return sum(eng.serving_stats()["prefill_sequences_total"]
                       .values())

        ann = None
        while True:
            now = time.perf_counter()
            if trace_dir is not None and traced is None and now >= trace_at:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # host spans, no py calls
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
                ann = jax.profiler.TraceAnnotation("bench.traced")
                ann.__enter__()
                traced = [time.perf_counter(), None]
                traced_from = prefills()
            elif (traced is not None and traced[1] is None
                  and now >= traced[0] + TRACE_SECONDS
                  and (prefills() > traced_from
                       or now >= traced[0] + TRACE_MAX_SECONDS)):
                ann.__exit__(None, None, None)
                traced[1] = time.perf_counter()
                jax.profiler.stop_trace()
            with jax.profiler.TraceAnnotation("bench.submit"):
                while pending and pending[0][0] <= now and pending[0][0] < end:
                    sched, _, it, k, client = heapq.heappop(pending)
                    submit(sched, it, k, client)
            if inflight:
                with jax.profiler.TraceAnnotation("bench.step"):
                    a = time.perf_counter()
                    srv.step()
                    b = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.stamp"):
                    lens = []
                    for rec in list(inflight.values()):
                        r = rec.req
                        new = len(r.tokens) - len(rec.times)
                        if new > 0:
                            rec.times.extend([b] * new)
                            lens.append(len(r.prompt) + len(r.tokens))
                        if r.done:
                            rec.failed = r.error is not None
                            finish(rec, b)
                    steps.append(Step(a, b, len(lens), lens,
                                      eng.queue_depth()))
                    kv_peak = max(kv_peak, eng.kv.live_pages())
                if b > end + DRAIN_S:
                    break
                continue
            if now >= end and not (pending and pending[0][0] < end):
                break
            with jax.profiler.TraceAnnotation("bench.wait"):
                nxt = pending[0][0] if pending else end
                time.sleep(max(0.0, min(nxt, end) - time.perf_counter()))
        if traced is not None and traced[1] is None:
            ann.__exit__(None, None, None)
            traced[1] = time.perf_counter()
            jax.profiler.stop_trace()
        for rec in inflight.values():
            rec.failed = True
        return {
            "t0": t0, "end": end, "wall0": wall0, "seconds": seconds,
            "recs": recs, "steps": steps, "kv_peak_pages": kv_peak,
            "stats0": stats0, "stats1": eng.serving_stats(),
            "engine_log": eng.trace(), "traced": traced,
            "lateness": lateness,
        }


# -------------------------------------------------------------- metrics


def end_to_end(rec: dict, setup_s: float) -> Dict[str, float]:
    """The user-facing numbers of the window, from the host stamps."""
    from bench import stats
    t0, end = rec["t0"], rec["end"]
    in_window = [r for r in rec["recs"] if t0 <= r.sched < end]
    tokens = sum(1 for r in rec["recs"] for x in r.times if x <= end)
    horizon = end + DRAIN_S
    ttft = [((r.times[0] if r.times and not r.failed else horizon)
             - r.sched) * 1e3 for r in in_window]
    itl = [(b - a) * 1e3 for r in in_window
           for a, b in zip(r.times, r.times[1:])]
    return {
        "output_tokens_per_s": stats.rate(tokens, rec["seconds"]),
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "itl_p95_ms": stats.percentile(itl, 95),
        "setup_s": setup_s,
    }


# ------------------------------------------------------------ correctness


def pick_checked(recs: List[Rec], seed: int, n: int = CHECK_REQUESTS):
    """The requests compared with the reference: finished greedy ones,
    the longest first, then one of each admission kind the run had
    (full prefill, suffix prefill, suffix with a copied page), then a
    draw from the seed."""
    done = [r for r in recs if r.req.done and not r.failed
            and r.req.temperature <= 0.0 and r.req.tokens]
    if not done:
        return []
    done.sort(key=lambda r: r.req.request_id)
    longest = max(done, key=lambda r: len(r.req.prompt) + len(r.req.tokens))
    rng = np.random.default_rng(seed & ((1 << 63) - 1))
    picked = [longest]
    kinds: Dict[str, list] = {}
    for r in done:
        kinds.setdefault(getattr(r, "kind", "full"), []).append(r)
    for kind in sorted(kinds):
        cands = [r for r in kinds[kind] if r not in picked]
        if cands and len(picked) < n:
            picked.append(cands[rng.integers(len(cands))])
    rest = [r for r in done if r not in picked]
    for i in rng.permutation(len(rest))[: max(0, n - len(picked))]:
        picked.append(rest[i])
    return picked


def classify(recs: List[Rec], engine_log: List[str]) -> None:
    """Mark each request with how it was admitted, from the engine's
    decision log: ``full``, ``suffix`` (shared prefix) or ``suffix+cow``
    (its first write copied a shared page)."""
    shared, cow = set(), set()
    for line in engine_log:
        parts = line.split()
        if len(parts) > 2 and parts[1] == "prefix_share":
            shared.add(parts[2].split("=")[1])
        elif len(parts) > 2 and parts[1] == "cow":
            for p in parts:
                if p.startswith("seq=req"):
                    cow.add(p[len("seq=req"):])
    for r in recs:
        rid = str(r.req.request_id)
        r.kind = ("suffix+cow" if rid in cow else
                  "suffix" if rid in shared else "full")


def numbers(per_request: List[np.ndarray]) -> Dict[str, float]:
    """The numbers compared with the cell's limits, from the gap at each
    served position of each checked request."""
    from bench import reference
    if not per_request:
        return {"logit_gap": math.inf, "mismatch_share": math.inf}
    gaps = np.concatenate(per_request)
    return {"logit_gap": reference.widest(per_request),
            "mismatch_share": 100.0 * float(np.mean(gaps > 0))}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit."""
    return all(values[name] <= limit for name, limit in limits.items())


def check(cell: Cell, s, seed: int, picked: List[Rec], *,
          control: bool = False) -> dict:
    """Compare the picked requests with the reference; the program's
    state must be freed already.  With ``control``, the fp8 control is
    read on the same requests and judged by the same limits."""
    from bench import reference, weights
    import jax
    w = weights.make(s, seed)
    served, ctl = [], []
    for r in picked:
        g, c = reference.gaps(w, s, r.req.prompt, r.req.tokens,
                              control=control)
        served.append(g)
        ctl.append(c)
    for a in jax.tree.leaves(w):
        a.delete()
    out = {"program": numbers(served),
           "tokens": int(sum(g.size for g in served)),
           "requests": len(picked),
           "kinds": sorted({getattr(r, "kind", "full") for r in picked})}
    if control:
        vals = numbers(ctl)
        out["control"] = {"correct": bool(picked) and judge(vals,
                                                            cell.limits),
                          **vals}
    return out


# ------------------------------------------------------------------- run


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: Path = ROOT, on_chip: bool = True,
             after_setup: Optional[Callable] = None,
             out_dir: Optional[Path] = None, control: bool = False) -> dict:
    """One run; returns the result line.  ``on_chip=False`` skips the
    look for a chip and the compile cache (the tests' rehearsal on the
    CPU, from a ``root`` with a small configuration);
    ``after_setup(runner)`` runs between set-up and the window (the
    tests plant faults there); ``control`` also reads the fp8 control on
    the same requests and judges it by the same limits
    (``calibrate.py``)."""
    import jax
    from bench import config, traffic as traffic_mod
    cell = load_cell(workload, root)
    if on_chip:
        devices = require_chips(cell.chips)
        log(f"compile cache {enable_compile_cache()}")
    else:
        devices = jax.devices()[:cell.chips]
    s = config.shapes_from(cell.raw, root)
    counter = CompileCounter()
    tr = traffic_mod.generate(cell.mix, seed, s.vocab, seconds)
    from bench import weights
    params = weights.make(s, seed)
    srv = build_server(s, cell.mix, params)
    del params
    runner = Runner(cell, s, tr, srv, seed)
    warmed = runner.warm()
    log(f"warmed programs: {warmed['full_prefill_widths']} full-prefill "
        f"widths, {warmed['suffix_prefill_shapes']} suffix-prefill shapes,"
        f" {warmed['decode_widths']} decode table widths")
    if after_setup is not None:
        after_setup(runner)
    setup_s = time.perf_counter() - t_start
    trace_dir = None
    if trace:
        trace_dir = (out_dir or root / ".bench_trace") / f"{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    counter.on = True
    rec = runner.window(seconds, trace_dir)
    counter.on = False
    dev = devices[0]
    peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in devices), default=0)
    recs = rec["recs"]
    in_window = [r for r in recs if rec["t0"] <= r.sched < rec["end"]]
    failed = sum(r.failed for r in in_window)
    late = sorted(rec["lateness"])
    log(f"generator lateness p50 {1e3 * late[len(late) // 2]:.3f} ms, max "
        f"{1e3 * late[-1]:.3f} ms over {len(late)} sends" if late else
        "generator sent nothing")
    log(f"requests sent {len(in_window)}, succeeded "
        f"{len(in_window) - failed}, failed {failed}; window compiles "
        f"{counter.count}")
    classify(recs, rec["engine_log"])
    picked = pick_checked(in_window, seed)
    stats1, stats0 = rec["stats1"], rec["stats0"]
    runner.srv = None
    free_server(srv)
    del srv
    chk = check(cell, s, seed, picked, control=control)
    checks = {name: {"value": chk["program"][name], "limit": limit}
              for name, limit in cell.limits.items()}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = (bool(picked) and failed == 0
               and judge(chk["program"], cell.limits))
    log(f"checked {chk['requests']} requests, {chk['tokens']} served "
        f"tokens, kinds {chk['kinds']}")
    if trace:
        from bench import trace as trace_mod
        record = {"shapes": s, "cell": cell, "window": rec,
                  "stats0": stats0, "stats1": stats1,
                  "trace": trace_mod.reduce(trace_dir, rec),
                  "device_kind": dev.device_kind, "on_chip": on_chip}
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in cell.metrics["per_layer"]:
            v = load_reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        red = record["trace"]
        device = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
        breakdown = {"device_ops": red["top_ops"],
                     "idle_gaps": red["idle_gaps"]}
    else:
        e2e = end_to_end(rec, setup_s)
        log("end to end, the cell's metrics and the rest: " + ", ".join(
            f"{k} {v!r}" for k, v in e2e.items()))
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics["end_to_end"]}
        device, breakdown = {}, None
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    line = {"correct": correct, "attempted": len(in_window),
            "failed": failed, "metrics": metrics,
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devices), "memory_peak_bytes": peak,
                       **device}}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["readings"] = chk["program"]
    if control:
        line["control"] = chk["control"]
    line["checks"] = checks
    return line
