"""Incremental-prefill serving engine vs the rebatching baseline.

The old serving loop re-prefilled the *whole* batch on every admit and
retire — O(active · steps) prefill work (plus a fresh shape, hence a
fresh XLA compile, per wave).  The engine's incremental mode prefills
exactly the admitted sequence and writes it into its slot, leaving live
slots untouched.

This bench drives both modes of the same :class:`ServingEngine` over a
**skewed admit/retire workload** — a few long-lived sequences pin their
slots while a stream of short requests churns through the rest, the
pattern that maximizes re-prefill waste — and reports decoded tokens/s.
Target: **>= 2x** for the incremental engine.  Also reported: prefill
tokens pushed by each mode (the work the tentpole deletes), and a
3-run same-seed SimExecutor determinism check on the engine trace.

The **paged sweep** then A/Bs ``kv_mode="paged"`` against ``"dense"``
over growing (active slots x max_seq) cells with *short* live sequences
— the serving regime paged KV exists for: the dense path drags a
(B, max_seq) reservation through every decode step (attention over the
full reservation plus an O(max_seq) cache scatter), while the paged
path's cost follows the pages actually allocated.  The headline
``paged_speedup_x`` is the largest cell's ratio, and the cell series
must show the gap growing.

``--json-out`` writes ``BENCH_serve.json`` for the CI trend check.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time
from typing import Dict, List, Optional

from repro.launch.mesh import make_serving_mesh, simulate_host_devices

# before the first computation: under JAX_PLATFORMS=cpu, split the host
# CPU into 4 simulated XLA devices so the shard sweep has a mesh to run
# on (a no-op if XLA_FLAGS already pins a device count — e.g. under the
# test conftest)
simulate_host_devices(4)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import Request, ServingEngine  # noqa: E402
from repro.runtime.serve_loop import ServerConfig  # noqa: E402


def _requests(n: int, prompt_len: int, new_tokens: int, long_every: int,
              long_tokens: int, vocab: int) -> List[Request]:
    """Deterministic skewed workload: mostly short churn, a few pinners."""
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(n):
        is_long = long_every > 0 and i % long_every == 0
        reqs.append(Request(
            prompt=rng.integers(0, vocab, (prompt_len,)).astype(np.int32),
            max_new_tokens=long_tokens if is_long else new_tokens,
            request_id=i,
        ))
    return reqs


def _build_engine(arch: str, *, max_batch: int, max_seq: int,
                  incremental: bool, kv_mode: str = "dense",
                  kv_pool_pages=None, executor=None,
                  prefill_chunk_tokens: int = 0):
    cfg = get_reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(
        model, params,
        ServerConfig(max_batch=max_batch, max_seq=max_seq,
                     incremental=incremental, kv_mode=kv_mode,
                     kv_pool_pages=kv_pool_pages,
                     prefill_chunk_tokens=prefill_chunk_tokens),
        executor=executor,
    )
    return engine, cfg


def run_mode(arch: str, *, incremental: bool, requests: int, prompt_len: int,
             new_tokens: int, long_every: int, long_tokens: int,
             max_batch: int, max_seq: int) -> Dict[str, float]:
    engine, cfg = _build_engine(
        arch, max_batch=max_batch, max_seq=max_seq, incremental=incremental,
    )
    # warmup outside the timed window: decode-jit compile + first prefill
    for r in _requests(max_batch, prompt_len, 2, 0, 2, cfg.vocab_size):
        r.request_id += 10_000
        engine.submit(r)
    engine.drain()
    warm_stats = engine.serving_stats()

    reqs = _requests(requests, prompt_len, new_tokens, long_every,
                     long_tokens, cfg.vocab_size)
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.drain()
    wall = time.perf_counter() - t0

    assert all(r.error is None for r in reqs)
    leaked = engine.kv.seq_lens()
    assert leaked.size == 0 and engine.kv.total_runs() == 0
    tokens = sum(len(r.tokens) for r in reqs)
    stats = engine.serving_stats()
    prefill_tokens = {
        mode: stats["prefill_tokens_total"][mode]
        - warm_stats["prefill_tokens_total"][mode]
        for mode in ("incremental", "full")
    }
    return {
        "tokens": tokens,
        "wall_s": wall,
        "tokens_per_s": tokens / wall,
        "prefill_tokens": float(sum(prefill_tokens.values())),
    }


#: (active slots, max_seq) cells for the paged-vs-dense sweep — both
#: axes grow together so the dense path's reservation tax compounds
PAGED_SWEEP_CELLS = ((2, 1024), (3, 2048), (4, 4096))


def _paged_cell(arch: str, *, kv_mode: str, slots: int, max_seq: int,
                requests: int, prompt_len: int, new_tokens: int) -> float:
    """Tokens/s for one (slots, max_seq) cell in one kv_mode.

    The workload is deliberately *short-lived churn*: live sequences
    never exceed a couple of KV pages, so every byte of the dense mode's
    (B, max_seq) reservation — the padded prefill, the full-width
    attention, the full-width cache scatter — is pure overhead that the
    paged mode does not pay.  The page pool is sized to the live-token
    working set (4x headroom), NOT to max_seq — sizing the pool to the
    memory actually available is how paged KV deploys, and it is why the
    paged columns stay flat while the dense columns degrade.
    """
    page = ServerConfig.tokens_per_page
    pool = 4 * slots * (-(-(prompt_len + new_tokens + 1) // page) + 1)
    engine, cfg = _build_engine(
        arch, max_batch=slots, max_seq=max_seq, incremental=True,
        kv_mode=kv_mode, kv_pool_pages=pool,
    )
    assert engine.kv_mode == kv_mode
    # warmup: same request shape as the timed run, so every jit variant
    # (prefill width, decode table bucket) compiles outside the window
    for r in _requests(slots, prompt_len, new_tokens, 0, 0, cfg.vocab_size):
        r.request_id += 10_000
        engine.submit(r)
    engine.drain()

    reqs = _requests(requests, prompt_len, new_tokens, 0, 0, cfg.vocab_size)
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.drain()
    wall = time.perf_counter() - t0
    assert all(r.error is None for r in reqs)
    assert engine.kv.total_runs() == 0
    assert engine.kv.pages_allocated == engine.kv.pages_freed
    return sum(len(r.tokens) for r in reqs) / wall


def run_paged_sweep(arch: str, *, prompt_len: int = 8,
                    new_tokens: int = 6) -> List[Dict[str, float]]:
    """A/B ``kv_mode`` over growing (slots, max_seq) cells.

    Returns one row per cell with both throughputs and the ratio; the
    caller asserts the ratio > 1 at the largest cell and that the gap
    grows along the sweep.
    """
    rows = []
    for slots, max_seq in PAGED_SWEEP_CELLS:
        cell = dict(slots=slots, max_seq=max_seq, requests=3 * slots,
                    prompt_len=prompt_len, new_tokens=new_tokens)
        dense = _paged_cell(arch, kv_mode="dense", **cell)
        paged = _paged_cell(arch, kv_mode="paged", **cell)
        rows.append({
            "slots": slots,
            "max_seq": max_seq,
            "dense_tokens_per_s": dense,
            "paged_tokens_per_s": paged,
            "speedup_x": paged / dense,
        })
    return rows


def run_chunk_interference(arch: str, *, long_prompt: int = 1024,
                           chunk: int = 32,
                           interactive_tokens: int = 48) -> Dict[str, float]:
    """Long-prompt admission interference on a live decode stream.

    One interactive request is mid-decode when a ``long_prompt``-token
    request arrives.  With monolithic prefill the admission tick runs
    the whole prompt before the live slot decodes again — a stall the
    interactive stream feels as one giant inter-token gap.  With a
    per-step budget (``prefill_chunk_tokens=chunk``) the prompt trickles
    in ``chunk`` rows per tick and the live slot decodes on every one
    of them, so the worst gap collapses to one-chunk-plus-one-decode.

    Measures wall-clock inter-token gaps on the interactive stream while
    the long prompt is in flight; the headline is the p99 ratio
    (monolithic over chunked), hard-floored at >= 3x.
    """
    page = ServerConfig.tokens_per_page
    pool = 4 * (-(-(long_prompt + interactive_tokens + 16) // page) + 2)

    def _one_pass(engine, cfg, rid: int) -> float:
        """One interference schedule; p99 inter-token gap on the
        interactive stream while the long prompt is in flight."""
        rng = np.random.default_rng(3)
        mk = lambda n, new, r: Request(  # noqa: E731
            prompt=rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32),
            max_new_tokens=new, request_id=r,
        )
        inter = mk(8, interactive_tokens, rid)
        engine.submit(inter)
        while len(inter.tokens) < 4:       # settle into steady decode
            engine.step()
        engine.submit(mk(long_prompt, 2, rid + 1))
        gaps = []
        last = time.perf_counter()
        while not inter.done:
            engine.step()
            now = time.perf_counter()
            gaps.append(now - last)
            last = now
        engine.drain()
        assert inter.error is None
        assert engine.kv.total_runs() == 0
        return float(np.percentile(np.asarray(gaps), 99))

    def _measure(budget: int) -> float:
        engine, cfg = _build_engine(
            arch, max_batch=2, max_seq=long_prompt + 64, incremental=True,
            kv_mode="paged", kv_pool_pages=pool,
            prefill_chunk_tokens=budget,
        )
        # the warmup pass IS the timed schedule — identical admission
        # order, so every jit variant (prefill/chunk widths at their
        # exact positions, decode table buckets) compiles before the
        # timed pass
        _one_pass(engine, cfg, 10_000)
        return _one_pass(engine, cfg, 1)

    mono_p99 = _measure(0)
    chunk_p99 = _measure(chunk)
    reduction = mono_p99 / chunk_p99
    # the tentpole's acceptance gate: budgeted prefill must shrink the
    # interactive stream's worst stall by at least 3x.  Wall-clock, but
    # the two runs share a process and the stall being measured is a
    # ~long_prompt/chunk compute ratio, so 3x holds with wide margin
    assert reduction >= 3.0, (
        f"chunked prefill only cut the p99 inter-token stall "
        f"{reduction:.2f}x (mono {mono_p99 * 1e3:.1f}ms vs "
        f"chunked {chunk_p99 * 1e3:.1f}ms)"
    )
    return {
        "long_prompt": long_prompt,
        "chunk": chunk,
        "mono_intertoken_p99_ms": mono_p99 * 1e3,
        "chunk_intertoken_p99_ms": chunk_p99 * 1e3,
        "chunk_stall_reduction_x": reduction,
    }


def _shard_cell(arch: str, *, mesh_devices: int, slots: int = 2,
                max_seq: int = 48, requests: int = 6, prompt_len: int = 8,
                new_tokens: int = 6) -> float:
    """Tokens/s for one tensor-parallel cell (``mesh_devices=0`` = no mesh).

    Uses a TP-capable head layout (4 query heads over 4 KV heads) so the
    mesh sizes 1/2/4 all divide the head axes — the stock reduced config
    has a single KV head and would fall back to the unsharded path.
    """
    cfg = dataclasses.replace(
        get_reduced(arch), num_heads=4, num_kv_heads=4, head_dim=16,
    )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    page = ServerConfig.tokens_per_page
    pool = 4 * slots * (-(-(prompt_len + new_tokens + 1) // page) + 1)
    engine = ServingEngine(
        model, params,
        ServerConfig(max_batch=slots, max_seq=max_seq, incremental=True,
                     kv_mode="paged", kv_pool_pages=pool),
        mesh=make_serving_mesh(mesh_devices) if mesh_devices else None,
    )
    expect_shards = mesh_devices if mesh_devices else 1
    assert engine.serving_stats()["tp_shards"] == expect_shards
    for r in _requests(slots, prompt_len, new_tokens, 0, 0, cfg.vocab_size):
        r.request_id += 10_000
        engine.submit(r)
    engine.drain()

    reqs = _requests(requests, prompt_len, new_tokens, 0, 0, cfg.vocab_size)
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.drain()
    wall = time.perf_counter() - t0
    assert all(r.error is None for r in reqs)
    assert engine.kv.pages_allocated == engine.kv.pages_freed
    return sum(len(r.tokens) for r in reqs) / wall


def run_shard_sweep(arch: str) -> Dict[str, object]:
    """Tensor-parallel paged decode over mesh sizes 1/2/4.

    The 1-device mesh row must land within noise of the no-mesh baseline
    (shard_map over one device is the same computation, so a real gap
    means the TP plumbing itself costs throughput).  ``shard_speedup_x``
    is largest-mesh over 1-device-mesh tokens/s — on a *simulated* CPU
    mesh the shards timeshare one physical core, so this is a plumbing-
    overhead measurement (expected near or below 1x), not a scaling
    claim; on real multi-chip hardware the same sweep measures scaling.
    """
    base = _shard_cell(arch, mesh_devices=0)
    rows = []
    for n in (1, 2, 4):
        if n > len(jax.devices()):
            continue
        rows.append({
            "mesh_devices": n,
            "tokens_per_s": _shard_cell(arch, mesh_devices=n),
        })
    ratio1 = rows[0]["tokens_per_s"] / base
    assert 1 / 3 <= ratio1 <= 3, (
        f"1-device mesh row diverged from the no-mesh baseline: "
        f"{ratio1:.2f}x"
    )
    return {
        "no_mesh_tokens_per_s": base,
        "rows": rows,
        "shard_speedup_x": rows[-1]["tokens_per_s"] / rows[0]["tokens_per_s"],
    }


def run_sim_determinism(arch: str, seed: int = 7) -> str:
    """Engine trace under SimExecutor must be a pure function of the seed."""
    from repro.core import SimExecutor

    def once():
        engine, cfg = _build_engine(
            arch, max_batch=2, max_seq=48, incremental=True,
            executor=SimExecutor(seed=seed),
        )
        engine.cfg.step_time_s = 0.01
        for r in _requests(6, 8, 3, 3, 6, cfg.vocab_size):
            engine.submit(r)
        engine.drain()
        return hashlib.sha256(engine.trace_text().encode()).hexdigest()

    digests = {once() for _ in range(3)}
    assert len(digests) == 1, f"engine traces diverged: {digests}"
    return next(iter(digests))


def main(
    arch: str = "qwen2.5-32b",
    requests: int = 18,
    prompt_len: int = 32,
    new_tokens: int = 4,
    long_every: int = 6,
    long_tokens: int = 32,
    max_batch: int = 4,
    max_seq: int = 96,
    json_out: Optional[str] = None,
) -> Dict[str, float]:
    common = dict(
        requests=requests, prompt_len=prompt_len, new_tokens=new_tokens,
        long_every=long_every, long_tokens=long_tokens,
        max_batch=max_batch, max_seq=max_seq,
    )
    rebatch = run_mode(arch, incremental=False, **common)
    incremental = run_mode(arch, incremental=True, **common)
    speedup = incremental["tokens_per_s"] / rebatch["tokens_per_s"]
    # the acceptance floor lives here (hard assert) rather than in the
    # trend check: the ratio's absolute value swings with compile-time
    # weather (~16-42x), but a collapse toward rebatching-order cost is
    # exactly what this bench exists to catch
    assert speedup >= 2.0, (
        f"incremental engine only {speedup:.2f}x over rebatching"
    )
    prefill_saved = (
        rebatch["prefill_tokens"] / max(incremental["prefill_tokens"], 1.0)
    )

    sweep = run_paged_sweep(arch)
    paged_speedup = sweep[-1]["speedup_x"]
    # the tentpole's acceptance gate: paged must beat dense, and the gap
    # must widen as the reservation (slots x max_seq) grows — if paging
    # overhead ever swamps the reservation tax, this is where it shows
    assert paged_speedup > 1.0, (
        f"paged decode lost to dense at the largest cell: "
        f"{paged_speedup:.2f}x"
    )
    assert sweep[-1]["speedup_x"] > sweep[0]["speedup_x"], (
        "paged-vs-dense gap did not grow along the sweep: "
        + ", ".join(f"{r['speedup_x']:.2f}x" for r in sweep)
    )

    interference = run_chunk_interference(arch)

    shard = run_shard_sweep(arch)

    digest = run_sim_determinism(arch)

    print("# serve_bench")
    print(f"  arch={arch} requests={requests} batch={max_batch} "
          f"prompt={prompt_len} new={new_tokens} "
          f"long=1/{long_every}@{long_tokens}tok")
    print(f"  rebatching baseline : {rebatch['tokens_per_s']:8.1f} tok/s "
          f"({rebatch['prefill_tokens']:.0f} prefill tokens)")
    print(f"  incremental engine  : {incremental['tokens_per_s']:8.1f} tok/s "
          f"({incremental['prefill_tokens']:.0f} prefill tokens)")
    print(f"  speedup             : {speedup:.1f}x tokens/s, "
          f"{prefill_saved:.1f}x less prefill work")
    print("  paged-vs-dense sweep (short-lived churn):")
    for row in sweep:
        print(f"    slots={row['slots']} max_seq={row['max_seq']:5d} : "
              f"dense {row['dense_tokens_per_s']:8.1f} tok/s, "
              f"paged {row['paged_tokens_per_s']:8.1f} tok/s "
              f"-> {row['speedup_x']:.2f}x")
    print(f"  paged speedup       : {paged_speedup:.2f}x at the largest "
          f"cell (gap grows along the sweep)")
    print(f"  long-prompt interference ({interference['long_prompt']}-token "
          f"admit into a live decode):")
    print(f"    monolithic prefill: p99 inter-token gap "
          f"{interference['mono_intertoken_p99_ms']:8.1f} ms")
    print(f"    chunked (budget={interference['chunk']:3d}): p99 gap "
          f"{interference['chunk_intertoken_p99_ms']:8.1f} ms")
    print(f"  stall reduction     : "
          f"{interference['chunk_stall_reduction_x']:.1f}x (target:>=3x)")
    print("  tensor-parallel shard sweep (simulated mesh):")
    print(f"    no mesh           : "
          f"{shard['no_mesh_tokens_per_s']:8.1f} tok/s")
    for row in shard["rows"]:
        print(f"    mesh={row['mesh_devices']}            : "
              f"{row['tokens_per_s']:8.1f} tok/s")
    print(f"  shard speedup       : {shard['shard_speedup_x']:.2f}x "
          f"(mesh-{shard['rows'][-1]['mesh_devices']} vs mesh-1; "
          f"simulated shards timeshare one core)")
    print(f"  sim determinism     : 3 runs -> trace sha256 "
          f"{digest[:16]}... identical")

    result = {
        "arch": arch,
        "requests": requests,
        "max_batch": max_batch,
        "rebatch_tokens_per_s": rebatch["tokens_per_s"],
        "incremental_tokens_per_s": incremental["tokens_per_s"],
        "incremental_speedup_x": speedup,
        "rebatch_prefill_tokens": rebatch["prefill_tokens"],
        "incremental_prefill_tokens": incremental["prefill_tokens"],
        "prefill_reduction_x": prefill_saved,
        "paged_speedup_x": paged_speedup,
        "paged_sweep": sweep,
        "chunk_stall_reduction_x": interference["chunk_stall_reduction_x"],
        "mono_intertoken_p99_ms": interference["mono_intertoken_p99_ms"],
        "chunk_intertoken_p99_ms": interference["chunk_intertoken_p99_ms"],
        "chunk_interference": interference,
        "shard_speedup_x": shard["shard_speedup_x"],
        "shard_sweep": shard,
        "sim_trace_sha256": digest,
    }
    if json_out:
        with open(json_out, "w") as fh:
            json.dump(result, fh, indent=2, sort_keys=True)
        print(f"  wrote {json_out}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-32b")
    ap.add_argument("--requests", type=int, default=18)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--long-every", type=int, default=6)
    ap.add_argument("--long-tokens", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=96)
    ap.add_argument("--json-out", default=None)
    a = ap.parse_args()
    main(arch=a.arch, requests=a.requests, prompt_len=a.prompt_len,
         new_tokens=a.new_tokens, long_every=a.long_every,
         long_tokens=a.long_tokens, max_batch=a.max_batch,
         max_seq=a.max_seq, json_out=a.json_out)
