"""Production serving launcher: continuous batching over the paged arena.

Example (CPU, reduced config)::

    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen2.5-32b --reduced --requests 8 --new-tokens 8
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced, list_archs
from repro.core.tasks import TenantQuota
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import Request, Server, ServerConfig


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2.5-32b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--legacy-arena", action="store_true",
                    help="A/B: run the KV arena under the paper's buggy "
                         "legacy allocator")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus text metrics on "
                         "http://127.0.0.1:PORT/metrics (0 = ephemeral)")
    ap.add_argument("--pool-watermark", type=int, default=0,
                    help="keep this many warm postprocess sandboxes via "
                         "the background refiller (0 = off)")
    ap.add_argument("--workers", type=int, default=0,
                    help="run request post-processors on this many "
                         "concurrent scheduler workers (0 = inline)")
    ap.add_argument("--heartbeat-timeout", type=float, default=0.0,
                    metavar="SECONDS",
                    help="reap postprocess workers silent this long "
                         "mid-task; their task requeues exactly once and "
                         "a replacement worker is spawned (0 = off; "
                         "needs --workers > 0)")
    ap.add_argument("--hold", type=float, default=0.0, metavar="SECONDS",
                    help="keep the process (and /metrics) alive after the "
                         "batch completes, e.g. to scrape it")
    ap.add_argument("--tenant", default="serving", metavar="NAMES",
                    help="comma-separated tenant names assigned to the "
                         "requests round-robin (admission identity; "
                         "default one 'serving' tenant)")
    ap.add_argument("--quota", type=int, default=0, metavar="SLOTS",
                    help="cap each tenant at this many concurrent decode "
                         "slots (0 = uncapped)")
    ap.add_argument("--deadline", type=float, default=None, metavar="SECONDS",
                    help="admit deadline per request: a request still "
                         "queued this long after arrival completes with "
                         "an 'expired' error instead of serving")
    ap.add_argument("--no-incremental", action="store_true",
                    help="A/B: run the old rebatching baseline (every "
                         "admit re-prefills the whole batch) instead of "
                         "per-slot incremental prefill")
    ap.add_argument("--kv-mode", default="auto",
                    choices=("auto", "paged", "dense"),
                    help="KV backing store: 'paged' routes decode through "
                         "the Pallas paged-attention kernel over the "
                         "arena's page pool; 'dense' keeps the per-slot "
                         "(batch, max_seq) reservation; 'auto' picks "
                         "paged when the model supports it")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample from the top K tokens (0 = no cap; "
                         "needs --temperature > 0)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off; needs "
                         "--temperature > 0)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request i draws with seed "
                         "base+i, so the token streams are reproducible "
                         "run to run (and across chaos evictions)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    # jitted, so each weight is drawn and cast to its dtype in one fused
    # pass instead of materialising a full-width f32 copy first
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    tenants = [t.strip() for t in args.tenant.split(",") if t.strip()] \
        or ["serving"]
    quotas = (
        {t: TenantQuota(max_tasks_in_flight=args.quota) for t in tenants}
        if args.quota > 0 else None
    )
    srv = Server(model, params, ServerConfig(
        max_batch=args.max_batch, max_seq=args.max_seq,
        mm_legacy=args.legacy_arena, pool_watermark=args.pool_watermark,
        workers=args.workers, heartbeat_timeout_s=args.heartbeat_timeout,
        incremental=not args.no_incremental, quotas=quotas,
        kv_mode=args.kv_mode,
    ))
    print(f"[serve] kv_mode: {srv.engine.kv_mode}")
    if args.metrics_port is not None:
        endpoint = srv.serve_metrics(port=args.metrics_port)
        print(f"[serve] metrics: {endpoint.url}")
    rng = np.random.default_rng(0)
    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size,
                                (int(rng.integers(4, 12)),)).astype(np.int32),
            max_new_tokens=args.new_tokens, request_id=i,
            tenant=tenants[i % len(tenants)], deadline_s=args.deadline,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed + i,
        )
        for i in range(args.requests)
    ]
    done = srv.run(reqs)
    for r in sorted(done, key=lambda r: r.request_id):
        status = f"ERROR: {r.error}" if r.error else (
            f"{len(r.tokens)} tokens "
            f"{r.tokens[:8]}{'...' if len(r.tokens) > 8 else ''}"
        )
        print(f"[serve] req {r.request_id} [{r.tenant}]: {status} "
              f"latency {r.latency_s*1e3:.0f}ms")
    print(f"[serve] arena ({'legacy' if args.legacy_arena else 'modern'}): "
          f"{json.dumps(srv.arena_report()['mm_stats'])}")
    stats = srv.engine.serving_stats()
    print(f"[serve] kv pages: allocated={stats['kv_pages_allocated_total']} "
          f"freed={stats['kv_pages_freed_total']} "
          f"resumed={stats['resumed_total']} "
          f"sampled={json.dumps(stats['sampled_tokens_total'])}")
    if args.metrics_port is not None:
        pool = {k: v for k, v in srv.dump_metrics().items()
                if k.startswith("seepp_pool")}
        print(f"[serve] pool metrics: {json.dumps(pool)}")
        if args.hold > 0:
            print(f"[serve] holding /metrics open for {args.hold:.0f}s ...")
            time.sleep(args.hold)
    srv.close()


if __name__ == "__main__":
    main()
