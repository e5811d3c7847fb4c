"""Bring-up smoke of the serving main path on a TPU v5e.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: TP-4, sharded kernel, replicas

One chip: qwen2.5-32b at its published widths cut to 4 layers
(``repro.configs.qwen2_5_32b.CHIP``), random bf16 weights from ``--seed``,
served through ``Server`` / ``ServerConfig`` with ``kv_mode="auto"``, which
resolves to the paged arena.  Eight requests of three prompt lengths, two
of them sharing a 256-token prefix, generate 32 tokens each.  The run
checks that every request completes with its token count and no error,
that every KV page allocated is freed, that the compiled decode program
holds the Pallas kernel (``tpu_custom_call``), and that the kernel agrees
with ``paged_attention_ref`` at these widths.

Four chips run only what exists across chips: a TP-4 ``ServingEngine``
over ``make_serving_mesh(4)`` serving the same requests, with one of its
decode steps compared against the one-chip step on the same pool state;
the head-sharded kernel against the unsharded one; and a ``ReplicaSet``
of four one-chip replicas, each on its own chip.

Times printed are smoke timings of one cold run, not benchmark numbers.
The last line of stdout is ``{"ok": true, "device": {...}}``.  With no TPU,
or when any check fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs.qwen2_5_32b import CHIP  # noqa: E402
from repro.core.sim import ThreadExecutor  # noqa: E402
from repro.kernels.paged_attention.ops import (  # noqa: E402
    paged_attention,
    paged_attention_sharded,
)
from repro.kernels.paged_attention.ref import paged_attention_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.parallel.sharding import serving_tp_shardings  # noqa: E402
from repro.runtime import Request, Server, ServerConfig, ServingEngine  # noqa: E402
from repro.runtime.replica import ReplicaSet  # noqa: E402

PROMPT_LENS = (160, 512, 1000)
SHARED_PREFIX = 256
NEW_TOKENS = 32
MAX_BATCH = 8
POOL_PAGES = 4096
MAX_SEQ = max(PROMPT_LENS) + NEW_TOKENS

#: kernel vs the f32 reference: the kernel writes its output in bf16
#: (8 mantissa bits, 2^-8 = 3.9e-3 relative), and the MXU may take the
#: f32 score and probability dots in one bf16 pass, which rounds the
#: scaled q and the probabilities once more each.  2e-2 is about five
#: bf16 ulps at |out| ~ 1.
KERNEL_TOL = 2e-2
#: TP-4 vs one-chip decode logits, relative L2 over the live rows: the
#: TP step sums four bf16 partial products of ``wo`` in a psum where the
#: one-chip step accumulates one matmul in f32, a bf16 rounding per layer
#: that the residual stream carries through all four layers and the
#: output head.  2e-2 is five bf16 ulps.
TP_LOGITS_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(count: int) -> list:
    """The first ``count`` TPU devices; exits non-zero on anything else."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees "
                 f"{devices[0].platform!r}); refusing to run elsewhere")
    if len(devices) < count:
        sys.exit(f"chip_smoke: --chips {count} needs {count} TPU devices, "
                 f"found {len(devices)}")
    return devices[:count]


def make_requests(vocab: int, seed: int) -> list:
    """Eight requests over three prompt lengths; the last one shares the
    first ``SHARED_PREFIX`` tokens of the third (its donor)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(MAX_BATCH):
        n = PROMPT_LENS[i % len(PROMPT_LENS)]
        reqs.append(Request(
            prompt=rng.integers(0, vocab, (n,)).astype(np.int32),
            max_new_tokens=NEW_TOKENS, request_id=i, seed=seed + i,
        ))
    donor, sharer = reqs[2].prompt, reqs[-1].prompt
    sharer[:SHARED_PREFIX] = donor[:SHARED_PREFIX]
    if sharer[SHARED_PREFIX] == donor[SHARED_PREFIX]:
        sharer[SHARED_PREFIX] = (donor[SHARED_PREFIX] + 1) % vocab
    return reqs


def server_config() -> ServerConfig:
    return ServerConfig(max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                        kv_pool_pages=POOL_PAGES, kv_mode="auto")


def serve(submit, step, drain, reqs) -> None:
    """The first seven requests, one engine tick (admits and prefills
    them, so the donor's pages are resident), then the sharer."""
    for r in reqs[:-1]:
        submit(r)
    step()
    submit(reqs[-1])
    drain()


def check_served(engine, reqs, *, shared: bool) -> dict:
    for r in reqs:
        check(r.error is None, f"request {r.request_id} failed: {r.error}")
        check(len(r.tokens) == NEW_TOKENS,
              f"request {r.request_id} made {len(r.tokens)} tokens, "
              f"not {NEW_TOKENS}")
    stats = engine.serving_stats()
    alloc = stats["kv_pages_allocated_total"]
    freed = stats["kv_pages_freed_total"]
    check(alloc == freed, f"kv pages allocated {alloc} != freed {freed}")
    check(engine.kv.live_pages() == 0, "kv pages still live after drain")
    if shared:
        check(stats["prefix_hits_total"] == 1
              and stats["prefix_prefill_tokens_saved_total"] == SHARED_PREFIX,
              f"prefix sharing did not map the {SHARED_PREFIX}-token "
              f"prefix: hits={stats['prefix_hits_total']} saved="
              f"{stats['prefix_prefill_tokens_saved_total']}")
    return {
        "completed": sum(r.done and r.error is None for r in reqs),
        "pages_allocated": alloc, "pages_freed": freed,
        "prefix_hits": stats["prefix_hits_total"],
        "prefix_tokens_saved": stats["prefix_prefill_tokens_saved_total"],
    }


def decode_program_has_kernel(engine) -> tuple:
    """Compile the engine's paged decode step ahead of time; returns
    (whether its HLO holds ``tpu_custom_call``, compile seconds)."""
    B = engine.cfg.max_batch
    table = jnp.full((B, 64), -1, jnp.int32)
    zeros = jnp.zeros((B,), jnp.int32)
    t0 = time.perf_counter()
    compiled = engine._decode_paged.lower(
        engine.params, engine.kv.store, zeros, table, zeros).compile()
    return "tpu_custom_call" in compiled.as_text(), time.perf_counter() - t0


def kernel_inputs(cfg, seed: int, *, batch: int = MAX_BATCH,
                  pages: int = POOL_PAGES, page: int = 16, max_pages: int = 64):
    """Random bf16 q and page pool at ``cfg``'s widths, ragged lengths
    (one token, mid-page ends, a full table) and scattered pages."""
    rng = np.random.default_rng(seed)
    K, hd = cfg.num_kv_heads, cfg.hd
    q = rng.standard_normal((batch, cfg.num_heads, hd), np.float32)
    kp = rng.standard_normal((pages, page, K, hd), np.float32)
    vp = rng.standard_normal((pages, page, K, hd), np.float32)
    lens = np.asarray([1, 17, 160, 512, 700, 1000, max_pages * page, 33],
                      np.int32)[:batch]
    table = rng.permutation(pages)[:batch * max_pages].reshape(
        batch, max_pages).astype(np.int32)
    table[np.arange(max_pages)[None, :] * page >= lens[:, None]] = -1
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(kp, jnp.bfloat16),
            jnp.asarray(vp, jnp.bfloat16), jnp.asarray(table),
            jnp.asarray(lens))


def check_kernel(cfg, seed: int) -> float:
    """The paged kernel vs the f32 reference on the same device inputs;
    returns the max abs error."""
    q, kp, vp, table, lens = kernel_inputs(cfg, seed)
    scale = 1.0 / float(np.sqrt(cfg.hd))
    out = paged_attention(q, kp, vp, table, lens, scale=scale)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_attention_ref, static_argnames="scale")(
            q.astype(jnp.float32), kp, vp, table, lens, scale=scale)
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    check(np.all(np.isfinite(out)), "kernel output not finite")
    check(np.allclose(out, ref, atol=KERNEL_TOL, rtol=KERNEL_TOL),
          f"kernel vs ref.py: max abs error {err:.3e} beyond {KERNEL_TOL}")
    return err


def init_params(model, seed: int, sharding=None):
    """Random weights drawn by a jitted init: each leaf is drawn and cast
    in one fused pass, so no full-width f32 copy sits on the device."""
    return jax.jit(model.init, out_shardings=sharding)(
        jax.random.PRNGKey(seed))


def one_chip(cfg, seed: int = 0) -> dict:
    """Serve the eight requests through ``Server`` on the default device
    and check the kernel; returns what was checked and measured."""
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model, seed))
    init_s = time.perf_counter() - t0
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    srv = Server(model, params, server_config())
    try:
        check(srv.engine.kv_mode == "paged",
              f"kv_mode auto resolved to {srv.engine.kv_mode!r}")
        has_kernel, compile_s = decode_program_has_kernel(srv.engine)
        on_tpu = jax.default_backend() == "tpu"
        check(has_kernel == on_tpu,
              f"decode HLO tpu_custom_call={has_kernel} on "
              f"{jax.default_backend()}: the kernel must be compiled on "
              "a TPU and interpreted on the CPU")
        reqs = make_requests(cfg.vocab_size, seed)
        t0 = time.perf_counter()
        serve(srv.submit, srv.step, srv.drain, reqs)
        serve_s = time.perf_counter() - t0
        report = check_served(srv.engine, reqs, shared=True)
    finally:
        srv.close()
    t0 = time.perf_counter()
    report["kernel_max_abs_err"] = check_kernel(cfg, seed)
    report.update(
        params=n_params, init_s=init_s, decode_compile_s=compile_s,
        serve_s=serve_s, kernel_s=time.perf_counter() - t0,
        decode_has_tpu_custom_call=has_kernel,
    )
    return report


def free(*trees) -> None:
    for a in jax.tree.leaves(trees):
        if not a.is_deleted():
            a.delete()


def tp4_vs_one_chip(cfg, seed: int, devices) -> dict:
    """(a) the TP-4 engine serves the eight requests; its fifth decode
    step is replayed by the one-chip step on the same pool state."""
    model = build_model(cfg)
    mesh = make_serving_mesh(len(devices))
    pspecs = model.tp_param_specs(
        jax.eval_shape(model.init, jax.random.PRNGKey(seed)))
    params = init_params(model, seed, serving_tp_shardings(mesh, pspecs))
    eng = ServingEngine(model, params, server_config(), mesh=mesh)
    check(eng.kv_mode == "paged" and eng.tp_shards == len(devices),
          f"TP engine: kv_mode={eng.kv_mode} tp_shards={eng.tp_shards}")
    step_fn, calls, seen = eng._decode_paged, [0], {}

    def spy(params, store, last, table, pos):
        calls[0] += 1
        if calls[0] != 5:
            return step_fn(params, store, last, table, pos)
        seen.update(store=jax.device_get(store), last=np.asarray(last),
                    table=np.asarray(table), pos=np.asarray(pos))
        store, logits = step_fn(params, store, last, table, pos)
        seen["logits"] = np.asarray(logits, np.float32)
        return store, logits

    eng._decode_paged = spy
    reqs = make_requests(cfg.vocab_size, seed)
    t0 = time.perf_counter()
    serve(eng.submit, eng.step, eng.drain, reqs)
    report = {"serve_s": time.perf_counter() - t0,
              **check_served(eng, reqs, shared=True)}
    check("logits" in seen, "TP engine ran fewer than 5 decode steps")
    free(params, eng.params, eng.kv.store)

    one = SingleDeviceSharding(devices[0])
    params = init_params(model, seed, one)
    store = jax.device_put(seen["store"], one)
    _, ref = jax.jit(model.paged_decode_step)(
        params, store, seen["last"], seen["table"], seen["pos"])
    ref = np.asarray(ref, np.float32)
    free(params, store)
    live = seen["table"][:, 0] >= 0
    tp = seen["logits"][live]
    rel = float(np.linalg.norm(tp - ref[live]) / np.linalg.norm(ref[live]))
    report.update(live_rows=int(live.sum()), logits_rel_l2=rel,
                  logits_max_abs=float(np.max(np.abs(tp - ref[live]))))
    check(np.all(np.isfinite(tp)), "TP logits not finite")
    check(rel <= TP_LOGITS_TOL,
          f"TP-{len(devices)} vs one-chip logits: relative L2 {rel:.3e} "
          f"beyond {TP_LOGITS_TOL}")
    return report


def sharded_kernel(cfg, seed: int, devices) -> dict:
    """(b) the head-sharded kernel is bit-identical to the unsharded one."""
    q, kp, vp, table, lens = kernel_inputs(cfg, seed)
    scale = 1.0 / float(np.sqrt(cfg.hd))
    base = np.asarray(paged_attention(q, kp, vp, table, lens, scale=scale))
    out = np.asarray(paged_attention_sharded(
        q, kp, vp, table, lens, scale=scale,
        mesh=make_serving_mesh(len(devices))))
    check(np.array_equal(out, base),
          "sharded kernel not bit-identical to the unsharded kernel")
    return {"bit_identical": True}


def replicas(cfg, seed: int, devices) -> dict:
    """(c) one one-chip replica per device behind a ``ReplicaSet``: each
    replica's params and pool live on its own chip."""
    model = build_model(cfg)
    ex = ThreadExecutor()
    engines = []
    for i, dev in enumerate(devices):
        params = init_params(model, seed, SingleDeviceSharding(dev))
        engines.append(ServingEngine(
            model, params, server_config(), executor=ex,
            mesh=make_serving_mesh(1, offset=i)))
    # the set reaps a replica whose heartbeat is older than this on the
    # wall clock, and each replica's first steps compile for seconds
    rs = ReplicaSet(engines, heartbeat_timeout_s=600.0)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, (PROMPT_LENS[0],))
                    .astype(np.int32), max_new_tokens=8, request_id=i,
                    tenant=f"tenant{i}", seed=seed + i)
            for i in range(len(devices))]
    homes = []
    for r in reqs:
        homes.append(rs.route(r.tenant))
        rs.submit(r)
    rs.drain()
    placed = []
    for eng in engines:
        params_on = {d for a in jax.tree.leaves(eng.params)
                     for d in a.devices()}
        pool_on = {d for a in jax.tree.leaves(eng.kv.store)
                   for d in a.devices()}
        check(len(params_on) == 1 and params_on == pool_on,
              f"replica params on {params_on}, pool on {pool_on}")
        placed.append(params_on.pop())
    check(len(set(placed)) == len(devices),
          f"replicas share devices: {placed}")
    check(all(r.error is None and len(r.tokens) == 8 for r in reqs),
          "replica requests failed")
    per = rs.replica_stats()["per_replica"]
    check(all(p["completed"] == 1 for p in per),
          f"requests not spread one per replica: {per}")
    for eng in engines:
        free(eng.params, eng.kv.store)
    return {"devices": [d.id for d in placed], "homes": homes}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    cache = enable_compile_cache()
    d0 = devices[0]
    say(f"device_kind={d0.device_kind} count={len(devices)} "
        f"compile_cache={cache}")
    say(f"config {CHIP.arch_id}: d_model={CHIP.d_model} heads={CHIP.num_heads}"
        f" kv_heads={CHIP.num_kv_heads} d_ff={CHIP.d_ff} "
        f"vocab={CHIP.vocab_size} layers={CHIP.num_layers}")
    if args.chips == 1:
        r = one_chip(CHIP, args.seed)
        say(f"params={r['params']} init {r['init_s']:.2f}s; decode program "
            f"compile {r['decode_compile_s']:.2f}s, tpu_custom_call="
            f"{r['decode_has_tpu_custom_call']}")
        say(f"served {r['completed']}/{MAX_BATCH} requests x {NEW_TOKENS} "
            f"tokens in {r['serve_s']:.2f}s wall (compiles included); "
            f"kv pages allocated={r['pages_allocated']} freed="
            f"{r['pages_freed']}; prefix hits={r['prefix_hits']} tokens "
            f"saved={r['prefix_tokens_saved']}")
        say(f"kernel vs ref.py: max abs error {r['kernel_max_abs_err']:.3e}"
            f" (tol {KERNEL_TOL}) in {r['kernel_s']:.2f}s")
    else:
        a = tp4_vs_one_chip(CHIP, args.seed, devices)
        say(f"(a) TP-{len(devices)} served {a['completed']}/{MAX_BATCH} in "
            f"{a['serve_s']:.2f}s wall; pages allocated="
            f"{a['pages_allocated']} freed={a['pages_freed']}; decode logits"
            f" vs one chip over {a['live_rows']} live rows: relative L2 "
            f"{a['logits_rel_l2']:.3e} (tol {TP_LOGITS_TOL}), max abs "
            f"{a['logits_max_abs']:.3e}")
        sharded_kernel(CHIP, args.seed, devices)
        say("(b) head-sharded kernel bit-identical to the unsharded kernel")
        c = replicas(CHIP, args.seed, devices)
        say(f"(c) {len(devices)} one-chip replicas on devices {c['devices']}"
            f", requests routed to replicas {c['homes']}")
    peak = (d0.memory_stats() or {}).get("peak_bytes_in_use")
    say(f"device 0 peak bytes in use: {peak}")
    say("smoke timings are one cold run each, not benchmark numbers")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
