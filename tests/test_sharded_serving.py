"""Sharded multi-device serving: TP paged decode, DP replicas, mesh faults.

The conftest splits the host CPU into 4 simulated XLA devices
(``--xla_force_host_platform_device_count``), so every test here runs on
a real multi-device mesh without hardware.  Three planes are covered:

* **Tensor-parallel differential** — a ServingEngine on a 1/2/4-device
  mesh must stream byte-identically to the no-mesh engine for the same
  seeds (ToyLM's integer recurrence makes the psum exact), across
  kv_mode paged/dense and prefix sharing on/off; head counts that don't
  divide the mesh fall back to dense (auto) or unsharded paged
  (explicit), pinned here.
* **Kernel parity under sharding** — the paged-attention kernel sharded
  over the KV-head axis is *bit*-identical to the unsharded grid
  (per-KV-head online softmax is independent), checked against ref.py
  and the brute-force oracle including ragged lens and dead rows.
* **Replica plane** — tenant-sticky routing over data-parallel engine
  replicas, loud kills (instant re-home) and silent mesh-member death
  (heartbeat reap), with completion/ledger invariants intact.
"""

import dataclasses
import random

import jax
import numpy as np
import pytest

from helpers.invariants import (
    check_replica_invariants,
    check_serving_invariants,
)
from helpers.serving import make_engine, make_requests
from repro.configs.registry import get_reduced
from repro.core.metrics import MetricsRegistry
from repro.core.sim import SimExecutor
from repro.kernels.paged_attention.ops import (
    paged_attention,
    paged_attention_sharded,
)
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.launch.mesh import SERVING_AXIS, make_serving_mesh
from repro.models.model import build_model
from repro.runtime.fault import FailureInjector
from repro.runtime.replica import ReplicaSet
from repro.runtime.serve_loop import Request, ServerConfig, ServingEngine

from test_kernels import _paged_brute_force, _paged_case


# ---------------------------------------------------------------------------
# simulated mesh plumbing
# ---------------------------------------------------------------------------

def test_simulated_device_split():
    """The conftest's device split is what every test here assumes."""
    assert len(jax.devices()) == 4
    assert jax.default_backend() == "cpu"


def test_make_serving_mesh_sizes_and_offsets():
    for n in (1, 2, 4):
        mesh = make_serving_mesh(n)
        assert mesh.devices.size == n
        assert mesh.axis_names == (SERVING_AXIS,)
    a = make_serving_mesh(2, offset=0)
    b = make_serving_mesh(2, offset=2)
    assert not set(a.devices.flat) & set(b.devices.flat)
    with pytest.raises(ValueError):
        make_serving_mesh(4, offset=2)
    with pytest.raises(ValueError):
        make_serving_mesh(0)


# ---------------------------------------------------------------------------
# tensor-parallel differential (ToyLM: byte-exact)
# ---------------------------------------------------------------------------

def _run_toylm(mesh_devices, kv_mode, share, *, seed=5, n_requests=10):
    eng, _ = make_engine(
        seed=seed, kv_mode=kv_mode, prefix_sharing=share,
        prefix_cache_seqs=2 if share else 0, mesh_devices=mesh_devices,
    )
    rng = random.Random(seed * 31 + 7)
    reqs = make_requests(rng, n_requests, sample_prob=0.5,
                         share_prob=0.4 if share else 0.0)
    for r in reqs:
        eng.submit(r)
    eng.drain(timeout=120)
    check_serving_invariants(
        eng, reqs, ctx=f"mesh={mesh_devices} kv={kv_mode} share={share}")
    return {r.request_id: (list(r.tokens), r.error) for r in reqs}, eng


@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("kv_mode", ["paged", "dense"])
def test_mesh_streams_byte_identical(kv_mode, share):
    """4-device (and 1-, 2-device) token streams == the no-mesh run.

    ToyLM TP shards the d axis and the only cross-shard op is an int32
    logits psum, so this is byte equality — same bar as chaos replay —
    across greedy and sampled requests, paged and dense, sharing on/off.
    """
    base, eng0 = _run_toylm(0, kv_mode, share)
    assert eng0.tp_shards == 1
    for n in (1, 2, 4):
        got, eng = _run_toylm(n, kv_mode, share)
        assert got == base, f"mesh={n} diverged from single-device run"
        # dense mode has no page pool to shard: the mesh is ignored
        assert eng.tp_shards == (n if kv_mode == "paged" else 1)
        assert eng.serving_stats()["tp_shards"] == eng.tp_shards


def test_tp_fallback_when_heads_dont_divide():
    """ToyLM d=8 on a 3-device mesh: auto falls back to *dense*, an
    explicit paged request falls back to an unsharded pool — both trace
    the decision and both stream identically to the no-mesh run."""
    base, _ = _run_toylm(0, "auto", False)

    eng, _ = make_engine(seed=5, kv_mode="auto", mesh_devices=3)
    assert eng.kv_mode == "dense"
    assert eng.mesh is None and eng.tp_shards == 1
    assert any("tp_fallback" in line for line in eng.trace())

    got, eng3 = _run_toylm(3, "auto", False)
    assert got == base
    assert eng3.kv_mode == "dense"

    got_p, eng_p = _run_toylm(3, "paged", False)
    assert got_p == base
    assert eng_p.kv_mode == "paged" and eng_p.tp_shards == 1
    assert any("tp_fallback" in line for line in eng_p.trace())


def test_arena_shard_stats():
    eng, _ = make_engine(seed=2, kv_mode="paged", mesh_devices=2)
    rng = random.Random(9)
    reqs = make_requests(rng, 4)
    for r in reqs:
        eng.submit(r)
    eng.drain(timeout=60)
    stats = eng.kv.shard_stats()
    assert stats["tp_shards"] == 2
    assert stats["live_pages_per_shard"] == 0
    assert stats["pages_allocated_per_shard"] == eng.kv.pages_allocated
    assert stats["page_bytes_per_shard"] * 2 == eng.kv.arena.page_bytes


# ---------------------------------------------------------------------------
# kernel parity under sharding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4])
def test_paged_attention_sharded_bit_exact(n):
    """Head-sharded kernel == unsharded kernel, bit for bit, and both
    match ref.py and the brute-force oracle — ragged lens, pages ending
    mid-page."""
    q, kp, vp, table, lens = _paged_case(
        3, 4, 2, 16, page=8, P=24, lens=[5, 17, 40])
    mesh = make_serving_mesh(n)
    out = paged_attention_sharded(q, kp, vp, table, lens, scale=0.25,
                                  mesh=mesh, interpret=True)
    base = paged_attention(q, kp, vp, table, lens, scale=0.25,
                           interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(base)), (
        f"sharded kernel (n={n}) not bit-identical to unsharded"
    )
    ref = paged_attention_ref(q, kp, vp, np.asarray(table),
                              np.asarray(lens), scale=0.25)
    brute = _paged_brute_force(q, kp, vp, table, lens, 0.25)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out, np.float32), brute,
                               rtol=1e-4, atol=1e-4)


def test_paged_attention_sharded_dead_rows():
    """A dead slot (len 0, all--1 table row) stays exactly zero on every
    shard, and live rows ignore trailing -1 padding."""
    q, kp, vp, table, lens = _paged_case(
        3, 2, 2, 16, page=8, P=16, lens=[11, 5, 16])
    lens = lens.copy()
    lens[1] = 0
    table[1, :] = -1
    wide = np.pad(table, ((0, 0), (0, 5)), constant_values=-1)
    mesh = make_serving_mesh(2)
    out = np.asarray(paged_attention_sharded(
        q, kp, vp, wide, lens, scale=0.25, mesh=mesh, interpret=True),
        np.float32)
    assert np.all(np.isfinite(out))
    assert np.all(out[1] == 0.0)
    brute = _paged_brute_force(q, kp, vp, table, lens, 0.25)
    np.testing.assert_allclose(out[[0, 2]], brute[[0, 2]],
                               rtol=1e-4, atol=1e-4)


def test_paged_attention_sharded_fallback_non_divisible():
    """K=3 KV heads on a 2-device mesh can't shard a head group: the
    wrapper must fall back to the unsharded kernel, not mis-slice."""
    q, kp, vp, table, lens = _paged_case(
        2, 3, 2, 16, page=8, P=16, lens=[9, 20])
    mesh = make_serving_mesh(2)
    out = paged_attention_sharded(q, kp, vp, table, lens, scale=0.25,
                                  mesh=mesh, interpret=True)
    base = paged_attention(q, kp, vp, table, lens, scale=0.25,
                           interpret=True)
    assert np.array_equal(np.asarray(out), np.asarray(base))
    none_mesh = paged_attention_sharded(q, kp, vp, table, lens, scale=0.25,
                                        mesh=None, interpret=True)
    assert np.array_equal(np.asarray(none_mesh), np.asarray(base))


# ---------------------------------------------------------------------------
# transformer under TP (bit-exact decode step + engine smoke)
# ---------------------------------------------------------------------------

_TP_MODEL = {}


def _tp_transformer():
    """A reduced qwen2.5 reshaped to 4 KV heads so TP-4 is legal (the
    stock reduction has K=1, which is the *fallback* case below)."""
    if not _TP_MODEL:
        cfg = dataclasses.replace(get_reduced("qwen2.5-32b"),
                                  num_heads=4, num_kv_heads=4, head_dim=16)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        _TP_MODEL["model"] = model
        _TP_MODEL["params"] = params
    return _TP_MODEL["model"], _TP_MODEL["params"]


@pytest.mark.parametrize("n", [2, 4])
def test_transformer_decode_step_sharded_bit_exact(n):
    """shard_map'd paged_decode_step == plain jit, bit for bit, for a
    fixed pool: per-KV-head attention is shard-local and the wo psum on
    a replicated-input matmul reduces the *same* partial products XLA
    would sum locally.  (Engine-level float divergence comes from GSPMD
    prefill reassociation, not the decode step — pinned exact here.)"""
    from repro.parallel.sharding import serving_tp_shardings
    from jax.sharding import PartitionSpec as P

    model, params = _tp_transformer()
    assert model.tp_supported(n)
    store = model.init_paged_state(16, 4)
    toks = jax.numpy.asarray(
        np.random.default_rng(1).integers(
            0, model.cfg.vocab_size, (1, 6)), np.int32)
    rows, _ = model.paged_prefill(params, toks)
    store = model.paged_write_prefill(
        store, rows,
        np.asarray([0, 0, 0, 0, 1, 1]), np.asarray([0, 1, 2, 3, 0, 1]))
    table = np.asarray([[0, 1, -1, -1], [2, 3, -1, -1]], np.int32)
    pos = np.asarray([6, 0], np.int32)
    tok = np.asarray([5, 7], np.int32)

    base_pool, base_logits = jax.jit(model.paged_decode_step)(
        params, store, tok, table, pos)

    mesh = make_serving_mesh(n)
    pspecs = model.tp_param_specs(params)
    poolspecs = model.tp_pool_specs(store)
    sp = jax.device_put(params, serving_tp_shardings(mesh, pspecs))
    sstore = jax.device_put(store, serving_tp_shardings(mesh, poolspecs))
    rep = P()
    fn = jax.jit(jax.shard_map(
        model.paged_decode_step, mesh=mesh,
        in_specs=(pspecs, poolspecs, rep, rep, rep),
        out_specs=(poolspecs, rep), check_vma=False))
    sh_pool, sh_logits = fn(sp, sstore, tok, table, pos)
    assert np.array_equal(np.asarray(sh_logits), np.asarray(base_logits))
    for k in ("k_pages", "v_pages"):
        assert np.array_equal(np.asarray(sh_pool[k]),
                              np.asarray(base_pool[k])), k


def test_transformer_sharded_engine_smoke():
    """End-to-end: a real transformer serves paged TP-4 — requests
    complete, the plane drains clean, and tp_shards reports the width."""
    model, params = _tp_transformer()
    ex = SimExecutor(seed=4)
    cfg = ServerConfig(max_batch=2, max_seq=32, tokens_per_page=4,
                       step_time_s=0.01, kv_mode="paged",
                       prefix_sharing=True)
    eng = ServingEngine(model, params, cfg, executor=ex,
                        mesh=make_serving_mesh(4))
    assert eng.kv_mode == "paged" and eng.tp_shards == 4
    rng = random.Random(21)
    reqs = []
    for i in range(4):
        prompt = np.asarray(
            [rng.randrange(model.cfg.vocab_size) for _ in range(4)],
            np.int32)
        reqs.append(Request(prompt=prompt, max_new_tokens=4, request_id=i,
                            tenant="t", seed=rng.randrange(1 << 31)))
    for r in reqs:
        eng.submit(r)
    eng.drain(timeout=300)
    check_serving_invariants(eng, reqs, ctx="transformer tp4")
    assert all(r.error is None and len(r.tokens) == 4 for r in reqs)


def test_transformer_auto_falls_back_to_dense():
    """Stock reduced qwen2.5 has 1 KV head: 1 % 4 != 0, so a 4-device
    mesh under kv_mode=auto must serve dense rather than mis-shard."""
    cfg_arch = get_reduced("qwen2.5-32b")
    model = build_model(cfg_arch)
    assert model.supports_paged_decode and not model.tp_supported(4)
    params = model.init(jax.random.PRNGKey(0))
    ex = SimExecutor(seed=4)
    cfg = ServerConfig(max_batch=2, max_seq=32, tokens_per_page=4,
                       step_time_s=0.01, kv_mode="auto")
    eng = ServingEngine(model, params, cfg, executor=ex,
                        mesh=make_serving_mesh(4))
    assert eng.kv_mode == "dense"
    assert eng.mesh is None and eng.tp_shards == 1
    assert any("tp_fallback" in line for line in eng.trace())


# ---------------------------------------------------------------------------
# data-parallel replicas
# ---------------------------------------------------------------------------

def _make_set(*, dp=2, tp=0, seed=3, heartbeat_timeout_s=0.05):
    ex = SimExecutor(seed=seed)
    engines = []
    for i in range(dp):
        kw = dict(executor=ex, kv_mode="paged", prefix_cache_seqs=2)
        if tp:
            kw.update(mesh_devices=tp, mesh_offset=i * tp)
        eng, _ = make_engine(**kw)
        engines.append(eng)
    return ReplicaSet(engines,
                      heartbeat_timeout_s=heartbeat_timeout_s), ex


def _run_set(plan=None, *, dp=2, tp=0, n_requests=12, seed=3,
             workload_seed=11):
    rs, ex = _make_set(dp=dp, tp=tp, seed=seed)
    rng = random.Random(workload_seed)
    reqs = make_requests(rng, n_requests, sample_prob=0.5, share_prob=0.4)
    if plan:
        FailureInjector(**plan).arm_replicas(ex, rs)
    for r in reqs:
        rs.submit(r)
    rs.drain(timeout=180)
    check_replica_invariants(rs, reqs, ctx=f"plan={plan} dp={dp} tp={tp}")
    return {r.request_id: (list(r.tokens), r.error) for r in reqs}, rs


def test_replica_routing_sticky_and_deterministic():
    def homes():
        rs, _ = _make_set()
        rng = random.Random(11)
        for r in make_requests(rng, 6):
            rs.submit(r)
        return rs, {t: rs.route(t) for t in ("alice", "bob", "carol")}

    rs, first = homes()
    _, second = homes()
    # routing is a pure function of (home map, load): replays agree
    assert first == second
    # sticky: a tenant's home survives later load shifts
    assert rs.route("alice") == first["alice"]
    # and the homed tenants spread across replicas (load-balanced at
    # submit time, not all piled on replica 0)
    assert len(set(first.values())) > 1


def test_replica_set_matches_single_engine():
    """Splitting a workload over 2 replicas changes *where* requests
    run, never *what* they decode: streams are byte-identical to one
    engine serving everything (sampling is (seed, index)-keyed)."""
    eng, _ = make_engine(seed=3, kv_mode="paged", prefix_cache_seqs=2)
    rng = random.Random(11)
    reqs = make_requests(rng, 12, sample_prob=0.5, share_prob=0.4)
    for r in reqs:
        eng.submit(r)
    eng.drain(timeout=120)
    base = {r.request_id: (list(r.tokens), r.error) for r in reqs}

    got, rs = _run_set()
    assert got == base
    stats = rs.replica_stats()
    assert stats["replicas_alive"] == 2
    assert sum(p["completed"] for p in stats["per_replica"]) == 12


def test_replica_set_dp_times_tp():
    """2 replicas × 2-way TP carve disjoint sub-meshes out of the 4
    simulated devices; streams still match the plain DP run."""
    base, _ = _run_set()
    got, rs = _run_set(tp=2)
    assert got == base
    assert all(p["tp_shards"] == 2 for p in rs.replica_stats()["per_replica"])


def test_one_device_replicas_each_on_own_device():
    """4 one-device replicas: each replica's params and page pool live
    on its own device (not all on the default one), and streams still
    match the plain DP run."""
    base, _ = _run_set()
    got, rs = _run_set(dp=4, tp=1)
    assert got == base
    placed = []
    for eng in rs.replicas:
        assert eng.tp_shards == 1
        on = {d for a in jax.tree.leaves((eng.params, eng.kv.store))
              for d in a.devices()}
        assert len(on) == 1
        placed.append(on.pop())
    assert len(set(placed)) == 4


def test_kill_replica_rehomes_and_completes():
    base, _ = _run_set()
    got, rs = _run_set(plan={"kill_replica_at_t": {0.07: [0]}})
    assert rs.replica_kills == 1
    assert rs.rehomed_total > 0
    assert rs.replicas[0].dead
    assert rs.replicas[0].kv.live_pages() == 0
    # every request still completes with the same byte stream
    for rid, (toks, err) in got.items():
        if err is None and base[rid][1] is None:
            assert toks == base[rid][0], rid


def test_mesh_member_kill_heartbeat_reap():
    """A silent mesh-member death strands the replica until the
    heartbeat monitor (virtual clock) times it out; the reap evacuates,
    survivors absorb the work, and a replay is byte-identical."""
    base, _ = _run_set()
    plan = {"kill_mesh_member_at_t": {0.03: [0]}}
    got, rs = _run_set(plan=plan)
    assert rs.mesh_member_kills == 1
    assert rs.heartbeat_reaps == 1
    assert rs.rehomed_total > 0
    assert rs.replicas[0].dead
    got2, rs2 = _run_set(plan=plan)
    assert got == got2, "mesh-kill run not replay-deterministic"
    assert rs2.heartbeat_reaps == 1
    for rid, (toks, err) in got.items():
        if err is None and base[rid][1] is None:
            assert toks == base[rid][0], rid


def test_replica_metrics_families():
    _, rs = _run_set(plan={"kill_mesh_member_at_t": {0.03: [0]}})
    reg = MetricsRegistry().register_replicas(rs)
    text = reg.render()
    for name in ("seepp_serving_replica_alive",
                 "seepp_serving_replica_tp_shards",
                 "seepp_serving_replica_rehomed_total",
                 "seepp_serving_mesh_members_dead",
                 "seepp_serving_mesh_heartbeat_reaps_total"):
        assert name in text, name
    dump = reg.dump()
    assert dump["seepp_serving_mesh_heartbeat_reaps_total"][""] == 1
    alive = dump["seepp_serving_replica_alive"]
    assert alive['{replica="0"}'] == 0
    assert alive['{replica="1"}'] == 1
