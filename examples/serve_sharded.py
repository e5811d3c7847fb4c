"""Sharded serving: tensor-parallel decode + data-parallel replicas.

On the CPU (``JAX_PLATFORMS=cpu``) splits the host into 4 simulated
XLA devices; on a four-chip TPU host the chips are the mesh.  Demos the
two sharding planes:

1. **Tensor parallel** — one engine whose attention params and KV page
   pool are sharded over a 4-device ``("model",)`` mesh; every decode
   step runs the paged-attention kernel per-shard and ``psum``s the
   logits.  The stream is the same stream, just computed across shards.

2. **DP x TP** — a :class:`ReplicaSet` of two engines, each TP-2 over a
   *disjoint* sub-mesh (devices 0-1 / 2-3), behind tenant-sticky
   routing.  Mid-run a mesh member under replica 0 dies *silently*; the
   heartbeat monitor reaps it on the executor's virtual clock and every
   stranded request re-homes to replica 1 and completes — sampling is
   keyed by (seed, token index), so re-homed streams stay byte-identical
   to an undisturbed run.

    JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_sharded.py
"""

import dataclasses

from repro.launch.mesh import make_serving_mesh, simulate_host_devices

# must run before the first computation: XLA reads the device-count
# flag once, at backend initialization (CPU backend only)
simulate_host_devices(4)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_reduced  # noqa: E402
from repro.core import SimExecutor  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.runtime import Request, ServingEngine  # noqa: E402
from repro.runtime.replica import ReplicaSet  # noqa: E402
from repro.runtime.serve_loop import ServerConfig  # noqa: E402


def tp_model():
    # a TP-capable head layout: 4 query heads over 4 KV heads, so mesh
    # sizes 1/2/4 all divide both head axes (the stock reduced config
    # has a single KV head and would auto-fall back to dense)
    cfg = dataclasses.replace(
        get_reduced("qwen2.5-32b"), num_heads=4, num_kv_heads=4, head_dim=16,
    )
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


def requests(vocab, n, *, tenants=("alice",), seed=0, base_id=0):
    rng = np.random.default_rng(seed)
    return [Request(
        prompt=rng.integers(0, vocab, (8,)).astype(np.int32),
        max_new_tokens=6, request_id=base_id + i,
        tenant=tenants[i % len(tenants)],
    ) for i in range(n)]


def demo_tensor_parallel():
    cfg, model, params = tp_model()
    engine = ServingEngine(
        model, params,
        ServerConfig(max_batch=2, max_seq=48, kv_mode="paged"),
        mesh=make_serving_mesh(4),
    )
    reqs = requests(cfg.vocab_size, 4)
    for r in reqs:
        engine.submit(r)
    engine.drain()
    stats = engine.serving_stats()
    print(f"[tp] {len(reqs)} requests over {stats['tp_shards']} shards: "
          f"{sum(stats['completed_total'].values())} completed, "
          f"0 errors = {all(r.error is None for r in reqs)}")
    assert engine.kv.shard_stats()["live_pages_per_shard"] == 0


def demo_dp_times_tp():
    cfg, model, params = tp_model()
    sim = SimExecutor(seed=0)
    replicas = [ServingEngine(
        model, params,
        ServerConfig(max_batch=2, max_seq=48, kv_mode="paged",
                     step_time_s=0.01),
        executor=sim,
        mesh=make_serving_mesh(2, offset=i * 2),   # disjoint sub-meshes
    ) for i in range(2)]
    rs = ReplicaSet(replicas, heartbeat_timeout_s=0.05)

    reqs = requests(cfg.vocab_size, 8,
                    tenants=("alice", "bob", "carol"), seed=1)
    for r in reqs:
        rs.submit(r)
    homes = {t: rs.route(t) for t in ("alice", "bob", "carol")}
    print(f"[dp] tenant homes: {homes}")

    for _ in range(3):                             # a few steps of progress
        rs.step()
        sim.sleep(rs.step_time_s)
    rs.kill_mesh_member(0)                         # silent device death
    rs.drain()

    st = rs.replica_stats()
    print(f"[dp] mesh member died: heartbeat reaps={st['heartbeat_reaps']}, "
          f"re-homed={st['rehomed_total']}, orphaned={st['orphaned']}")
    print(f"[dp] all {len(reqs)} requests completed: "
          f"{all(r.done and r.error is None for r in reqs)}")
    for i, p in enumerate(st["per_replica"]):
        print(f"     replica {i}: alive={p['alive']} "
              f"tp_shards={p['tp_shards']} completed={p['completed']} "
              f"live_pages={p['live_pages']}")


if __name__ == "__main__":
    demo_tensor_parallel()
    demo_dp_times_tp()
