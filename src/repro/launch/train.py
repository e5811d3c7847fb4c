"""Production training launcher.

Builds the mesh from available devices (or the production 16×16 via
``--dryrun-devices``), shards params/optimizer/batch per
``repro.parallel.sharding``, and runs the fault-tolerant trainer with
checkpointing and sandboxed data transforms.

Example (CPU, reduced config)::

    PYTHONPATH=src python -m repro.launch.train \
        --arch gemma2-9b --reduced --steps 100 --global-batch 8 --seq 64
"""

from __future__ import annotations

import argparse

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_reduced, list_archs
from repro.core.gofer import Gofer
from repro.data import DataConfig, Loader, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model, mesh_context
from repro.optim import ScheduleConfig
from repro.runtime import (
    HeartbeatMonitor,
    StragglerDetector,
    Trainer,
    TrainerConfig,
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="gemma2-9b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_host_mesh()
    print(f"[train] arch={cfg.arch_id} params≈{cfg.param_count():,} "
          f"mesh={dict(mesh.shape)}")

    dc = DataConfig(global_batch=args.global_batch, seq_len=args.seq,
                    vocab_size=cfg.vocab_size)
    loader = Loader(SyntheticLM(dc), dc)
    ckpt = CheckpointManager(
        Gofer.for_root("ckpt", args.ckpt_dir, write=True), keep=3)
    trainer = Trainer(
        model, loader,
        TrainerConfig(
            total_steps=args.steps, accum_steps=args.accum,
            ckpt_every=args.ckpt_every, log_every=10,
            schedule=ScheduleConfig(peak_lr=args.lr, warmup_steps=20,
                                    decay_steps=args.steps),
        ),
        ckpt=ckpt,
        monitor=HeartbeatMonitor(["host0"]),
        stragglers=StragglerDetector(),
    )

    with mesh, mesh_context(mesh):
        params, opt = trainer.init_state(jax.random.PRNGKey(0))
        start = 0
        if args.resume:
            restored = ckpt.restore_latest({"params": params, "opt": opt})
            if restored is not None:
                start, tree, _ = restored
                params, opt = tree["params"], tree["opt"]
                print(f"[train] resumed from step {start}")
        params, opt = trainer.run(params, opt, start_step=start)

    loader.stop()
    for row in trainer.metrics_log:
        print(f"[train] step {row['step']:5d} loss {row['loss']:.4f} "
              f"gnorm {row['gnorm']:.3f} lr {row['lr']:.2e} "
              f"({row['secs']:.2f}s)")
    print(f"[train] done; checkpoints: {ckpt.all_steps()}")


if __name__ == "__main__":
    main()
