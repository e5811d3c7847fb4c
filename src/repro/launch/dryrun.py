import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# dry-runs simulate the pod on forced *host* devices; without this an
# accelerator-capable install hangs probing for real hardware first
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ^ MUST precede every other import: JAX locks the device count on first use.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the model from its full config, creates
ShapeDtypeStruct stand-ins for params/optimizer/batch (zero allocation),
jits the train/prefill/decode step with explicit in/out shardings,
``.lower().compile()``s it for the single-pod (16×16) and multi-pod
(2×16×16) production meshes, and records:

* ``compiled.cost_analysis()``  — HLO FLOPs / bytes (per partition),
* ``compiled.memory_analysis()`` — argument/output/temp bytes per device,
* a collective inventory parsed from the post-SPMD HLO (op type, result
  bytes, group size, ring-adjusted wire bytes),
* the three roofline terms (DESIGN.md §8) against v5e constants.

Results land in ``experiments/dryrun/<arch>__<shape>__<mesh>.json`` and are
aggregated by ``benchmarks/roofline.py`` into EXPERIMENTS.md tables.

Usage::

    python -m repro.launch.dryrun --arch gemma2-9b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, cells_for, get_config, list_archs
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_batch_stub, make_decode_fn, make_prefill_fn, make_train_step
from repro.models import build_model, mesh_context
from repro.models.common import ArchConfig
from repro.optim import adamw_init
from repro.parallel.sharding import (
    batch_shardings,
    decode_state_shardings,
    named,
    opt_state_shardings,
    param_shardings,
)
from jax.sharding import PartitionSpec as P

# ---- v5e roofline constants (per chip) -------------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

_COLL_RE = re.compile(
    r"(\w+)\[([\d,]*)\][^=]*?\s(all-reduce|all-gather|reduce-scatter"
    r"|all-to-all|collective-permute)(?:-start)?\(",
)
_GROUPS_RE = re.compile(r"replica_groups=(?:\[(\d+),(\d+)\]|(\{\{[^}]*\}))")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}


def parse_collectives(hlo_text: str):
    """Collective inventory with ring-adjusted per-device wire bytes."""
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        if dtype not in _DTYPE_BYTES:
            continue
        elems = 1
        if dims:
            for d in dims.split(","):
                if d:
                    elems *= int(d)
        nbytes = elems * _DTYPE_BYTES[dtype]
        g = _GROUPS_RE.search(line)
        group = 1
        if g:
            if g.group(2):                      # iota [num_groups,size]<=[...]
                group = int(g.group(2))
            elif g.group(3):
                group = g.group(3).count(",") + 1
        n = max(group, 2)
        if op == "all-reduce":
            wire = 2 * nbytes * (n - 1) / n
        elif op == "all-gather":
            wire = nbytes * (n - 1) / n         # nbytes = gathered result
        elif op == "reduce-scatter":
            wire = nbytes * (n - 1)             # nbytes = scattered result
        elif op == "all-to-all":
            wire = nbytes * (n - 1) / n
        else:                                    # collective-permute
            wire = nbytes
        out.append({"op": op, "bytes": nbytes, "group": group, "wire": wire})
    return out


def model_flops(cfg: ArchConfig, kind: str, batch: int, seq: int) -> float:
    """Analytic MODEL_FLOPS = 6·N_active·tokens (train) / 2·N·tokens (fwd)."""
    n = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n * batch * seq
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch  # decode: one token per sequence


def build_cell(arch: str, shape_name: str, *, multi_pod: bool,
               decode_layout: str = "seq", remat: str = "full",
               extra: dict | None = None):
    """Returns (jitted_fn, example_args, meta) ready to lower."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    extra = extra or {}
    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg, impl="xla", remat=remat, decode_layout=decode_layout)

    n_batch_shards = mesh.shape.get("pod", 1) * mesh.shape["data"]
    divisible = shape.global_batch % n_batch_shards == 0

    rng = jax.random.PRNGKey(0)
    p_shapes = jax.eval_shape(model.init, rng)
    param_mode = extra.get("param_mode", "train")
    hd_div = cfg.num_heads % mesh.shape["model"] == 0
    p_shard = param_shardings(p_shapes, mesh, mode=param_mode,
                              heads_divisible=hd_div)

    if shape.kind == "train":
        o_shapes = jax.eval_shape(adamw_init, p_shapes)
        o_shard = opt_state_shardings(o_shapes, mesh,
                                      heads_divisible=hd_div)
        batch = make_batch_stub(cfg, batch=shape.global_batch,
                                seq=shape.seq_len, kind="train")
        b_shard = batch_shardings(batch, mesh, batch_divisible=divisible)
        step = make_train_step(model)
        rep = named(mesh, P())
        m_shard = {k: rep for k in
                   ("ce", "aux", "tokens", "loss", "gnorm", "lr")}
        fn = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, m_shard),
                     donate_argnums=(0, 1))
        args = (p_shapes, o_shapes, batch)
    elif shape.kind == "prefill":
        batch = make_batch_stub(cfg, batch=shape.global_batch,
                                seq=shape.seq_len, kind="prefill")
        b_shard = batch_shardings(batch, mesh, batch_divisible=divisible)
        prefill = make_prefill_fn(model, max_seq=shape.seq_len)
        s_shapes = jax.eval_shape(prefill, p_shapes, batch)[0]
        s_shard = decode_state_shardings(s_shapes, mesh, layout=decode_layout,
                                         batch_divisible=divisible)
        l_shard = named(mesh, P(("pod", "data") if divisible else None, None))
        fn = jax.jit(prefill, in_shardings=(p_shard, b_shard),
                     out_shardings=(s_shard, l_shard))
        args = (p_shapes, batch)
    else:  # decode
        state_shapes = jax.eval_shape(
            lambda: model.init_decode_state(shape.global_batch, shape.seq_len)
        )
        s_shard = decode_state_shardings(state_shapes, mesh,
                                         layout=decode_layout,
                                         batch_divisible=divisible)
        tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        t_shard = named(mesh, P(("pod", "data") if divisible else None))
        l_shard = named(mesh, P(("pod", "data") if divisible else None, None))
        decode = make_decode_fn(model)
        fn = jax.jit(decode, in_shardings=(p_shard, s_shard, t_shard),
                     out_shardings=(s_shard, l_shard), donate_argnums=(1,))
        args = (p_shapes, state_shapes, tok)

    meta = {
        "arch": arch, "shape": shape_name, "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": int(np.prod(list(mesh.shape.values()))),
        "decode_layout": decode_layout, "remat": remat,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
        "model_flops": model_flops(cfg, shape.kind, shape.global_batch,
                                   shape.seq_len),
    }
    meta.update(extra)
    return mesh, fn, args, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Path,
             decode_layout: str = "seq", remat: str = "full",
             tag: str = "", extra: dict | None = None) -> dict:
    mesh, fn, args, meta = build_cell(
        arch, shape_name, multi_pod=multi_pod,
        decode_layout=decode_layout, remat=remat, extra=extra,
    )
    chips = meta["chips"]
    with mesh, mesh_context(mesh):
        t0 = time.time()
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # ---- analyses -----------------------------------------------------------
    try:
        cost = compiled.cost_analysis() or {}
    except Exception as e:  # pragma: no cover
        cost = {"error": str(e)}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_d = {"error": str(e)}

    hlo = compiled.as_text()
    # Loop-aware static analysis (XLA's cost_analysis counts while bodies
    # once; analyze_hlo multiplies by trip counts — see hlo_cost.py).
    hc = analyze_hlo(hlo)
    coll_by_op = hc.collectives
    wire_bytes = hc.wire_bytes

    flops = float(hc.flops)
    bytes_accessed = float(hc.bytes)
    compute_s = flops / PEAK_FLOPS
    memory_s = bytes_accessed / HBM_BW
    collective_s = wire_bytes / ICI_BW

    dominant = max(
        ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
        key=lambda kv: kv[1],
    )[0]
    mf = meta["model_flops"]
    useful_ratio = mf / (flops * chips) if flops else 0.0

    result = {
        **meta,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_accessed,
        "collective_wire_bytes_per_chip": wire_bytes,
        "collectives": coll_by_op,
        "while_trip_counts": hc.while_trip_counts[:8],
        "xla_cost_analysis": {
            "flops_single_visit": float(cost.get("flops", 0.0)),
            "bytes_single_visit": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": mem_d,
        "roofline": {
            "compute_s": compute_s,
            "memory_s": memory_s,
            "collective_s": collective_s,
            "dominant": dominant,
            "step_s_lower_bound": max(compute_s, memory_s, collective_s),
            "useful_flop_ratio": useful_ratio,
        },
        "transcript_lines": hlo.count("\n"),
        "ok": True,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch.replace('/', '_')}__{shape_name}__{meta['mesh']}"
    if tag:
        name += f"__{tag}"
    (out_dir / f"{name}.json").write_text(json.dumps(result, indent=1))
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--decode-layout", default="seq", choices=["heads", "seq"])
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--serve-params", action="store_true",
                    help="§Perf-C1: replicate dense weights over data for "
                         "decode/prefill (no per-token FSDP gathers)")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    out_dir = Path(args.out)
    cells = []
    if args.all:
        for arch in list_archs():
            for shape in cells_for(arch):
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        if args.shape not in cells_for(args.arch):
            print(f"[n/a]  {args.arch}__{args.shape} (DESIGN.md §4 skip)")
            return
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            mesh_name = "2x16x16" if mp else "16x16"
            fname = f"{arch}__{shape}__{mesh_name}"
            if args.tag:
                fname += f"__{args.tag}"
            if args.skip_existing and (out_dir / f"{fname}.json").exists():
                print(f"[skip] {fname}")
                continue
            t0 = time.time()
            try:
                r = run_cell(arch, shape, multi_pod=mp, out_dir=out_dir,
                             decode_layout=args.decode_layout,
                             remat=args.remat, tag=args.tag,
                             extra={"param_mode": "serve"}
                             if args.serve_params else None)
                rf = r["roofline"]
                print(
                    f"[ok]   {fname}  compile={r['compile_s']:.0f}s "
                    f"flops/chip={r['hlo_flops_per_chip']:.3e} "
                    f"dom={rf['dominant']} "
                    f"bound={rf['step_s_lower_bound']*1e3:.2f}ms "
                    f"useful={rf['useful_flop_ratio']:.2f}",
                    flush=True,
                )
            except Exception as e:
                failures += 1
                print(f"[FAIL] {fname}  {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
                (out_dir / f"{fname}.FAILED.txt").write_text(
                    f"{e}\n{traceback.format_exc()}"
                )
            print(f"       ({time.time()-t0:.0f}s)", flush=True)
            jax.clear_caches()
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
