import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# The lines above MUST run before jax initializes: the dry-run builds the
# production 16x16 (and 2x16x16) mesh from host placeholder CPU devices,
# and never asks for an accelerator another process may hold.
# Everything below proves the distribution config is coherent without TPU
# hardware: every (architecture x input-shape x mesh) stage program must
# lower + compile, and the compiled artifact yields the roofline terms
# (EXPERIMENTS.md §Dry-run / §Roofline).
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs.base import ARCH_IDS, INPUT_SHAPES
from repro.core.train_step import (make_lm_train_step, make_prefill_step,
                                   make_serve_step)
from repro.launch.mesh import (arch_config_for_shape, input_specs,
                               make_production_mesh, stage_shardings)
from repro.models.registry import build_model
from repro.optim.adamw import adamw
from repro.utils import hlo as hlo_utils
from repro.utils import roofline

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# Assigned shapes; "qwen2.5-72b" (the paper's own model) is benched
# separately, keep the 40-combo matrix to the 10 assigned archs.
ASSIGNED_ARCHS = [a for a in ARCH_IDS if a != "qwen2.5-72b"]


def build_stage(arch_id: str, shape_name: str, mesh, *, fsdp=True,
                rules=None, remat=None, donate=True, microbatch=0):
    """Returns (jitted_fn, ordered abstract args, metadata)."""
    specs = input_specs(arch_id, shape_name)
    model = specs["model"]
    cfg = model.cfg
    if remat is not None:
        from dataclasses import replace
        cfg = replace(cfg, remat=remat)
        model = build_model(cfg)
        specs["model"] = model
    sh = stage_shardings(specs, mesh, fsdp=fsdp, rules=rules)
    kind = specs["kind"]
    extra = specs.get("extra")

    if kind == "train":
        opt = adamw(3e-4)
        step = make_lm_train_step(model, opt, microbatch=microbatch)
        if extra:
            fn = lambda p, o, t, l, e: step(p, o, t, l, extra=e)
            args = (specs["params"], specs["opt_state"], specs["tokens"],
                    specs["labels"], extra)
            in_sh = (sh["params"], sh["opt_state"], sh["tokens"],
                     sh["labels"], sh["extra"])
        else:
            fn = step
            args = (specs["params"], specs["opt_state"], specs["tokens"],
                    specs["labels"])
            in_sh = (sh["params"], sh["opt_state"], sh["tokens"],
                     sh["labels"])
        donate_argnums = (0, 1) if donate else ()
    elif kind == "prefill":
        pf = make_prefill_step(model)
        if extra:
            fn = lambda p, t, c, e: pf(p, t, c, extra=e)
            args = (specs["params"], specs["tokens"], specs["cache"], extra)
            in_sh = (sh["params"], sh["tokens"], sh["cache"], sh["extra"])
        else:
            fn = pf
            args = (specs["params"], specs["tokens"], specs["cache"])
            in_sh = (sh["params"], sh["tokens"], sh["cache"])
        donate_argnums = (2,) if donate else ()
    else:
        sv = make_serve_step(model)
        if extra:
            fn = lambda p, t, c, e: sv(p, t, c, extra=e)
            args = (specs["params"], specs["token"], specs["cache"], extra)
            in_sh = (sh["params"], sh["token"], sh["cache"], sh["extra"])
        else:
            fn = sv
            args = (specs["params"], specs["token"], specs["cache"])
            in_sh = (sh["params"], sh["token"], sh["cache"])
        donate_argnums = (2,) if donate else ()

    jit_fn = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate_argnums)
    return jit_fn, args, {"kind": kind, "cfg": cfg, "model": model}


def model_flops_for(cfg, kind: str, shape) -> float:
    n_active = cfg.active_param_count()
    if kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # decode: 1 tok/row


def mem_fields(mem) -> dict:
    out = {}
    for f in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        if hasattr(mem, f):
            out[f] = int(getattr(mem, f))
    return out


def peak_bytes(mem) -> int:
    d = mem_fields(mem)
    return (d.get("argument_size_in_bytes", 0)
            + d.get("output_size_in_bytes", 0)
            + d.get("temp_size_in_bytes", 0)
            - d.get("alias_size_in_bytes", 0))


def run_one(arch_id: str, shape_name: str, *, multi_pod: bool,
            fsdp=True, rules=None, remat=None, microbatch=0,
            verbose=True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = INPUT_SHAPES[shape_name]
    chips = 512 if multi_pod else 256
    name = f"{arch_id}|{shape_name}|{'2x16x16' if multi_pod else '16x16'}"
    t0 = time.time()
    jit_fn, args, meta = build_stage(arch_id, shape_name, mesh, fsdp=fsdp,
                                     rules=rules, remat=remat,
                                     microbatch=microbatch)
    with jax.set_mesh(mesh):
        lowered = jit_fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    # XLA's cost_analysis counts scan bodies once; full_cost weights while
    # loops by trip count (utils/hlo.py) — the honest per-device numbers.
    fc = hlo_utils.full_cost(compiled.as_text())
    mf = model_flops_for(meta["cfg"], meta["kind"], shape)
    rep = roofline.analyze(
        name, chips=chips,
        cost_analysis={"flops": fc.flops, "bytes accessed": fc.bytes_accessed},
        collective_bytes=fc.collective_bytes, model_flops=mf,
        hw=roofline.V5E, peak_memory_bytes=peak_bytes(mem))

    row = {
        "arch": arch_id, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": meta["kind"], "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_fields(mem),
        "peak_bytes_per_device": peak_bytes(mem),
        "cost": {
            "flops": fc.flops, "bytes_accessed": fc.bytes_accessed,
            "xla_flops_once": float(cost.get("flops", 0.0) or 0.0),
            "xla_bytes_once": float(cost.get("bytes accessed", 0.0) or 0.0),
        },
        "collectives": {
            "total_bytes": fc.collective_bytes,
            "by_kind_bytes": fc.collective_by_kind,
        },
        "roofline": rep.row(),
    }
    if verbose:
        print(f"[OK] {name}  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"     memory_analysis: {mem}")
        print(f"     cost (trip-count corrected): flops={fc.flops:.4g} "
              f"bytes={fc.bytes_accessed:.4g} "
              f"(xla-once: {cost.get('flops', 0):.3g})")
        print(f"     collectives: " + "; ".join(
            f"{k}: {v/2**20:.1f} MiB" for k, v in
            sorted(fc.collective_by_kind.items())))
        print(f"     roofline: compute {rep.compute_s:.4g}s | memory "
              f"{rep.memory_s:.4g}s | collective {rep.collective_s:.4g}s "
              f"-> {rep.bottleneck}-bound, useful-FLOP ratio "
              f"{rep.useful_flops_ratio:.3f}, peak "
              f"{row['peak_bytes_per_device']/2**30:.2f} GiB/device")
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description="EARL multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="single arch id (default: all)")
    ap.add_argument("--shape", default=None, help="single shape (default: all)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="use the 2x16x16 512-chip mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--remat", default=None, choices=["none", "full"])
    ap.add_argument("--microbatch", type=int, default=0,
                    help="gradient-accumulation slices for train shapes")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args(argv)

    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                try:
                    row = run_one(arch, shape, multi_pod=mp,
                                  fsdp=not args.no_fsdp, remat=args.remat,
                                  microbatch=args.microbatch)
                    (outdir / f"{tag}.json").write_text(json.dumps(row,
                                                                   indent=1))
                    n_ok += 1
                except Exception:
                    n_fail += 1
                    print(f"[FAIL] {tag}")
                    traceback.print_exc()
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
