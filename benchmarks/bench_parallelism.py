"""Paper Fig. 3 — relative rollout-throughput speedup switching TP=4 -> TP=8
across context lengths and response counts, including the OOM cell.

Reproduction path (CPU container): the Parallelism Selector's cost-model
profiling. For each (TP, context, #responses) we lower+compile the decode
stage of the paper's model (Qwen2.5-72B) on a 64-chip slice with dp=64/TP,
and score TGS with the TPU-v5e profile (197 TFLOP/s, 819 GB/s HBM, 16 GiB,
~1 us ICI hop latency). The hardware adaptation (DESIGN.md §2): on the TPU
target decode weights stay FSDP-sharded over the data axis and are
all-gathered layer-by-layer, so the TP4-vs-TP8 trade is: fewer collective
latency hops per step (TP4 rings are shorter) vs smaller FSDP gather
slices + smaller transient footprint (TP8). Configs whose compiled
per-device footprint exceeds the 16 GiB v5e HBM are OOM — the analytic
analogue of Fig. 3's crash. (A vLLM-faithful fsdp=False variant was tried
and refuted as a measurement: XLA materializes a second copy of the scanned
weight stack in the while-loop carry, inflating every footprint ~2x —
see EXPERIMENTS.md §Fig3.)

Runs in a subprocess (needs forced host devices; must not leak XLA_FLAGS
into the caller).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
import json
import jax
import jax.numpy as jnp
from repro.configs.base import get_config
from repro.core.parallelism_selector import (ProfileEntry,
                                             make_cost_model_measure)
from repro.utils.roofline import H100, V5E
from repro.core.resharding import MeshConfig
from repro.core.train_step import make_serve_step
from repro.launch.mesh import cache_shardings, stage_shardings
from repro.core.resharding import param_shardings
from repro.models.registry import build_model

ARCH = "qwen2.5-72b"
CONTEXTS = [1024, 2048, 4096, 8192, 16384, 32768]
RESPONSES = [32, 128]
CHIPS = 64

cfg = get_config(ARCH)
model = build_model(cfg)


def lower_decode(mesh_cfg, ctx, responses):
    mesh = mesh_cfg.make_mesh()
    params = model.abstract()
    cache = jax.eval_shape(lambda: model.init_cache(responses, ctx))
    token = jax.ShapeDtypeStruct((responses,), jnp.int32)
    from repro.launch.mesh import cache_shardings, _batch_spec
    p_sh = param_shardings(model, mesh)    # FSDP decode (TPU-idiomatic)
    c_sh = cache_shardings(cache, mesh, seq_len=ctx,
                           n_kv_heads=cfg.n_kv_heads)
    t_sh = _batch_spec(mesh, (responses,))
    serve = make_serve_step(model)
    jf = jax.jit(serve, in_shardings=(p_sh, t_sh, c_sh),
                 donate_argnums=(2,))
    with jax.set_mesh(mesh):
        return jf.lower(params, token, cache)


rows = []
for responses in RESPONSES:
    for ctx in CONTEXTS:
        entries = {}
        for tp in (4, 8):
            mc = MeshConfig(f"tp{tp}", dp=CHIPS // tp, tp=tp)
            measure = make_cost_model_measure(
                lambda m, c, r=responses: lower_decode(m, c, r),
                seq_tokens_fn=lambda c, r=responses: float(r), hw=V5E)
            e = measure(mc, ctx)
            entries[tp] = e
        e4, e8 = entries[4], entries[8]
        if not e4.feasible and e8.feasible:
            speedup = None      # the OOM cell: TP8 survives, TP4 crashes
        elif e4.feasible and e8.feasible:
            speedup = (e8.tgs - e4.tgs) / e4.tgs * 100.0
        else:
            speedup = float("nan")
        rows.append(dict(
            responses=responses, context=ctx, speedup_pct=speedup,
            tp4_feasible=e4.feasible, tp8_feasible=e8.feasible,
            tp4_feasible_v5e=e4.peak_bytes <= V5E.hbm_bytes,
            tp8_feasible_v5e=e8.peak_bytes <= V5E.hbm_bytes,
            tp4_tgs=e4.tgs, tp8_tgs=e8.tgs,
            tp4_peak_GiB=e4.peak_bytes / 2**30,
            tp8_peak_GiB=e8.peak_bytes / 2**30))
print(json.dumps(rows))
"""


def run():
    # the child analyses virtual CPU devices; pinning its platform keeps it
    # off any accelerator this process (or another) holds
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(SNIPPET)],
                         capture_output=True, text=True, env=env,
                         timeout=3600)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def analytic_weights_resident_grid():
    """The paper's own serving regime (vLLM: weights resident per TP group),
    modeled analytically on its H100 testbed — per decode step:

        t(tp) = weights/tp / hbm_bw            (weight reads, the B<<1 term)
              + kv_per_gpu(tp) / hbm_bw        (cache reads)
              + 2 * L * tp * hop_latency       (2 all-reduces/layer, ring)

    This is where Fig. 3's TP4-advantage at short context lives: TP4 rings
    are half as long, and at short context the latency floor beats TP8's
    halved weight traffic. OOM feasibility = weights/tp + kv_per_gpu vs
    0.9 * 80 GB (vLLM default utilization)."""
    from repro.utils.roofline import H100
    from repro.configs.base import get_config
    cfg = get_config("qwen2.5-72b")
    n_params = cfg.param_count()
    L = cfg.n_layers
    chips = 64
    rows = []
    for responses in (32, 128):
        for ctx in (1024, 2048, 4096, 8192, 16384, 32768):
            t = {}
            feas = {}
            for tp in (4, 8):
                # responses are PER ENGINE (vLLM n-responses semantics):
                # each TP group serves the full response count, so cache
                # reads/GPU scale 1/tp — this is what makes TP8 win at long
                # context AND what OOMs TP4 first (both Fig. 3 regimes).
                r_g = responses
                w_pc = n_params * 2 / tp
                kv_pc = (L * r_g * ctx * cfg.n_kv_heads * cfg.head_dim_
                         * 2 * 2) / tp
                t[tp] = (w_pc / H100.hbm_bw + kv_pc / H100.hbm_bw
                         + 2 * L * tp * H100.coll_hop_latency)
                feas[tp] = (w_pc + kv_pc) <= 0.9 * H100.hbm_bytes
            if not feas[4] and feas[8]:
                sp = None
            elif feas[4] and feas[8]:
                sp = (1 / t[8] - 1 / t[4]) / (1 / t[4]) * 100.0
            else:
                sp = float("nan")
            rows.append(dict(responses=responses, context=ctx,
                             speedup_pct=sp, t4_ms=t[4] * 1e3,
                             t8_ms=t[8] * 1e3, tp4_feasible=feas[4],
                             tp8_feasible=feas[8]))
    return rows


def main():
    rows = run()
    print("# Fig.3 repro: Speedup%(TP4->TP8), cost-model TGS, qwen2.5-72b"
          " decode on 64 chips")
    print("responses,context,speedup_pct,tp4_feasible,tp8_feasible,"
          "tp4_peak_GiB,tp8_peak_GiB")
    for r in rows:
        sp = ("OOM->TP8" if r["speedup_pct"] is None
              else f"{r['speedup_pct']:.1f}")
        print(f"{r['responses']},{r['context']},{sp},"
              f"{r['tp4_feasible']},{r['tp8_feasible']},"
              f"{r['tp4_peak_GiB']:.2f},{r['tp8_peak_GiB']:.2f}")
    print("\n# Fig.3 analytic (weights-resident vLLM regime, H100 —"
          " the paper's testbed):")
    print("responses,context,speedup_pct,t4_ms,t8_ms")
    for r in analytic_weights_resident_grid():
        sp = ("OOM->TP8" if r["speedup_pct"] is None else
              ("nan" if r["speedup_pct"] != r["speedup_pct"] else
               f"{r['speedup_pct']:+.1f}"))
        print(f"{r['responses']},{r['context']},{sp},"
              f"{r['t4_ms']:.1f},{r['t8_ms']:.1f}")
    return rows


if __name__ == "__main__":
    main()
