"""Paper Fig. 4 — dispatch latency: centralized (single-controller
gather-and-scatter) vs EARL (layout-aware direct dispatch).

The measured tensor is the reference-model log-probability batch (the
paper's §3.3 choice: it has no aggregation dependency). Three context
lengths; per strategy we report wall time on a multi-device host mesh,
bytes through the bottleneck device, and the analytic latency at the
paper's 25 Gbps transport. Runs in a subprocess (forced host devices)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SNIPPET = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.data_dispatcher import DataDispatcher
from repro.core.resharding import MeshConfig
from repro.rl.experience import zeros_like_experience

# rollout layout: dp=16 (one shard per worker); update layout: dp=8, tp=2
src_mesh = MeshConfig("rollout_dp16", dp=16, tp=1).make_mesh()
dst_mesh = MeshConfig("update_dp8tp2", dp=8, tp=2).make_mesh()

CONTEXTS = [8192, 16384, 32768]
ROWS = 64
REPEATS = 3

results = []
for ctx in CONTEXTS:
    exp = zeros_like_experience(ROWS, ctx)
    src_sh = jax.tree.map(
        lambda x: NamedSharding(src_mesh, P("data", *([None] *
                                                      (x.ndim - 1)))), exp)
    dst_sh = jax.tree.map(
        lambda x: NamedSharding(dst_mesh, P("data", *([None] *
                                                      (x.ndim - 1)))), exp)
    for strategy in ("centralized", "direct"):
        times = []
        for _ in range(REPEATS):
            batch = jax.tree.map(jax.device_put, exp, src_sh)
            jax.block_until_ready(batch)
            d = DataDispatcher()
            out, rep = d.dispatch(batch, dst_sh, strategy=strategy)
            times.append(rep.wall_time_s)
        results.append(dict(
            context=ctx, strategy=strategy,
            wall_ms=min(times) * 1e3,
            total_MiB=rep.total_bytes / 2**20,
            moved_MiB=rep.moved_bytes / 2**20,
            bottleneck_MiB=rep.bottleneck_bytes / 2**20,
            eth25_s=rep.est_latency_ethernet_s,
            ici_s=rep.est_latency_ici_s))
print(json.dumps(results))
"""


def run():
    # the child analyses virtual CPU devices; pinning its platform keeps it
    # off any accelerator this process (or another) holds
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(SNIPPET)],
                         capture_output=True, text=True, env=env,
                         timeout=1200)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-4000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def main():
    rows = run()
    print("# Fig.4 repro: dispatch latency, centralized vs EARL direct")
    print("context,strategy,wall_ms,bottleneck_MiB,eth25Gbps_s")
    by_ctx = {}
    for r in rows:
        print(f"{r['context']},{r['strategy']},{r['wall_ms']:.2f},"
              f"{r['bottleneck_MiB']:.1f},{r['eth25_s']:.4f}")
        by_ctx.setdefault(r["context"], {})[r["strategy"]] = r
    print("context,wall_speedup,eth_latency_reduction")
    for ctx, d in sorted(by_ctx.items()):
        ws = d["centralized"]["wall_ms"] / max(d["direct"]["wall_ms"], 1e-9)
        es = d["centralized"]["eth25_s"] / max(d["direct"]["eth25_s"], 1e-9)
        print(f"{ctx},{ws:.1f}x,{es:.1f}x")
    return rows


if __name__ == "__main__":
    main()
