"""EARL Data Dispatcher demo: move an experience batch from the rollout
layout to the update layout, centralized vs direct, on 16 host devices.

Shows the paper's Fig. 4 effect structurally: the single-controller path
funnels the whole batch through one device; the layout-aware all-to-all
moves only the shards that change owner.

    python examples/dispatch_demo.py

It sets its own XLA_FLAGS and pins JAX_PLATFORMS=cpu: the 16 devices are
virtual CPU devices, on any machine, chip or not.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=16")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.data_dispatcher import DataDispatcher
from repro.core.resharding import MeshConfig
from repro.rl.experience import zeros_like_experience


def main():
    # rollout stage: 16-way data parallel; update stage: dp=4 x tp=4
    rollout_mesh = MeshConfig("rollout_dp16", dp=16, tp=1).make_mesh()
    update_mesh = MeshConfig("update_dp4tp4", dp=4, tp=4).make_mesh()

    exp = zeros_like_experience(batch=64, seq=8192)
    batch_spec = lambda x: P("data", *([None] * (x.ndim - 1)))
    src = jax.tree.map(
        lambda x: NamedSharding(rollout_mesh, batch_spec(x)), exp)
    dst = jax.tree.map(
        lambda x: NamedSharding(update_mesh, batch_spec(x)), exp)

    print(f"experience batch: {exp.nbytes()/2**20:.1f} MiB "
          f"({len(jax.tree.leaves(exp))} tensors), 16 devices")
    d = DataDispatcher()
    for strategy in ("centralized", "direct"):
        placed = jax.tree.map(jax.device_put, exp, src)
        jax.block_until_ready(placed)
        out, rep = d.dispatch(placed, dst, strategy=strategy)
        print(f"\n[{strategy}]")
        print(f"  wall time          {rep.wall_time_s*1e3:9.2f} ms")
        print(f"  bytes moved        {rep.moved_bytes/2**20:9.2f} MiB")
        print(f"  bottleneck device  {rep.bottleneck_bytes/2**20:9.2f} MiB")
        print(f"  est. 25 Gbps       {rep.est_latency_ethernet_s*1e3:9.2f} ms")
        print(f"  est. ICI           {rep.est_latency_ici_s*1e6:9.2f} us")
    c, e = d.log[0], d.log[1]
    print(f"\nEARL bottleneck-bytes reduction: "
          f"{c.bottleneck_bytes / max(e.bottleneck_bytes, 1):.1f}x "
          f"(paper Fig. 4: 9.7-11.2x wall-clock at 128 GPUs)")


if __name__ == "__main__":
    main()
