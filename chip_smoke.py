"""Smoke check of the EARL training loop on a TPU, at qwen2-0.5b's full
width (24 layers, d=896, vocab 151936, random weights from a seed).

    python3 chip_smoke.py               # one chip: phases a, b, c
    python3 chip_smoke.py --four-chips  # four chips: phase d only

Phases, each of which ends the run with a non-zero exit when it fails:

  a. the device: platform, kind, count and JAX version; anything but a
     TPU (with one chip, or four under ``--four-chips``) is a failure;
  b. every Pallas kernel of the main path, compiled for the chip at
     qwen2-0.5b widths (the SSD scan at mamba2-370m's) and compared with
     its pure-jnp oracle (``repro.kernels.cases``);
  c. ``repro.launch.train.main`` twice on tictactoe: with the CLI's
     default rollout path, then with the compiled engine, paged KV cache
     and fused sampler. Loss and KL must be finite, step 0's KL (the
     policy against itself) within ``KL0_TOL`` of zero, and every step
     must generate tokens. Prints the first step's seconds (compilation
     included), the steady steps/s on the host clock (each step ends in a
     blocking read of its loss) and the device's peak bytes in use;
  d. the Data Dispatcher and the Update stage across four chips: an
     experience batch moves from a dp=4 rollout layout to a dp=2 x tp=2
     update layout by direct and by centralized dispatch (bitwise equal,
     four distinct devices), then two trainer steps run with the compiled
     engine bound to dp=4 and the update on the dp=2 x tp=2 mesh, held to
     phase c's loss and KL checks.

The last line of standard output is the JSON result, and is printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
OUT = ROOT / "chiprun_out" / "chip_smoke"

ARCH = "qwen2-0.5b"
TRAIN_ARGS = ["--arch", ARCH, "--env", "tictactoe", "--steps", "3",
              "--batch", "8", "--seed", "0"]
FAST_PATH = ["--rollout-backend", "compiled", "--cache-layout", "paged",
             "--sampling", "fused"]
# at step 0 the policy is the reference, so its KL is rounding alone; a
# rollout that records log-probs its own tokens do not have shows here
KL0_TOL = 1e-2


def check_step(tag: str, step: int, loss: float, kl: float) -> None:
    if not (math.isfinite(loss) and math.isfinite(kl)):
        fail(f"{tag}: step {step} loss {loss} kl {kl}")
    if step == 0 and not kl <= KL0_TOL:
        fail(f"{tag}: step 0 kl {kl} above {KL0_TOL}: the recorded "
             "log-probs disagree with the policy that made them")


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def check_device(count: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[a] device {dev} jax {jax.__version__}", flush=True)
    if dev["platform"] != "tpu":
        fail(f"platform is {dev['platform']!r}, not 'tpu'")
    if dev["count"] != count:
        fail(f"{dev['count']} devices, this phase needs {count}")
    return dev


def check_kernels(attn_cfg, ssm_cfg, **sizes) -> None:
    """Compile each kernel case, confirm a Mosaic kernel is in the
    program, run it and the oracle, and bound their difference."""
    import jax
    from repro.kernels.cases import kernel_cases, max_error
    key = jax.random.PRNGKey(0)
    for case in kernel_cases(attn_cfg, ssm_cfg, **sizes):
        args = case.args(key)
        compiled = jax.jit(case.kernel).lower(*args).compile()
        on_chip = "tpu_custom_call" in compiled.as_text()
        out = compiled(*args)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(case.ref)(*args)
        err = max_error(out, ref)
        print(f"[b] {case.name}: max error {err:.3e} (tolerance "
              f"{case.tol:g}), mosaic kernel {on_chip}", flush=True)
        if jax.default_backend() == "tpu" and not on_chip:
            fail(f"{case.name}: no tpu_custom_call in the compiled program")
        if not err <= case.tol:
            fail(f"{case.name}: max error {err} above {case.tol}")


def run_trainer(tag: str, extra: list, log_dir: Path) -> dict:
    """One ``launch.train`` run; reads its JSONL log back and checks it."""
    import jax
    from repro.launch.train import main as train_main
    log = log_dir / f"train_{tag}.jsonl"
    rc = train_main(TRAIN_ARGS + extra + ["--log", str(log)])
    if rc != 0:
        fail(f"{tag}: launch.train returned {rc}")
    rows = [json.loads(line) for line in log.read_text().splitlines()]
    if not rows:
        fail(f"{tag}: no steps logged")
    for r in rows:
        check_step(tag, r["step"], r["loss"], r["kl"])
        if not r["turn_len"] > 0:
            fail(f"{tag}: step {r['step']} generated no tokens")
    walls = [r["wall_s"] for r in rows]
    stats = jax.devices()[0].memory_stats() or {}
    res = {"tag": tag, "steps": len(rows),
           "first_step_s": walls[0],
           "steady_steps_per_s": (len(walls) - 1) / sum(walls[1:]),
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "loss": [r["loss"] for r in rows], "kl": [r["kl"] for r in rows],
           "turn_len": [r["turn_len"] for r in rows]}
    print(f"[c] {json.dumps(res)}", flush=True)
    return res


def check_four_chips(model, batch: int, max_context: int) -> None:
    """Phase d: dispatch across layouts, then the trainer on four chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.data_dispatcher import DataDispatcher
    from repro.core.resharding import MeshConfig, param_shardings
    from repro.core.stages import EarlTrainer
    from repro.rl.envs import make_env
    from repro.rl.experience import ExperienceBatch, experience_specs

    rollout_cfg = MeshConfig("rollout_dp4", dp=4, tp=1)
    update_mesh = MeshConfig("update_dp2tp2", dp=2, tp=2).make_mesh()
    rows = lambda mesh, x: NamedSharding(mesh, P("data",
                                                 *([None] * (x.ndim - 1))))

    specs = experience_specs(batch, max_context)
    keys = jax.random.split(jax.random.PRNGKey(1), len(specs))
    exp = ExperienceBatch(*(
        jax.random.randint(k, s.shape, 0, 1 << 30).astype(s.dtype)
        if jnp.issubdtype(s.dtype, jnp.integer) else
        (jax.random.uniform(k, s.shape) < 0.5 if s.dtype == jnp.bool_
         else jax.random.normal(k, s.shape, s.dtype))
        for k, s in zip(keys, specs)))
    host = [np.asarray(x) for x in exp]
    src = jax.tree.map(lambda x: rows(rollout_cfg.make_mesh(), x), exp)
    dst = jax.tree.map(lambda x: rows(update_mesh, x), exp)
    outs = {}
    for strategy in ("direct", "centralized"):
        placed = jax.tree.map(jax.device_put, exp, src)
        out, rep = DataDispatcher().dispatch(placed, dst, strategy=strategy,
                                             src_shardings=src)
        jax.block_until_ready(out)
        outs[strategy] = out
        print(f"[d] dispatch {strategy}: {json.dumps(rep.row())}",
              flush=True)
        for x, h in zip(out, host):
            devs = {s.device.id for s in x.addressable_shards}
            if len(devs) != 4:
                fail(f"{strategy}: shards on devices {sorted(devs)}")
            if not np.array_equal(np.asarray(x), h):
                fail(f"{strategy}: dispatched batch differs from source")
    for a, b in zip(outs["direct"], outs["centralized"]):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            fail("direct and centralized dispatch differ")
    print("[d] direct == centralized bitwise, shards on 4 devices",
          flush=True)

    trainer = EarlTrainer(model=model, env=make_env("tictactoe"),
                          batch_size=batch, max_context=max_context,
                          max_turns=3, max_turn_tokens=6,
                          rollout_backend="compiled", kl_coef=0.05,
                          clip_eps=0.2, seed=0)
    trainer.rollout.bind_mesh(rollout_cfg)
    params, opt_state, ref_params = trainer.init_state()
    p_sh = param_shardings(model, update_mesh)
    params = jax.device_put(params, p_sh)
    opt_state = opt_state._replace(mu=jax.device_put(opt_state.mu, p_sh),
                                   nu=jax.device_put(opt_state.nu, p_sh))
    ref_params = params if ref_params is not None else None
    params, _, hist = trainer.train(2, params=params, opt_state=opt_state,
                                    ref_params=ref_params,
                                    dst_shardings=dst)
    for r in hist:
        print(f"[d] step {r.step}: loss {r.loss} kl {r.kl} dispatch "
              f"{json.dumps(r.dispatch)}", flush=True)
        check_step("four chips", r.step, r.loss, r.kl)
    leaves = jax.tree.leaves(params)
    spans = {len(p.sharding.device_set) for p in leaves}
    split = sum(not p.sharding.is_fully_replicated for p in leaves)
    print(f"[d] params: {len(leaves)} leaves, device-set sizes {spans}, "
          f"{split} sharded leaves", flush=True)
    if spans != {4} or split == 0:
        fail("updated params do not span the four devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip dispatch/update phase")
    args = ap.parse_args(argv)

    dev = check_device(4 if args.four_chips else 1)
    from repro.configs.base import get_config
    from repro.models.registry import build_model
    from repro.utils.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    OUT.mkdir(parents=True, exist_ok=True)
    cfg = get_config(ARCH)

    if args.four_chips:
        check_four_chips(build_model(cfg), batch=8, max_context=160)
    else:
        check_kernels(cfg, get_config("mamba2-370m"))
        run_trainer("default", [], OUT)
        run_trainer("compiled_paged_fused", FAST_PATH, OUT)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
