"""Readings that set the limits of ``correct`` for one cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 3] [--fault-seeds 3] [--out FILE]

In one process, for each seed: the program's first steps through the
timed call, and the float32 reference following them (the program's
readings, whose largest over the seeds is the lower reading of each
number); on the first ``--control-seeds`` seeds also the control, the
reference computed with float8 matmul operands in the program's place;
on the first ``--fault-seeds`` seeds the program with each fault of
``bench.harness.plant`` planted. Prints one JSON line per reading and a
summary. Needs the chip, as ``bench/run.py`` does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def side(trainer, block, cfg, traffic, seed, stages, fault):
    """The program's three set-up steps for ``seed`` with ``fault``."""
    import jax
    from bench import harness, weights
    for s in stages.values():
        s.before, s.after = None, []
    harness.plant(fault, trainer, stages, cfg["model"]["vocab_size"])
    trainer._rng = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    params = weights.to_program(block, weights.make_weights(
        block, cfg["model"], weights.seed_key(seed)))
    opt_state = trainer.optimizer.init(params)
    out = harness.program_side(trainer, params, opt_state, params, stages,
                               block, cfg, traffic["algo"])
    return out[2:]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    ap.add_argument("--root", default=str(ROOT))
    args = ap.parse_args(argv)

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, common, harness

    root = Path(args.root)
    spec = common.load_benchmark(root)
    wl = common.workload(spec, args.workload)
    cfg = common.load_config(spec, wl["config"], root)
    block = common.block(cfg, root)
    traffic = common.load_traffic(wl["traffic"], root)
    limits = common.load_limits(wl["name"], root)
    dev = harness.device_info(wl["chips"], True)
    print(f"device {dev}", flush=True)

    seeds = [int(s) for s in args.seeds.split(",")]
    trainer = harness.build(cfg, traffic, seeds[0], block)[0]
    stages = harness.wrap_stages(trainer)

    out = open(args.out, "w") if args.out else None
    rows = []

    def record(kind, seed, values, secs):
        row = {"kind": kind, "seed": seed, "seconds": secs, **values}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        steps, g0, change = side(trainer, block, cfg, traffic, seed, stages,
                                 None)
        gc.collect()
        ref = harness.reference_side(block, cfg, traffic, seed, steps,
                                     limits)
        gens = [s["gen_mask"] for s in steps]
        record("program", seed, check.readings(steps, g0, change, *ref,
                                               gens),
               time.perf_counter() - t)
        if i < args.control_seeds:
            t = time.perf_counter()
            ctl = harness.reference_side(block, cfg, traffic, seed, steps,
                                         limits, precision="fp8")
            record("control_fp8", seed,
                   check.readings(*ctl, *ref, gens), time.perf_counter() - t)
        if i < args.fault_seeds:
            for fault in ("half_batch", "token_altered",
                          "advantage_altered"):
                t = time.perf_counter()
                f_steps, f_g0, f_change = side(trainer, block, cfg, traffic,
                                               seed, stages, fault)
                gc.collect()
                f_ref = harness.reference_side(block, cfg, traffic, seed,
                                               f_steps, limits)
                record(fault, seed,
                       check.readings(f_steps, f_g0, f_change, *f_ref,
                                      [s["gen_mask"] for s in f_steps]),
                       time.perf_counter() - t)

    summary = {}
    for k in check.NUMBERS:
        prog = [r[k] for r in rows if r["kind"] == "program"]
        summary[k] = {"lower": max(prog),
                      **{kind: min(r[k] for r in rows if r["kind"] == kind)
                         for kind in ("control_fp8", "half_batch",
                                      "token_altered", "advantage_altered")
                         if any(r["kind"] == kind for r in rows)}}
    print("SUMMARY " + json.dumps(summary), flush=True)
    print(f"total seconds {time.perf_counter() - T_START:.1f}", flush=True)
    if out:
        out.write("SUMMARY " + json.dumps(summary) + "\n")
        out.close()


if __name__ == "__main__":
    main()
