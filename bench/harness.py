"""One run of one cell: set-up, the measured window, the check.

Set-up builds the program's ``EarlTrainer`` from the configuration and
traffic files, with the traffic's algorithm settings and no path option,
so the program's defaults are the path measured. Weights come from the
seed (``bench/weights.py``) by the configuration's block
(``bench/blocks/<block>.py``). Three whole steps go through
``EarlTrainer.run_step``, the call the window drives; they compile every
program the window uses, and what they produce is kept for the check.
The window then runs whole steps until ``seconds`` have passed; each ends
in the step's blocking read of its loss. With ``trace`` the window is a
few profiled steps whose stage spans block on their outputs.

After the window the device's peak memory is read, the program's state is
freed, and the float32 reference (``bench/reference.py``) follows the
three set-up steps from the same seed; ``bench/check.py`` compares.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from dataclasses import replace

import numpy as np

from bench import check, common, flops, trace, weights

TRACED_STEPS = 3
SETUP_STEPS = 3
FAULTS = ("stale_state", "half_batch", "token_altered", "advantage_altered")

class _Compiles:
    """Backend compilations in this process, counted by one listener."""
    count = 0
    listening = False


def compiles() -> int:
    if not _Compiles.listening:
        import jax
        from jax._src import dispatch

        def listener(event, duration, **kw):
            if event == dispatch.BACKEND_COMPILE_EVENT:
                _Compiles.count += 1
        jax.monitoring.register_event_duration_secs_listener(listener)
        _Compiles.listening = True
    return _Compiles.count


def device_info(chips: int, require_tpu: bool) -> dict:
    """The device as JAX reports it; exits when it is not the chip the
    cell asks for."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if require_tpu:
        if dev["platform"] != "tpu":
            raise SystemExit(f"no accelerator: JAX's platform is "
                             f"{dev['platform']!r}, the benchmark measures "
                             "only on a TPU")
        trace.peaks(dev["kind"])
    if dev["count"] < chips:
        raise SystemExit(f"{dev['count']} devices; the cell asks for "
                         f"{chips}")
    return dev


class Stage:
    """A benchmark span around one of the trainer's stages. ``hooks``
    may alter the call's arguments (``before``) or its result
    (``after``); ``block`` waits for the result inside the span."""

    def __init__(self, name, fn):
        self.name, self.fn = name, fn
        self.block = False
        self.before = None
        self.after = []

    def __call__(self, *args, **kw):
        import jax
        if self.before is not None:
            args, kw = self.before(args, kw)
        with jax.profiler.TraceAnnotation("bench." + self.name):
            out = self.fn(*args, **kw)
            if self.block:
                jax.block_until_ready(out)
        for f in self.after:
            out = f(out)
        return out


def wrap_stages(trainer) -> dict:
    """Put a ``Stage`` around each of the trainer's stages; returns them
    by name."""
    stages = {n: Stage(n, getattr(trainer, f"{n}_stage"))
              for n in ("rollout", "expprep", "update")}
    for n, s in stages.items():
        setattr(trainer, f"{n}_stage", s)
    return stages


def build(cfg: dict, traffic: dict, seed: int, block):
    """The program's trainer and its initial state."""
    from repro.configs import get_config
    from repro.core.stages import EarlTrainer
    from repro.models.registry import build_model
    from repro.optim.adamw import adamw
    import importlib

    mcfg = replace(get_config(cfg["arch"]),
                   **block.program_config(cfg["model"]))
    model = build_model(mcfg)
    gen = importlib.import_module(f"bench.traffic.{traffic['generator']}")
    env = getattr(gen, traffic["class"])(**traffic.get("kwargs", {}))
    a = traffic["algo"]
    opt = adamw(a["lr"], b1=a["b1"], b2=a["b2"], eps=a["eps"],
                weight_decay=a["weight_decay"],
                max_grad_norm=a["max_grad_norm"])
    trainer = EarlTrainer(
        model, env, opt, batch_size=traffic["batch"],
        max_turns=traffic["max_turns"],
        max_turn_tokens=traffic["max_turn_tokens"],
        max_context=traffic["max_context"], kl_coef=a["kl_coef"],
        clip_eps=a["clip_eps"], advantage=a["advantage"],
        temperature=a["temperature"], seed=int(seed) % (2 ** 31 - 1))
    w = weights.make_weights(block, cfg["model"], weights.seed_key(seed))
    params = weights.to_program(block, w)
    opt_state = opt.init(params)
    return trainer, params, opt_state


def plant(fault, trainer, stages, vocab):
    """Break the timed path underneath, for the tests and the readings of
    faults: the Update returns the state it was given; the Update leaves
    out half of the batch and takes the mean over the rest; the Rollout
    alters each episode's first generated token where it is produced;
    ExpPrep's advantages take the batch mean of the rewards as baseline
    in place of the leave-one-out mean."""
    import jax.numpy as jnp
    if fault == "stale_state":
        def keep(args, kw):
            keep.params = args[0]
            return args, kw
        stages["update"].before = keep
        stages["update"].after.append(
            lambda out: (keep.params,) + tuple(out[1:]))
    elif fault == "half_batch":
        def half(args, kw):
            exp = args[2]
            rows = jnp.arange(exp.batch) < exp.batch // 2
            exp = exp._replace(loss_mask=exp.loss_mask & rows[:, None])
            return (args[0], args[1], exp) + tuple(args[3:]), kw
        stages["update"].before = half
    elif fault == "token_altered":
        def alter(out):
            exp = out[0]
            first = jnp.argmax(exp.gen_mask, axis=1)
            rows = jnp.arange(exp.batch)
            tok = (exp.tokens[rows, first] + 1) % vocab
            return (exp._replace(tokens=exp.tokens.at[rows, first].set(tok)),
                    ) + tuple(out[1:])
        stages["rollout"].after.append(alter)
    elif fault == "advantage_altered":
        def mean_baseline(exp):
            r = exp.rewards.astype(jnp.float32)
            return exp._replace(advantages=r - jnp.mean(r))
        stages["expprep"].after.append(mean_baseline)
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")


def program_side(trainer, params, opt_state, ref_params, stages, block,
                 cfg, algo, n_steps=SETUP_STEPS):
    """Drive the first steps through ``run_step`` and keep, on the host,
    what each produced; the first gradient's norms from Adam's first
    moment, and the change of the weights."""
    import jax
    kept = []
    stages["expprep"].after.append(lambda exp: kept.append(exp) or exp)
    names = sorted(block.shapes(cfg["model"]))
    params0 = params
    steps, g0 = [], None
    for k in range(n_steps):
        params, opt_state, rec = trainer.run_step(k, params, opt_state,
                                                  ref_params)
        exp = jax.device_get(kept.pop())
        steps.append({"tokens": np.asarray(exp.tokens),
                      "gen_mask": np.asarray(exp.gen_mask),
                      "rewards": np.asarray(exp.rewards),
                      "lp": np.asarray(exp.logprobs)[:, 1:],
                      "ref_lp": np.asarray(exp.ref_logprobs)[:, 1:],
                      "adv": np.asarray(exp.advantages),
                      "loss": float(rec.loss)})
        if k == 0:
            mu = weights.from_program(block, opt_state.mu, names)
            g0 = {n: v / (1 - algo["b1"])
                  for n, v in weights.leaf_norms(block, mu).items()}
    stages["expprep"].after.pop()
    change = weights.change_norms(
        block, weights.from_program(block, params, names),
        weights.from_program(block, params0, names))
    return params, opt_state, steps, g0, change


def reference_side(block, cfg, traffic, seed, steps, limits,
                   precision="f32"):
    from bench.reference import Reference
    ref = Reference(block, cfg["model"], traffic["algo"],
                    precision=precision,
                    rows_per_block=limits["rows_per_block"])
    w0 = weights.make_weights(block, cfg["model"], weights.seed_key(seed))
    return ref.follow(w0, steps)


def per_layer(spec, wl, ctx, root=common.ROOT) -> dict:
    """The cell's per-layer metrics, each read by its own reader from the
    traced run; a reader that finds nothing leaves its metric out."""
    out = {}
    for m in common.metrics_of(spec, wl["name"], "per_layer"):
        v = common.metric_reader(m["name"], root).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def run_cell(spec, wl, seed, seconds, traced, *, t_start,
             require_tpu=True, fault=None, root=common.ROOT, log=print,
             keep_trace=None, trace_steps=TRACED_STEPS):
    """Set-up, window and check of cell ``wl``; returns the result line's
    object, the compared numbers' lines and the compilations counted in
    the window."""
    import jax
    cfg = common.load_config(spec, wl["config"], root)
    block = common.block(cfg, root)
    traffic = common.load_traffic(wl["traffic"], root)
    limits = common.load_limits(wl["name"], root)
    dev = device_info(wl["chips"], require_tpu)
    peaks = trace.PEAKS.get(dev["kind"], {"bf16_flops": float("nan"),
                                          "hbm_bytes_per_s": float("nan")})
    compiles()

    trainer, params, opt_state = build(cfg, traffic, seed, block)
    ref_params = params if traffic["algo"]["kl_coef"] > 0 else None
    stages = wrap_stages(trainer)
    plant(fault, trainer, stages, cfg["model"]["vocab_size"])

    params, opt_state, prog_steps, prog_g0, prog_change = program_side(
        trainer, params, opt_state, ref_params, stages, block, cfg,
        traffic["algo"])

    B = traffic["batch"]
    compiles0 = compiles()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    step_s, live, lens = [], [], []
    tmp = None
    if traced:
        for s in stages.values():
            s.block = True
        lens_now = []
        stages["expprep"].after.append(
            lambda exp: lens_now.append(np.asarray(exp.context_len)) or exp)
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1          # the benchmark's spans only
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tmp, profiler_options=opts)
    k = SETUP_STEPS
    while True:
        a = time.perf_counter()
        params, opt_state, rec = trainer.run_step(k, params, opt_state,
                                                  ref_params)
        b = time.perf_counter()
        step_s.append(b - a)
        live.append(rec.mean_context_len * B)
        k += 1
        if traced and len(step_s) >= trace_steps:
            break
        if not traced and b - t0 >= seconds:
            break
    window_s = b - t0
    if traced:
        jax.profiler.stop_trace()
        lens = lens_now
    n_compiles = compiles() - compiles0
    log(f"compilations in the window: {n_compiles}")
    log(f"window steps: {len(step_s)}, seconds: {step_s}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    log(f"memory peak bytes: {peak}")
    dev["memory_peak_bytes"] = int(peak)

    metrics = {}
    breakdown = None
    if traced:
        xplane = trace.find_xplane(tmp)
        if keep_trace:
            shutil.copy(xplane, keep_trace)
        red = trace.reduce(trace.read(xplane))
        shutil.rmtree(tmp, ignore_errors=True)
        ctx = trace.Context(
            reduction=red, step_s=step_s,
            step_flops=[flops.step_flops(block, cfg["model"], x)
                        for x in lens],
            peaks=peaks, chips=wl["chips"], config=cfg, traffic=traffic,
            context_lens=lens, root=root)
        metrics = per_layer(spec, wl, ctx, root)
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops, "idle_gaps": red.idle_gaps}
    else:
        e2e = {"tokens_per_s": sum(live) / window_s,
               "step_s_p90": float(np.percentile(step_s, 90)),
               "peak_hbm_gib": peak / 2 ** 30,
               "setup_s": setup_s}
        for m in common.metrics_of(spec, wl["name"], "end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    del trainer, params, opt_state, ref_params, stages
    gc.collect()
    ref_steps, ref_g0, ref_change = reference_side(
        block, cfg, traffic, seed, prog_steps, limits)
    values = check.readings(prog_steps, prog_g0, prog_change, ref_steps,
                            ref_g0, ref_change,
                            [s["gen_mask"] for s in prog_steps])
    correct, lines = check.verdict(values, limits["limits"])
    result = {"correct": correct,
              "attempted": SETUP_STEPS + len(step_s),
              "failed": 0,
              "metrics": metrics,
              "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": values[k],
                            "limit": limits["limits"][k]}
                        for k in check.NUMBERS}
    return result, lines, n_compiles
