"""End-to-end agentic RL training driver (the paper's Fig. 2 loop, live).

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-0.5b --env tictactoe --steps 50 --batch 16

The model runs at the architecture's published widths and depth
(``configs/<arch>.py``, CONFIG); ``--smoke`` selects the reduced
SMOKE_CONFIG of the same family, sized for CPU tests.

Runs the full EARL system on the available devices: multi-turn rollouts,
experience preparation with a frozen reference model (folded into the
rollout macro-step), layout-aware dispatch, policy-gradient update, with
the Parallelism Selector monitoring context growth (on CPU the selector
profiles via the compiled cost model). ``--pipeline async`` overlaps
Rollout(k+1) with Update(k) one-step-off (``core/scheduler.py``), with
the truncated importance-sampling correction armed via ``--is-rho-max``.
Writes a JSONL training log usable by benchmarks/bench_context_growth.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax

from repro.configs.base import get_config, get_smoke_config
from repro.core.stages import EarlTrainer
from repro.models.registry import build_model
from repro.optim.adamw import adamw
from repro.rl.envs import make_env
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.faults import FaultInjector


def main(argv=None):
    ap = argparse.ArgumentParser(description="EARL agentic RL training")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--env", default="tictactoe",
                    choices=["tictactoe", "connect_four", "bandit"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--rollout-backend", default="python",
                    choices=["python", "compiled"],
                    help="python = per-token reference loop; compiled = "
                         "in-graph slot-based engine (one XLA program per "
                         "turn, continuous batching)")
    ap.add_argument("--rollout-episodes", type=int, default=None,
                    help="compiled backend: episodes per rollout (> batch "
                         "keeps slots full via in-graph refill)")
    ap.add_argument("--cache-layout", default="dense",
                    choices=["dense", "paged"],
                    help="compiled backend KV layout: dense = per-slot "
                         "(max_context,) rows; paged = shared page pool + "
                         "block tables (slot refill frees pages instead of "
                         "zeroing, pool memory scales with live tokens)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged layout: tokens per KV page")
    ap.add_argument("--cache-pages", type=int, default=None,
                    help="paged layout: pool size in pages (default: full "
                         "per-slot provisioning batch*ceil(ctx/page); pass "
                         "less to cap memory at expected live tokens)")
    ap.add_argument("--share-prefix", action="store_true",
                    help="paged layout: prefill the episodes' common "
                         "prompt prefix once and fork its pages across "
                         "slots (copy-on-write; prefix length from the "
                         "env's prompt_prefix_len unless --prefix-len)")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="override the env-declared shared-prompt length "
                         "in tokens (full pages of it are shared)")
    ap.add_argument("--on-exhaust", default="count",
                    choices=["count", "raise", "preempt"],
                    help="paged pool exhaustion: 'count' records dropped "
                         "KV writes in telemetry (default); 'raise' fails "
                         "the rollout with per-slot shortfalls; 'preempt' "
                         "evicts the longest-context slot and re-queues "
                         "its episode — zero dropped writes, an "
                         "undersized pool just runs slower")
    ap.add_argument("--pool-growth", default="off",
                    choices=["off", "double"],
                    help="paged layout: double the page pool between "
                         "macro-steps when it shows distress (dropped "
                         "write, preemption, or free pages under the "
                         "admission watermark), up to --pool-growth-max")
    ap.add_argument("--pool-growth-max", type=int, default=None,
                    help="pool growth cap in pages (default: full "
                         "per-slot provisioning)")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=["fp32", "bf16", "int8"],
                    help="KV cache element type; int8 (paged layout only) "
                         "stores quantized pages with per-entry scales "
                         "and dequantizes inside the decode kernel")
    ap.add_argument("--sampling", default="reference",
                    choices=["reference", "fused"],
                    help="fused = single Pallas pass that samples the "
                         "next token and feeds the decode write step "
                         "(compiled engine only)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off; ignored "
                         "when greedy)")
    ap.add_argument("--speculation", default="off",
                    choices=["off", "self", "draft"],
                    help="in-graph speculative decoding (compiled engine "
                         "+ paged layout): self = truncated-layer-stack "
                         "draft of the policy itself; committed tokens "
                         "are bit-identical to speculation=off")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative chunk length: 1 exact token + up "
                         "to spec-k - 1 draft proposals verified per "
                         "round")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="speculation=self: layers in the draft's "
                         "truncated stack (default n_layers // 2)")
    ap.add_argument("--pipeline", default="sync",
                    choices=["sync", "async"],
                    help="async = overlap Rollout(k+1) with Update(k) "
                         "across the rollout/trainer meshes (one-step-off "
                         "policy lag, bounded by --max-policy-lag)")
    ap.add_argument("--max-policy-lag", type=int, default=1,
                    help="async pipeline: max params-version staleness of "
                         "rollout experience (0 = sync-equivalent order)")
    ap.add_argument("--is-rho-max", type=float, default=2.0,
                    help="truncated importance-sampling cap for stale-"
                         "params experience (0 disables; only applied "
                         "when > 0)")
    ap.add_argument("--max-turns", type=int, default=3)
    ap.add_argument("--max-turn-tokens", type=int, default=6)
    ap.add_argument("--max-context", type=int, default=160)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--kl-coef", type=float, default=0.05)
    ap.add_argument("--clip-eps", type=float, default=0.2)
    ap.add_argument("--advantage", default="reinforce",
                    choices=["reinforce", "group"])
    ap.add_argument("--dispatch", default="direct",
                    choices=["direct", "centralized"])
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for periodic {params, opt_state, rng} "
                         "checkpoints (checkpoint/checkpoint.py)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save a checkpoint every N completed steps "
                         "(0 = off; requires --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="auto-resume from the latest checkpoint in "
                         "--checkpoint-dir when one exists")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="step-level retries (sync) / checkpoint restarts "
                         "(async) before a stage failure aborts the run")
    ap.add_argument("--retry-backoff", type=float, default=0.05,
                    help="base retry backoff in seconds (doubles per "
                         "attempt)")
    ap.add_argument("--inject-fault", action="append", default=None,
                    metavar="SITE@STEP[*TIMES]",
                    help="deterministically raise at a stage boundary, "
                         "e.g. 'update@3' or 'rollout@1*2' (sites: "
                         "rollout, dispatch, update; repeatable) — the "
                         "fault-injection harness for recovery testing")
    ap.add_argument("--inject-pool-pressure", type=float, default=0.0,
                    help="undersize the paged pool to this fraction of "
                         "its exhaustion-free provisioning (0 = off)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log", default="train_log.jsonl")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config of --arch (CPU-"
                         "sized) instead of its published widths")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.rollout_episodes is not None and args.rollout_backend != \
            "compiled":
        print("warning: --rollout-episodes only applies to the compiled "
              "backend (slot refill); ignoring it", file=sys.stderr)
        args.rollout_episodes = None

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    env = make_env(args.env)

    trainer = EarlTrainer(
        model=model, env=env,
        optimizer=adamw(args.lr, weight_decay=0.0),
        dispatch_strategy=args.dispatch,
        batch_size=args.batch, max_turns=args.max_turns,
        max_turn_tokens=args.max_turn_tokens, max_context=args.max_context,
        kl_coef=args.kl_coef, clip_eps=args.clip_eps,
        advantage=args.advantage, rollout_backend=args.rollout_backend,
        rollout_episodes=args.rollout_episodes,
        cache_layout=args.cache_layout, page_size=args.page_size,
        cache_pages=args.cache_pages, share_prefix=args.share_prefix,
        prefix_len=args.prefix_len, on_exhaust=args.on_exhaust,
        pool_growth=args.pool_growth,
        pool_growth_max=args.pool_growth_max,
        kv_dtype=args.kv_dtype, sampling=args.sampling, top_p=args.top_p,
        speculation=args.speculation, spec_k=args.spec_k,
        draft_layers=args.draft_layers,
        pipeline=args.pipeline,
        max_policy_lag=args.max_policy_lag,
        max_retries=args.max_retries,
        retry_backoff_s=args.retry_backoff,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        resume=args.resume,
        faults=(FaultInjector.parse(args.inject_fault,
                                    args.inject_pool_pressure)
                if args.inject_fault or args.inject_pool_pressure > 0
                else None),
        # lag 0 experience is on-policy: arming the correction there
        # would only inject decode-vs-forward fp noise into the weights
        # and break the documented sync-equivalence of lag-0 async runs
        is_rho_max=(args.is_rho_max if args.pipeline == "async"
                    and args.max_policy_lag > 0 else 0.0),
        seed=args.seed)

    t0 = time.time()
    params, opt_state, history = trainer.train(args.steps, verbose=True)
    wall = time.time() - t0

    log_path = Path(args.log)
    with log_path.open("w") as f:
        for rec in history:
            row = {
                "step": rec.step,
                "return": rec.mean_return,
                "context_len": rec.mean_context_len,
                "turn_len": rec.mean_turn_len,
                "truncated_frac": rec.truncated_frac,
                "loss": rec.loss,
                "kl": rec.kl,
                "wall_s": rec.wall_time_s,
                "params_version": rec.params_version,
                "policy_lag": rec.policy_lag,
                "is_weight_mean": rec.is_weight_mean,
                "pages_in_use": rec.pages_in_use,
                "page_capacity": rec.page_capacity,
                "kv_dropped_writes": rec.kv_dropped_writes,
                "preemptions": rec.preemptions,
                "requeue_depth": rec.requeue_depth,
                "pool_grows": rec.pool_grows,
                "spec_proposed": rec.spec_proposed,
                "spec_accepted": rec.spec_accepted,
                "spec_rounds": rec.spec_rounds,
            }
            f.write(json.dumps(row) + "\n")
    print(f"done: {args.steps} steps in {wall:.1f}s "
          f"({args.steps / max(wall, 1e-9):.2f} steps/s, "
          f"pipeline={args.pipeline}) -> {log_path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
