"""Compiled slot-based rollout engine (paper Fig. 2 ①, production shape).

One *macro-step* == one agent turn for every slot, compiled into a single
XLA program:

    [generation lax.scan over decode steps] -> [fallback actions] ->
    [env transition] -> [harvest finished episodes] ->
    [in-graph slot refill] -> [combined obs feed scan]

(the combined feed teacher-forces continuing rows' env observation AND
refilled rows' reset observation in ONE scan over obs_len decode steps, so
a turn costs max_turn_tokens + obs_len model evaluations total), and the
host syncs once per *turn* (a single scalar read of the
episodes-returned counter) instead of once per *token* — the python-loop
reference (``rl/rollout.py``) pays a device round-trip per decoded token,
which is the dominant overhead this engine removes.

**In-graph experience preparation** (``ref_params`` passed to ``run``):
the frozen reference model decodes the *same* token stream as the policy
inside the macro-step — one extra model evaluation per fed token, with
its own dense decode cache — and the per-token reference log-probs are
harvested alongside the behavior log-probs. ExpPrep then never re-runs a
forward pass over the full harvested context (paper §3.3: the tensor is
produced where the tokens already live, ready for the dispatcher).

Mesh integration (selector hook ①): the macro-step program is compiled
**per MeshConfig** (cache keyed by ``(mesh_config, B, N, with_ref)``)
with the slot carry's batch leaves bound to the mesh's (pod, data) axes
and the KV cache laid out by ``launch.mesh.cache_shardings``;
``bind_mesh`` re-binds the engine when the Parallelism Selector switches,
re-using previously compiled programs for revisited configs. The env
transition runs under ``shard_map`` when the data axis is >1 (envs are
row-wise pure ``jnp``, so each shard steps its rows locally with a
per-shard rng). Model compute itself is partitioned by GSPMD through the
in/out shardings + the activation constraints in ``models/layers.py`` —
manually ``shard_map``-ing the transformer body would drop the TP psum
GSPMD inserts after the attention/MLP output projections.

The harvested ``ExperienceBatch`` leaves keep the compiled out-shardings,
so ``EarlTrainer`` hands the Data Dispatcher a *real* ``src_shardings``
(``experience_shardings``) instead of inferring the source layout.

Telemetry: ``run(..., params_version=k)`` tags the resulting
``RolloutStats`` with the params version that generated the batch (the
async pipeline schedule's policy-lag accounting), and paged layouts
report peak pool occupancy + dropped KV writes instead of dropping
writes silently (``RolloutStats.pages_in_use`` / ``kv_dropped_writes``);
``on_exhaust="raise"`` turns a non-zero drop counter into a hard error
at the existing once-per-turn host sync.

**Prefix sharing** (``share_prefix=True``, paged layout): every
episode's initial observation opens with the env-declared common prefix
(``env.prompt_prefix_len`` tokens — system prompt / tool schemas / GRPO
group prompt). Its full pages are decoded ONCE through slot 0 at init,
pinned by an engine-held refcount, and *forked* into every slot's block
table — at init and again on every in-graph refill — so the dominant
fixed prompt cost is paid once per rollout instead of once per episode.
Refilled slots then feed only the per-episode suffix (a refill-only
wave runs a short suffix scan instead of the full obs_len scan), while
writes into shared pages are copy-on-write guarded. Greedy decode is
bit-identical to the unshared engine: per-row model math is
row-independent, so a forked page holds exactly the K/V the slot would
have computed itself. See ``rl/engine/README.md``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.models import transformer as dense_tf
from repro.rl.algo import reinforce_advantages
from repro.rl.engine import common, paging, slots
from repro.rl.engine.common import ACTION_BASE
from repro.rl.envs.base import TOK_PAD, default_reset_rows
from repro.rl.experience import ExperienceBatch
from repro.utils.tracing import span, to_host


def _reset_cache_rows(cache, refill):
    """Reset a decode cache row-wise for refilled slots (fresh episode).

    Paged caches release the slot's pages back to the shared pool — an
    O(pages_per_slot) bookkeeping update, no KV data touched (see
    ``rl/engine/paging.py``). Dense caches are zeroed generically over
    cache families: rank-1 leaves (``pos``) are per-row on dim 0,
    everything else (KV rings, conv windows, SSM states) on dim 1.
    Zeroing ``pos`` alone suffices for ring-buffer attention caches (slot
    validity is derived from ``pos``), but SSM/conv states are not
    position-invalidated — zeroing every leaf is correct for all families.
    """
    refill = jnp.asarray(refill)
    if paging.is_paged(cache):
        return paging.release_slot_pages(cache, refill)

    def zero(leaf):
        bdim = 0 if leaf.ndim == 1 else 1
        shape = [1] * leaf.ndim
        shape[bdim] = refill.shape[0]
        return jnp.where(refill.reshape(shape),
                         jnp.zeros((), leaf.dtype), leaf)

    return jax.tree.map(zero, cache)


class CompiledRolloutEngine:
    """In-graph multi-turn generation with slot-based continuous batching.

    Drop-in alternative to ``RolloutEngine``: ``run(params, rng, batch)``
    returns the same ``(ExperienceBatch, RolloutStats)``, and under greedy
    decoding (``temperature=0``) produces *identical trajectories* (tested
    parity). Additionally supports ``n_episodes > batch``: finished
    episodes free their slot and a fresh episode is reset into it
    in-graph, keeping the device batch full.
    """

    def __init__(self, model, env, *, max_turns: int = 4,
                 max_turn_tokens: int = 8, max_context: int = 256,
                 temperature: float = 1.0, top_p: float = 1.0,
                 sampling: str = "reference",
                 mesh_config=None, attn_impl: str = "xla",
                 cache_layout: str = "dense", page_size: int = 16,
                 cache_pages: Optional[int] = None,
                 kv_dtype: str = "bf16",
                 share_prefix: bool = False,
                 prefix_len: Optional[int] = None,
                 on_exhaust: str = "count",
                 pool_growth: str = "off",
                 pool_growth_max: Optional[int] = None,
                 admit_watermark: Optional[int] = None,
                 speculation: str = "off",
                 spec_k: int = 4,
                 draft_layers: Optional[int] = None,
                 draft_model=None):
        cfg = model.cfg
        assert ACTION_BASE + env.n_actions <= cfg.vocab_size
        assert getattr(env, "jit_safe", False), (
            f"{type(env).__name__} must declare jit_safe=True (pure-jnp "
            f"reset/step/encode_obs + reset_rows) for the compiled engine")
        assert env.obs_len + max_turn_tokens + env.obs_len <= max_context, (
            "max_context cannot fit even one turn")
        assert cache_layout in ("dense", "paged"), cache_layout
        if attn_impl == "paged" and cache_layout != "paged":
            raise ValueError(
                "attn_impl='paged' requires cache_layout='paged' (the "
                "kernel reads the pool through the block table)")
        if on_exhaust not in ("count", "raise", "preempt"):
            raise ValueError(f"on_exhaust must be 'count', 'raise' or "
                             f"'preempt', got {on_exhaust!r}")
        if on_exhaust == "preempt" and cache_layout != "paged":
            raise ValueError(
                "on_exhaust='preempt' requires cache_layout='paged' (the "
                "pressure governor releases and re-admits pool pages; "
                "dense rows have nothing to preempt)")
        if pool_growth not in ("off", "double"):
            raise ValueError(f"pool_growth must be 'off' or 'double', got "
                             f"{pool_growth!r}")
        if pool_growth != "off" and cache_layout != "paged":
            raise ValueError(
                "pool_growth requires cache_layout='paged' (growth appends "
                "free pages to the shared pool)")
        if share_prefix and cache_layout != "paged":
            raise ValueError(
                "share_prefix requires cache_layout='paged' (sharing works "
                "by forking pool pages across slots' block tables; dense "
                "rows have nothing to fork)")
        if kv_dtype not in ("fp32", "bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'fp32', 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if kv_dtype == "int8" and cache_layout != "paged":
            raise ValueError(
                "kv_dtype='int8' requires cache_layout='paged' — the "
                "quantization scales are a second page pool sharing the "
                "block-table/refcount lifecycle")
        if sampling not in ("reference", "fused"):
            raise ValueError(f"sampling must be 'reference' or 'fused', "
                             f"got {sampling!r}")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if speculation not in ("off", "self", "draft"):
            raise ValueError(f"speculation must be 'off', 'self' or "
                             f"'draft', got {speculation!r}")
        if speculation != "off":
            if cache_layout != "paged":
                raise ValueError(
                    "speculation requires cache_layout='paged' — the "
                    "verify pass bulk-scatters the candidate chunk into "
                    "pool pages before attending (see "
                    "models/transformer.spec_verify_step)")
            if cfg.family != "dense":
                raise ValueError(
                    f"speculation is a dense-family feature (the verify "
                    f"step and the draft's truncated layer stack live in "
                    f"models/transformer.py); got family "
                    f"{cfg.family!r}")
            if sampling == "fused":
                raise ValueError(
                    "speculation='"+speculation+"' is incompatible with "
                    "sampling='fused': the speculative path samples from "
                    "precomputed per-step noise rows so the committed "
                    "stream stays bit-identical to non-speculative "
                    "decode; the fused sampler draws one token per call")
            if spec_k < 2:
                raise ValueError(
                    f"spec_k must be >= 2 (k=1 is non-speculative "
                    f"decode), got {spec_k}")
        if speculation == "self":
            if draft_layers is None:
                draft_layers = max(1, cfg.n_layers // 2)
            if not 1 <= draft_layers < cfg.n_layers:
                raise ValueError(
                    f"draft_layers must be in [1, n_layers) = "
                    f"[1, {cfg.n_layers}), got {draft_layers}")
        if speculation == "draft":
            if draft_model is None:
                raise ValueError(
                    "speculation='draft' requires a draft_model (a small "
                    "registry Model whose params are passed to "
                    "run(draft_params=...)); use speculation='self' for "
                    "the truncated-layer-stack draft")
            if draft_model.cfg.family != "dense":
                raise ValueError("draft_model must be dense-family")
            if draft_model.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft_model vocab ({draft_model.cfg.vocab_size}) "
                    f"must match the policy's ({cfg.vocab_size}): the "
                    f"draft proposes token ids the verify pass scores")
        self.model = model
        self.env = env
        self.max_turns = max_turns
        self.max_turn_tokens = max_turn_tokens
        self.max_context = max_context
        self.temperature = temperature
        self.top_p = top_p
        self.sampling = sampling
        self.attn_impl = attn_impl
        self.cache_layout = cache_layout
        self.page_size = page_size
        self.cache_pages = cache_pages      # None = full provisioning
        self.kv_dtype = kv_dtype
        self.on_exhaust = on_exhaust
        self.pool_growth = pool_growth
        self.pool_growth_max = pool_growth_max
        # admission low-watermark (preempt mode): free pages the refill
        # path must keep AFTER admitting — one full turn's worth for the
        # slots already running, so admission never re-creates the
        # exhaustion it is recovering from
        self.admit_watermark = (
            admit_watermark if admit_watermark is not None
            else math.ceil((max_turn_tokens + env.obs_len) / page_size) + 1)
        self.speculation = speculation
        self.spec_k = spec_k
        self.draft_layers = draft_layers
        self.draft_model = draft_model
        if speculation == "self":
            import dataclasses
            self._draft_cfg = dataclasses.replace(cfg,
                                                  n_layers=draft_layers)
        elif speculation == "draft":
            self._draft_cfg = draft_model.cfg
        else:
            self._draft_cfg = None
        self.share_prefix = share_prefix
        # the shared run covers FULL pages of the episode-initial
        # observation's common prefix, and never the whole observation:
        # the per-slot feed must run at least one step so every slot's
        # logits are its own post-observation distribution. prefix_len
        # defaults to the env's declared contract (the leading tokens of
        # EVERY episode's initial observation that are identical).
        if prefix_len is None:
            prefix_len = int(getattr(env, "prompt_prefix_len", 0))
        self.prefix_len = prefix_len
        self.shared_pages = (
            min(prefix_len, env.obs_len - 1) // page_size
            if share_prefix else 0)
        self.shared_len = self.shared_pages * page_size
        self._mesh_config = mesh_config
        self._compiled: Dict[Tuple[Any, int, int, bool], Any] = {}
        # real source layout of the last harvested batch (Data Dispatcher
        # src_shardings — see EarlTrainer.run_step)
        self.experience_shardings: Optional[ExperienceBatch] = None

    # -- selector hook ① ----------------------------------------------------
    @property
    def mesh_config(self):
        """The MeshConfig the generation program is currently bound to
        (None = plain single-device jit)."""
        return self._mesh_config

    def bind_mesh(self, mesh_config) -> None:
        """Re-bind to a new MeshConfig (Parallelism Selector switch). The
        per-config compile cache means switching back to a previously used
        config costs nothing."""
        self._mesh_config = mesh_config

    def min_pool_pages(self, batch: int) -> int:
        """Smallest pool for which ``on_exhaust="preempt"`` can guarantee
        zero dropped KV writes at batch ``batch``: the pool must hold (a)
        one full-context episode — the designated survivor always makes
        progress even with every other slot evicted — and (b) the
        ungoverned initial feed, which fills every slot's initial
        observation before the first macro-step's pressure plan runs
        (``shared_pages`` pinned once + a private suffix per slot)."""
        ps = self.page_size
        pages_per_slot = -(-self.max_context // ps)
        per_admit = -(-(self.env.obs_len - self.shared_len) // ps)
        return max(pages_per_slot,
                   self.shared_pages + batch * per_admit)

    # -- compiled macro-step ------------------------------------------------
    def _build_turn_step(self, B: int, N: int, with_ref: bool):
        model, env = self.model, self.env
        T, olen = self.max_context, self.env.obs_len
        n_actions = env.n_actions
        mtt, mturns = self.max_turn_tokens, self.max_turns
        temperature, top_p = self.temperature, self.top_p
        fused_sampling = self.sampling == "fused"
        attn_impl = self.attn_impl
        paged = self.cache_layout == "paged"
        page_size = self.page_size
        shared_pages, shared_len = self.shared_pages, self.shared_len
        # the copy-on-write guard costs an allocator pass + per-layer
        # page copy per decode token; with sharing off no page can reach
        # refcount > 1, so drop it statically (PR-3 configs unchanged).
        # With sharing ON it stays armed as insurance even though the
        # engine's page-aligned runs never trigger it.
        cow_kw = {"cow": False} if paged and shared_pages == 0 else {}
        speculation = self.speculation
        spec_on = speculation != "off"
        spec_k = self.spec_k
        draft_cfg = self._draft_cfg
        draft_layers = self.draft_layers
        vocab = model.cfg.vocab_size
        model_cfg = model.cfg
        preempt = self.on_exhaust == "preempt"
        per_admit = -(-(olen - shared_len) // page_size)
        admit_wm = self.admit_watermark
        env_step = self._make_env_step(B)
        if preempt:
            # Row-wise env transition / reset with per-EPISODE keys
            # (``common.episode_env_rng`` / ``episode_reset_rng``):
            # preemption replays an episode in a different slot at a
            # different macro-step, so env randomness must be a function
            # of the episode's own coordinates, not the schedule's —
            # that is what makes an undersized-pool preempt run
            # bit-identical (greedy) to a right-sized one.
            def _row_step(state, action, key):
                one = lambda t: jax.tree.map(lambda x: x[0], t)
                s2, r = env.step(jax.tree.map(lambda x: x[None], state),
                                 action[None], key)
                return one(s2), one(r)

            rowwise_step = jax.vmap(_row_step)
            rowwise_reset = jax.vmap(
                lambda k: jax.tree.map(lambda x: x[0], env.reset(k, 1)))
        # envs usually declare reset_rows; the shared row-wise blend is
        # the fallback so a missing method isn't a runtime footgun
        reset_rows = getattr(
            env, "reset_rows",
            lambda rng, state, mask: default_reset_rows(env, rng, state,
                                                        mask))
        rows = jnp.arange(B)

        def ref_score(ref_logits, tok, mask, pos):
            """Reference log-prob of ``tok`` from the pre-advance ref
            logits; 0 at position 0 (no prediction for the first token,
            matching ``make_ref_logprob_step``)."""
            lp = common.token_lp(ref_logits, tok)
            return jnp.where(mask & (pos > 0), lp, 0.0)

        def feed_obs(decode, ref_decode, draft_decode, logits, cache,
                     ref_logits, ref_cache, tokens, ref_lp_buf, pos, obs,
                     mask, draft_cache=None, skip=None, n_skip: int = 0):
            """Teacher-force obs columns into ``mask`` rows (scan). The
            reference model (when folded in) consumes the same columns and
            scores each before advancing; the speculative draft model
            (when on) consumes them too so its cache tracks the committed
            stream (its logits are discarded — proposals always start
            from a freshly consumed c0). ``skip`` rows sit out the first
            ``n_skip`` columns (their cache already holds those tokens —
            the forked shared-prefix pages) and join at column
            ``n_skip``, where their fill position already points."""
            d_logits = (jnp.zeros((B, vocab), jnp.float32)
                        if draft_decode is not None else None)

            def body(carry, x):
                (logits, cache, ref_logits, ref_cache, tokens,
                 ref_lp_buf, pos, d_logits, draft_cache) = carry
                if n_skip > 0:
                    col, j = x
                    m = mask & (~skip | (j >= n_skip))
                else:
                    col, m = x, mask
                col = jnp.where(m, col, TOK_PAD).astype(jnp.int32)
                cidx = jnp.where(m, pos, T)              # OOB write -> drop
                tokens = tokens.at[rows, cidx].set(col, mode="drop")
                if ref_decode is not None:
                    rlp = ref_score(ref_logits, col, m, pos)
                    ref_lp_buf = ref_lp_buf.at[rows, cidx].set(
                        rlp, mode="drop")
                    (ref_logits, ref_cache), _ = ref_decode(
                        (ref_logits, ref_cache), (col, m))
                if draft_decode is not None:
                    (d_logits, draft_cache), _ = draft_decode(
                        (d_logits, draft_cache), (col, m))
                (logits, cache), _ = decode((logits, cache), (col, m))
                pos = pos + m.astype(jnp.int32)
                return (logits, cache, ref_logits, ref_cache, tokens,
                        ref_lp_buf, pos, d_logits, draft_cache), None

            cols = jnp.swapaxes(jnp.asarray(obs, jnp.int32), 0, 1)
            xs = ((cols, jnp.arange(cols.shape[0], dtype=jnp.int32))
                  if n_skip > 0 else cols)
            (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
             pos, _, draft_cache), _ = lax.scan(
                body, (logits, cache, ref_logits, ref_cache, tokens,
                       ref_lp_buf, pos, d_logits, draft_cache), xs)
            return (logits, cache, ref_logits, ref_cache, tokens,
                    ref_lp_buf, pos, draft_cache)

        def sample_and_write(decode, logits, cache, krng, write):
            """The fused sample-and-write step (``sampling="fused"``):
            ONE packaged op takes the final-layer logits, samples via the
            one-pass Pallas sampler (temperature / top-p / greedy), and
            immediately appends the sampled token's K/V into its page —
            the token feeds the decode write directly instead of
            round-tripping through the scan carry between two ops."""
            from repro.kernels.fused_sample import ops as fs_ops
            tok, lp = fs_ops.fused_sample_tokens(
                krng, logits, temperature, top_p=top_p)
            (new_logits, new_cache), _ = decode((logits, cache),
                                                (tok, write))
            return tok, lp, new_logits, new_cache

        def gen_turn(decode, ref_decode, logits, cache, ref_logits,
                     ref_cache, tokens, gen_mask, logprobs, ref_lp_buf,
                     pos, active, krngs):
            """One turn of generation: scan over ``mtt`` decode steps."""

            def body(carry, krng):
                (logits, cache, ref_logits, ref_cache, tokens, gen_mask,
                 logprobs, ref_lp_buf, pos, acted, actions, last_tok,
                 tl) = carry
                write = ~acted
                if fused_sampling:
                    # sample + KV append as one fused step; the buffer
                    # bookkeeping below depends only on (tok, lp), so
                    # the decode no longer waits behind it in dataflow
                    tok, lp, new_logits, cache = sample_and_write(
                        decode, logits, cache, krng, write)
                else:
                    tok, lp = common.sample_tokens(krng, logits,
                                                   temperature, top_p)
                cidx = jnp.where(write, pos, T)          # OOB write -> drop
                tokens = tokens.at[rows, cidx].set(tok, mode="drop")
                gen_mask = gen_mask.at[rows, cidx].set(True, mode="drop")
                logprobs = logprobs.at[rows, cidx].set(lp, mode="drop")
                if ref_decode is not None:
                    rlp = ref_score(ref_logits, tok, write, pos)
                    ref_lp_buf = ref_lp_buf.at[rows, cidx].set(
                        rlp, mode="drop")
                    (ref_logits, ref_cache), _ = ref_decode(
                        (ref_logits, ref_cache), (tok, write))
                pos = pos + write.astype(jnp.int32)
                tl = tl + write.astype(jnp.int32)
                last_tok = jnp.where(write, tok, last_tok)
                newly = write & common.action_mask(tok, n_actions)
                actions = jnp.where(newly, tok - ACTION_BASE, actions)
                acted = acted | newly
                if fused_sampling:
                    logits = new_logits
                else:
                    (logits, cache), _ = decode((logits, cache),
                                                (tok, write))
                return (logits, cache, ref_logits, ref_cache, tokens,
                        gen_mask, logprobs, ref_lp_buf, pos, acted,
                        actions, last_tok, tl), None

            zeros = jnp.zeros((B,), jnp.int32)
            init = (logits, cache, ref_logits, ref_cache, tokens, gen_mask,
                    logprobs, ref_lp_buf, pos, ~active, zeros, zeros, zeros)
            out, _ = lax.scan(body, init, krngs)
            return out

        def spec_gen_turn(params, d_params, logits, cache, draft_cache,
                          tokens, gen_mask, logprobs, pos, active, trng):
            """One turn of speculative generation: a ``lax.while_loop``
            over verify rounds instead of a scan over single decode steps.

            Each round, per still-writing row: c0 is sampled EXACTLY as
            the non-speculative engine would from the carried logits; the
            draft model then proposes up to ``spec_k - 1`` follow-on
            tokens sequentially; ONE batched ``spec_verify_step`` scores
            all chunk positions against the full model; and the longest
            prefix whose tokens match what the full model would have
            sampled (from the SAME per-step noise rows) is committed.
            Every round commits >= 1 token per writing row, so the loop
            runs at most ``mtt`` rounds and — because acceptance is
            judged against the exact non-speculative sampling rule — the
            committed stream is bit-identical to ``gen_turn``'s at equal
            rng (greedy always; sampled when the verify logits match the
            sequential logits bitwise, which the scatter-first verify
            kernel guarantees).
            """
            K = spec_k
            if temperature > 0.0:
                # per-step Gumbel noise from the SAME keys gen_turn uses:
                # jax.random.categorical(key, lg) == argmax(lg +
                # gumbel(key, lg.shape, f32)); row b's token at
                # turn-index t draws noise row (t, b) in both engines
                noise_all = jax.vmap(
                    lambda t: common.sample_noise(
                        common.sample_rng(trng, t), (B, vocab)))(
                            jnp.arange(mtt))
            else:
                noise_all = None
            dummy_noise = jnp.zeros((B, vocab), jnp.float32)

            def noise_at(step_idx):
                """(B,) per-row turn-step index -> (B,V) noise rows."""
                if noise_all is None:
                    return dummy_noise              # greedy: never read
                return noise_all[jnp.clip(step_idx, 0, mtt - 1), rows]

            def draft_step(tok, dc, adv):
                return dense_tf.decode_step(draft_cfg, d_params, tok, dc,
                                            advance=adv)

            def cond(carry):
                acted, tl = carry[7], carry[10]
                return jnp.any(active & ~acted & (tl < mtt))

            def body(carry):
                (logits, cache, draft_cache, tokens, gen_mask, logprobs,
                 pos, acted, actions, last_tok, tl, sp, sa, sr) = carry
                write = active & ~acted & (tl < mtt)
                ek = jnp.where(write, jnp.minimum(K, mtt - tl), 0)
                # c0: the exact token the non-speculative engine commits
                c0, lp0 = common.sample_with_noise(
                    logits, noise_at(tl), temperature, top_p)
                # draft proposes c1..c_{K-1}; it also consumes c_{K-1}
                # so its cache covers every position a full acceptance
                # could commit
                toks, lps = [c0], [lp0]
                d_logits, dc, cur = logits, draft_cache, c0
                for jj in range(K):
                    adv_j = write & (jj < ek)
                    dl_new, dc = draft_step(cur, dc, adv_j)
                    d_logits = jnp.where(adv_j[:, None], dl_new, d_logits)
                    if jj < K - 1:
                        cur, _ = common.sample_with_noise(
                            d_logits, noise_at(tl + jj + 1), temperature,
                            top_p)
                        toks.append(cur)
                chunk = jnp.stack(toks, axis=1)          # (B,K)
                # ONE batched verify pass: vlogits[:, j] is the full
                # model's distribution after consuming chunk[:, :j+1]
                vlogits, cache = dense_tf.spec_verify_step(
                    model_cfg, params, chunk, cache, attn_impl=attn_impl,
                    advance=write, eff_k=ek, **cow_kw)
                # acceptance: chunk[:, j] commits iff it IS the token the
                # non-speculative engine would sample from vlogits[:,j-1]
                # with that step's noise row (greedy: exact argmax match)
                match = write
                commits = write.astype(jnp.int32)        # c0 always
                for jj in range(1, K):
                    e_j, lp_j = common.sample_with_noise(
                        vlogits[:, jj - 1], noise_at(tl + jj),
                        temperature, top_p)
                    lps.append(lp_j)
                    match = match & (chunk[:, jj] == e_j) & (jj < ek)
                    commits = commits + match.astype(jnp.int32)
                # an action token ends the turn: never commit past the
                # first one (the scan engine stops writing after it)
                is_act = common.action_mask(chunk, n_actions)
                first_act = jnp.where(jnp.any(is_act, axis=1),
                                      jnp.argmax(is_act, axis=1), K)
                commits = jnp.minimum(commits, first_act + 1)
                commits = jnp.where(write, commits, 0)
                # buffer writes for all committed positions in one 2D
                # scatter (OOB column T drops the rest of the chunk)
                jarr = jnp.arange(K)[None, :]
                cmask = write[:, None] & (jarr < commits[:, None])
                cidx = jnp.where(cmask, pos[:, None] + jarr, T)
                lp_all = jnp.stack(lps, axis=1)          # (B,K)
                r2 = rows[:, None]
                tokens = tokens.at[r2, cidx].set(chunk, mode="drop")
                gen_mask = gen_mask.at[r2, cidx].set(True, mode="drop")
                logprobs = logprobs.at[r2, cidx].set(lp_all, mode="drop")
                # carried logits: the full model's distribution after the
                # last committed token — bitwise what sequential decode
                # would carry (non-writing rows keep theirs)
                lastj = jnp.clip(commits - 1, 0, K - 1)
                logits = jnp.where(write[:, None], vlogits[rows, lastj],
                                   logits)
                cache = dense_tf.spec_commit(cache, commits)
                # draft rollback: its fill line := the committed position
                # (ring validity is derived from pos, so entries above it
                # — rejected proposals — become invisible and are
                # overwritten by the next round's writes)
                dc = dc._replace(pos=pos + commits)
                last_commit = chunk[rows, lastj]
                last_tok = jnp.where(write, last_commit, last_tok)
                newly = write & (first_act < commits)
                act_tok = chunk[rows, jnp.clip(first_act, 0, K - 1)]
                actions = jnp.where(newly, act_tok - ACTION_BASE, actions)
                acted = acted | newly
                pos = pos + commits
                tl = tl + commits
                sp = sp + jnp.sum(jnp.maximum(ek - 1, 0))
                sa = sa + jnp.sum(jnp.where(write, commits - 1, 0))
                sr = sr + jnp.sum(write.astype(jnp.int32))
                return (logits, cache, dc, tokens, gen_mask, logprobs,
                        pos, acted, actions, last_tok, tl, sp, sa, sr)

            zeros = jnp.zeros((B,), jnp.int32)
            z0 = jnp.asarray(0, jnp.int32)
            init = (logits, cache, draft_cache, tokens, gen_mask,
                    logprobs, pos, ~active, zeros, zeros, zeros, z0, z0,
                    z0)
            return lax.while_loop(cond, body, init)

        def write_prefix_tokens(tokens, obs, rows_mask):
            """Bulk-write the (skipped) shared-prefix observation tokens
            into ``rows_mask`` rows' context buffers: the harvested
            episode must carry its full prompt even though the model
            never re-consumed the prefix columns (the forked pages hold
            their K/V)."""
            pre = jnp.asarray(obs, jnp.int32)[:, :shared_len]
            pad = jnp.pad(pre, ((0, 0), (0, T - shared_len)))
            m = rows_mask[:, None] & (jnp.arange(T)[None, :] < shared_len)
            return jnp.where(m, pad, tokens)

        def make_draft(params, draft_params):
            """(draft params pytree, scan body) for the active speculation
            mode; ``"self"`` slices the policy's own layer stack in-graph
            (a view — XLA aliases it, no copy)."""
            if not spec_on:
                return None, None
            d_params = (dense_tf.draft_params_view(params, draft_layers)
                        if speculation == "self" else draft_params)
            return d_params, dense_tf.decode_scan_body(draft_cfg, d_params)

        def init_feed(params, ref_params, draft_params,
                      carry: slots.SlotCarry):
            """Feed the initial observation of every live slot (the
            engine's "prefill", run once before the macro-step loop)."""
            decode = model.decode_scan_body(params, attn_impl=attn_impl,
                                            **cow_kw)
            ref_decode = (model.decode_scan_body(ref_params)
                          if with_ref else None)
            _, draft_decode = make_draft(params, draft_params)
            obs = env.encode_obs(carry.env_state)
            if shared_pages == 0:
                (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
                 pos, draft_cache) = feed_obs(
                    decode, ref_decode, draft_decode, carry.logits,
                    carry.cache, carry.ref_logits, carry.ref_cache,
                    carry.tokens, carry.ref_logprobs, carry.pos, obs,
                    carry.live, draft_cache=carry.draft_cache)
                return carry._replace(logits=logits, cache=cache,
                                      ref_logits=ref_logits,
                                      ref_cache=ref_cache, tokens=tokens,
                                      ref_logprobs=ref_lp_buf, pos=pos,
                                      draft_cache=draft_cache)
            # shared-prefix init: decode the common prefix through slot 0
            # ONLY (per-row math is row-independent, so the pages slot 0
            # fills hold bitwise the K/V any slot would have computed),
            # pin the run, fork it into every live slot's block table,
            # then feed just the per-slot suffix columns.
            row0 = rows == 0                    # slot 0 is live (N >= 1)
            (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
             pos, draft_cache) = feed_obs(
                decode, ref_decode, draft_decode, carry.logits,
                carry.cache, carry.ref_logits, carry.ref_cache,
                carry.tokens, carry.ref_logprobs, carry.pos,
                obs[:, :shared_len], row0 & carry.live,
                draft_cache=carry.draft_cache)
            prefix_pages = cache.block_table[0, :shared_pages]
            # engine-held pin; guard unmapped entries (pool exhausted
            # during the slot-0 feed): -1 would WRAP, not drop
            pin = jnp.where(prefix_pages >= 0, prefix_pages,
                            cache.refcount.shape[0])
            cache = cache._replace(
                refcount=cache.refcount.at[pin].add(1, mode="drop"))
            cache = paging.fork_prefix(cache, prefix_pages,
                                       carry.live & ~row0, shared_len)
            pos = jnp.where(carry.live, shared_len, pos)
            if spec_on:
                # the draft's dense cache cannot fork pool pages: rows
                # other than slot 0 skip the prefix columns with ZERO
                # draft K/V behind their fill line — draft predictions
                # degrade (lower acceptance) but the verify pass gates
                # every commit, so the committed stream is unaffected
                draft_cache = draft_cache._replace(
                    pos=jnp.where(carry.live, shared_len,
                                  draft_cache.pos))
            tokens = write_prefix_tokens(tokens, obs, carry.live)
            (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
             pos, draft_cache) = feed_obs(
                decode, ref_decode, draft_decode, logits, cache,
                ref_logits, ref_cache, tokens, ref_lp_buf, pos,
                obs[:, shared_len:], carry.live, draft_cache=draft_cache)
            return carry._replace(logits=logits, cache=cache,
                                  ref_logits=ref_logits,
                                  ref_cache=ref_cache, tokens=tokens,
                                  ref_logprobs=ref_lp_buf, pos=pos,
                                  prefix_pages=prefix_pages,
                                  draft_cache=draft_cache)

        def turn_step(params, ref_params, draft_params,
                      carry: slots.SlotCarry, trng, brng):
            # invariant: every live slot's observation is already fed (by
            # init_feed or the previous step's combined feed), so the turn
            # starts generating immediately
            decode = model.decode_scan_body(params, attn_impl=attn_impl,
                                            **cow_kw)
            ref_decode = (model.decode_scan_body(ref_params)
                          if with_ref else None)
            d_params, draft_decode = make_draft(params, draft_params)
            c = carry

            # 0. memory-pressure governor (preempt mode): BEFORE anything
            #    generates, plan which slots may write this turn and which
            #    must be evicted. Stalled slots keep their pages and their
            #    fed observation and simply sit the turn out; victims
            #    release their private pages (prefix-shared pages survive
            #    via refcounts) and their episode enters the requeue
            #    bitmap for a from-scratch restart.
            if preempt:
                room0 = c.pos + mtt + olen <= T
                elig = c.live & room0 & (c.n_turns < mturns)
                npw = c.cache.block_table.shape[1]
                tgt = jnp.minimum(c.pos + mtt + olen, npw * page_size)
                tgt_pages = (tgt + page_size - 1) // page_size
                mapped = jnp.sum((c.cache.block_table >= 0)
                                 .astype(jnp.int32), axis=1)
                demand = jnp.where(elig,
                                   jnp.maximum(tgt_pages - mapped, 0), 0)
                run_mask, victims = paging.pressure_plan(
                    c.cache.refcount, c.cache.block_table, elig, c.pos,
                    demand)
                requeue = c.requeue.at[
                    jnp.where(victims, c.episode, N)].set(
                        True, mode="drop")
                c = c._replace(
                    cache=paging.release_slot_pages(c.cache, victims),
                    live=c.live & ~victims,
                    truncated=c.truncated & ~victims,
                    episode=jnp.where(victims, N, c.episode),
                    preempted=(c.preempted
                               + jnp.sum(victims.astype(jnp.int32))),
                    requeue=requeue,
                    requeue_peak=jnp.maximum(
                        c.requeue_peak,
                        jnp.sum(requeue.astype(jnp.int32))),
                )

            # 1. truncation / active set (same predicate as the reference)
            room = c.pos + mtt + olen <= T
            truncated = c.truncated | (c.live & ~room)
            active = c.live & room & (c.n_turns < mturns)
            if preempt:
                active = active & run_mask

            # 2. generation: a scan over single decode steps, or — with
            #    speculation on — a while_loop over draft-propose /
            #    batch-verify rounds committing the same token stream
            #    (per-token keys from the shared derivation in both — the
            #    parity contract with the python engine)
            if spec_on:
                (logits, cache, draft_cache, tokens, gen_mask, logprobs,
                 pos, acted, actions, last_tok, tl, d_sp, d_sa,
                 d_sr) = spec_gen_turn(
                    params, d_params, c.logits, c.cache, c.draft_cache,
                    c.tokens, c.gen_mask, c.logprobs, c.pos, active, trng)
                ref_logits, ref_cache = c.ref_logits, c.ref_cache
                ref_lp_buf = c.ref_logprobs
                spec_proposed = c.spec_proposed + d_sp
                spec_accepted = c.spec_accepted + d_sa
                spec_rounds = c.spec_rounds + d_sr
            else:
                krngs = jax.vmap(lambda t: common.sample_rng(trng, t))(
                    jnp.arange(mtt))
                (logits, cache, ref_logits, ref_cache, tokens, gen_mask,
                 logprobs, ref_lp_buf, pos, acted, actions, last_tok,
                 tl) = gen_turn(
                    decode, ref_decode, c.logits, c.cache, c.ref_logits,
                    c.ref_cache, c.tokens, c.gen_mask, c.logprobs,
                    c.ref_logprobs, c.pos, active, krngs)
                draft_cache = c.draft_cache
                spec_proposed, spec_accepted, spec_rounds = (
                    c.spec_proposed, c.spec_accepted, c.spec_rounds)

            # 2b. paged-pool telemetry, measured post-generation (peak
            #     occupancy: finished slots have not released yet). The
            #     dropped-write counter accumulates per-slot shortfall
            #     *growth* so recovery-mapped pages never un-count a drop.
            pages_peak, kv_dropped, kv_shortfall = (
                c.pages_peak, c.kv_dropped, c.kv_shortfall)
            if paged:
                occ, _ = paging.pool_stats(cache)
                pages_peak = jnp.maximum(pages_peak, occ)
                drop_now = paging.dropped_tokens(cache, page_size)
                kv_dropped = kv_dropped + jnp.sum(
                    jnp.maximum(drop_now - kv_shortfall, 0))
                kv_shortfall = drop_now

            # 3. action fallback + turn accounting
            actions = common.fallback_actions(actions, last_tok, active,
                                              acted, n_actions)
            turn_idx = jnp.clip(c.n_turns, 0, mturns - 1)
            turn_lengths = c.turn_lengths.at[rows, turn_idx].add(
                jnp.where(active, tl, 0))
            n_turns = c.n_turns + active.astype(jnp.int32)

            # 4. env transition (inactive rows absorb inside env.step).
            #    Preempt mode steps row-wise with episode-keyed rng and
            #    blends inactive rows back to their prior state — a
            #    stalled slot must be a perfect no-op, not an env step
            #    with a zero action.
            env_actions = jnp.where(active, actions, 0).astype(jnp.int32)
            if preempt:
                ekeys = jax.vmap(
                    lambda e, t: common.episode_env_rng(brng, e, t))(
                        c.episode, c.n_turns)
                s2f, res = rowwise_step(c.env_state, env_actions, ekeys)
                keep = lambda new, old: jnp.where(
                    active.reshape((B,) + (1,) * (new.ndim - 1)), new, old)
                state2 = jax.tree.map(keep, s2f, c.env_state)
            else:
                state2, res = env_step(c.env_state, env_actions,
                                       common.env_rng(trng))

            # 5. episodes finishing this turn (terminal / truncated / out
            #    of turn budget) -> harvest into the episode store
            #    (truncated -> zero reward, the Fig. 1 "low-quality data"
            #    rule)
            finished = c.live & (state2.done | truncated
                                 | (n_turns >= mturns))
            rewards_row = jnp.where(truncated, 0.0,
                                    state2.reward).astype(jnp.float32)
            store = slots.harvest(
                c.store, finished=finished, episode=c.episode,
                tokens=tokens, gen_mask=gen_mask, logprobs=logprobs,
                ref_logprobs=ref_lp_buf if with_ref else None,
                rewards=rewards_row, pos=pos, truncated=truncated,
                n_turns=n_turns, turn_lengths=turn_lengths)
            returned = c.returned + jnp.sum(finished.astype(jnp.int32))

            # 6. slot refill: reset fresh episodes into freed slots
            #    (lax.cond skips the env reset and buffer/cache resets on
            #    the common no-refill step). Preempt mode swaps the plain
            #    refill plan for the watermark-gated admission plan: ALL
            #    finished slots release their pages first (headroom must
            #    see them), re-queued episodes are re-admitted before any
            #    fresh launch, and admission is capped so that free pages
            #    after the continuing slots' obs feeds stay above the
            #    low-watermark.
            rrng = common.reset_rng(trng)
            if preempt:
                cache = paging.release_slot_pages(cache, finished)
                free_now = jnp.sum((cache.refcount == 0)
                                   .astype(jnp.int32))
                npw = cache.block_table.shape[1]
                mapped_now = jnp.sum((cache.block_table >= 0)
                                     .astype(jnp.int32), axis=1)
                cont_pre = active & ~state2.done & ~finished
                tgt2 = jnp.minimum(pos + olen, npw * page_size)
                tgt2_pages = (tgt2 + page_size - 1) // page_size
                reserved = jnp.sum(jnp.where(
                    cont_pre, jnp.maximum(tgt2_pages - mapped_now, 0), 0))
                quota = jnp.maximum(
                    (free_now - reserved - admit_wm) // per_admit, 0)
                # deadlock breaker: with no survivor but work remaining,
                # every unpinned page is free (finished + victims all
                # released) — admit at least one episode so the rollout
                # always drains (min_pool_pages guarantees it fits)
                surv = jnp.any(c.live & ~finished)
                work_left = (c.launched < N) | jnp.any(c.requeue)
                quota = jnp.where(~surv & work_left,
                                  jnp.maximum(quota, 1), quota)
                free_slots = finished | (~c.live & ~victims)
                refill, new_ids, launched, requeue = slots.admission_plan(
                    free_slots, c.requeue, c.launched, N, quota)
            else:
                refill, new_ids, launched = slots.refill_plan(
                    finished, c.launched, N)
                requeue = c.requeue
            r1 = refill[:, None]

            def do_reset(args):
                (cache, ref_cache, draft_cache, tokens, gen_mask, logprobs,
                 ref_lp_buf, pos, n_turns, tls, shortfall, state) = args
                cache = _reset_cache_rows(cache, refill)
                if spec_on:
                    # fresh episode: zero the draft rows; with prefix
                    # sharing its fill line starts at shared_len with
                    # zero K/V behind it (acceptance-only degradation —
                    # see init_feed)
                    draft_cache = _reset_cache_rows(draft_cache, refill)
                    draft_cache = draft_cache._replace(
                        pos=jnp.where(refill, shared_len,
                                      draft_cache.pos))
                if shared_pages > 0:
                    # fresh episode inherits the pinned shared-prefix run:
                    # fork its pages into the freed slot's block table and
                    # start the slot's own writes after them — the
                    # prefix's KV is never recomputed for a refill
                    cache = paging.fork_prefix(cache, c.prefix_pages,
                                               refill, shared_len)
                if preempt:
                    # episode-keyed reset: a re-admitted episode draws the
                    # SAME initial state it drew at first launch
                    rkeys = jax.vmap(
                        lambda e: common.episode_reset_rng(brng, e))(
                            jnp.where(refill, new_ids, 0))
                    fresh = rowwise_reset(rkeys)
                    keep = lambda new, old: jnp.where(
                        refill.reshape((B,) + (1,) * (new.ndim - 1)),
                        new, old)
                    state_reset = jax.tree.map(keep, fresh, state)
                else:
                    state_reset = reset_rows(rrng, state, refill)
                return (cache,
                        (_reset_cache_rows(ref_cache, refill)
                         if with_ref else ref_cache),
                        draft_cache,
                        jnp.where(r1, TOK_PAD, tokens),
                        jnp.where(r1, False, gen_mask),
                        jnp.where(r1, 0.0, logprobs),
                        (jnp.where(r1, 0.0, ref_lp_buf)
                         if with_ref else ref_lp_buf),
                        jnp.where(refill, shared_len, pos),
                        jnp.where(refill, 0, n_turns),
                        jnp.where(r1, 0, tls),
                        jnp.where(refill, 0, shortfall),
                        state_reset)

            (cache, ref_cache, draft_cache, tokens, gen_mask, logprobs,
             ref_lp_buf, pos, n_turns, turn_lengths, kv_shortfall,
             state3) = lax.cond(
                jnp.any(refill), do_reset, lambda args: args,
                (cache, ref_cache, draft_cache, tokens, gen_mask,
                 logprobs, ref_lp_buf, pos, n_turns, turn_lengths,
                 kv_shortfall, state2))

            # 7. ONE combined obs feed: continuing rows teacher-force the
            #    env observation, refilled rows their reset observation —
            #    a single scan over obs_len decode steps per macro-step,
            #    skipped entirely (lax.cond) when no row needs it (e.g.
            #    the final drain step). With prefix sharing, refilled rows
            #    skip the shared columns (their forked pages already hold
            #    that K/V); a refill-only wave — the common case under
            #    churn — runs the SHORT suffix scan, which is where the
            #    per-wave prefill-FLOP cut lands.
            cont = active & ~state2.done & ~finished
            feed_mask = cont | refill

            def do_feed(args):
                (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
                 pos, draft_cache) = args
                obs = jnp.where(r1, env.encode_obs(state3),
                                jnp.asarray(res.obs_tokens))
                if shared_pages == 0:
                    return feed_obs(decode, ref_decode, draft_decode,
                                    logits, cache, ref_logits, ref_cache,
                                    tokens, ref_lp_buf, pos, obs,
                                    feed_mask, draft_cache=draft_cache)
                tokens = write_prefix_tokens(tokens, obs, refill)

                def full(a):
                    (logits, cache, ref_logits, ref_cache, tokens,
                     ref_lp_buf, pos, draft_cache) = a
                    return feed_obs(decode, ref_decode, draft_decode,
                                    logits, cache, ref_logits, ref_cache,
                                    tokens, ref_lp_buf, pos, obs,
                                    feed_mask, draft_cache=draft_cache,
                                    skip=refill, n_skip=shared_len)

                def suffix_only(a):
                    (logits, cache, ref_logits, ref_cache, tokens,
                     ref_lp_buf, pos, draft_cache) = a
                    return feed_obs(decode, ref_decode, draft_decode,
                                    logits, cache, ref_logits, ref_cache,
                                    tokens, ref_lp_buf, pos,
                                    obs[:, shared_len:], refill,
                                    draft_cache=draft_cache)

                return lax.cond(jnp.any(cont), full, suffix_only,
                                (logits, cache, ref_logits, ref_cache,
                                 tokens, ref_lp_buf, pos, draft_cache))

            (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
             pos, draft_cache) = lax.cond(
                jnp.any(feed_mask), do_feed, lambda args: args,
                (logits, cache, ref_logits, ref_cache, tokens, ref_lp_buf,
                 pos, draft_cache))

            return slots.SlotCarry(
                cache=cache,
                logits=logits,
                env_state=state3,
                tokens=tokens,
                gen_mask=gen_mask,
                logprobs=logprobs,
                pos=pos,
                live=(c.live & ~finished) | refill,
                truncated=jnp.where(finished | refill, False, truncated),
                n_turns=n_turns,
                turn_lengths=turn_lengths,
                episode=jnp.where(refill, new_ids,
                                  jnp.where(finished, N, c.episode)),
                launched=launched,
                returned=returned,
                store=store,
                ref_cache=ref_cache,
                ref_logits=ref_logits,
                ref_logprobs=ref_lp_buf,
                pages_peak=pages_peak,
                kv_dropped=kv_dropped,
                kv_shortfall=kv_shortfall,
                prefix_pages=c.prefix_pages,
                preempted=c.preempted,
                requeue=requeue,
                requeue_peak=c.requeue_peak,
                draft_cache=draft_cache,
                spec_proposed=spec_proposed,
                spec_accepted=spec_accepted,
                spec_rounds=spec_rounds,
            )

        return init_feed, turn_step

    # -- env transition (shard_map over the data axis when sharded) ---------
    def _make_env_step(self, B: int):
        env = self.env
        mesh_cfg = self._mesh_config
        if mesh_cfg is None:
            return env.step
        mesh = mesh_cfg.make_mesh()
        if (mesh_cfg.pods > 1 or "data" not in mesh.axis_names
                or mesh.shape["data"] <= 1 or B % mesh.shape["data"] != 0):
            return env.step                  # GSPMD partitions it instead

        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P

        def body(state, actions, rng):
            # per-shard rng: decorrelate opponent noise across data shards
            rng = jax.random.fold_in(rng, lax.axis_index("data"))
            return env.step(state, actions, rng)

        return shard_map(body, mesh=mesh,
                         in_specs=(P("data"), P("data"), P()),
                         out_specs=(P("data"), P("data")))

    # -- compile cache ------------------------------------------------------
    def _get_compiled(self, B: int, N: int, with_ref: bool):
        key = (self._mesh_config, B, N, with_ref)
        fn = self._compiled.get(key)
        if fn is None:
            fn = self._compile(B, N, with_ref)
            self._compiled[key] = fn
        return fn

    def _compile(self, B: int, N: int, with_ref: bool):
        init_feed, turn_step = self._build_turn_step(B, N, with_ref)
        if self._mesh_config is None:
            return (jax.jit(init_feed, donate_argnums=(3,)),
                    jax.jit(turn_step, donate_argnums=(3,)))

        mesh = self._mesh_config.make_mesh()
        carry_sh = self._carry_shardings(mesh, B, N, with_ref)
        jf_init = jax.jit(init_feed,
                          in_shardings=(None, None, None, carry_sh),
                          out_shardings=carry_sh, donate_argnums=(3,))
        jf_turn = jax.jit(turn_step,
                          in_shardings=(None, None, None, carry_sh, None,
                                        None),
                          out_shardings=carry_sh, donate_argnums=(3,))

        def call_init(params, ref_params, draft_params, carry):
            with jax.set_mesh(mesh):     # anchors layers.constrain
                return jf_init(params, ref_params, draft_params, carry)

        def call_turn(params, ref_params, draft_params, carry, trng,
                      brng):
            with jax.set_mesh(mesh):
                return jf_turn(params, ref_params, draft_params, carry,
                               trng, brng)

        return call_init, call_turn

    def _carry_shardings(self, mesh, B: int, N: int, with_ref: bool):
        """Batch leaves over (pod, data); KV cache by the production cache
        rules; scalars replicated."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import _batch_spec, cache_shardings

        rep = NamedSharding(mesh, P())
        bs = lambda leaf: _batch_spec(mesh, leaf.shape)
        carry_abs = jax.eval_shape(
            lambda: self._init_carry(jax.random.PRNGKey(0), B, N, with_ref))
        batched = lambda tree: jax.tree.map(bs, tree)
        csh = lambda c: cache_shardings(
            c, mesh, seq_len=self.max_context,
            n_kv_heads=self.model.cfg.n_kv_heads)
        return slots.SlotCarry(
            cache=csh(carry_abs.cache),
            logits=bs(carry_abs.logits),
            env_state=batched(carry_abs.env_state),
            tokens=bs(carry_abs.tokens),
            gen_mask=bs(carry_abs.gen_mask),
            logprobs=bs(carry_abs.logprobs),
            pos=bs(carry_abs.pos),
            live=bs(carry_abs.live),
            truncated=bs(carry_abs.truncated),
            n_turns=bs(carry_abs.n_turns),
            turn_lengths=bs(carry_abs.turn_lengths),
            episode=bs(carry_abs.episode),
            launched=rep,
            returned=rep,
            store=batched(carry_abs.store),
            ref_cache=csh(carry_abs.ref_cache) if with_ref else None,
            ref_logits=bs(carry_abs.ref_logits) if with_ref else None,
            ref_logprobs=bs(carry_abs.ref_logprobs) if with_ref else None,
            pages_peak=rep,
            kv_dropped=rep,
            kv_shortfall=bs(carry_abs.kv_shortfall),
            prefix_pages=(rep if carry_abs.prefix_pages is not None
                          else None),
            preempted=(rep if carry_abs.preempted is not None else None),
            requeue=(bs(carry_abs.requeue)
                     if carry_abs.requeue is not None else None),
            requeue_peak=(rep if carry_abs.requeue_peak is not None
                          else None),
            draft_cache=(csh(carry_abs.draft_cache)
                         if carry_abs.draft_cache is not None else None),
            spec_proposed=(rep if carry_abs.spec_proposed is not None
                           else None),
            spec_accepted=(rep if carry_abs.spec_accepted is not None
                           else None),
            spec_rounds=(rep if carry_abs.spec_rounds is not None
                         else None),
        )

    # -- carry init ---------------------------------------------------------
    def _init_carry(self, rng, B: int, N: int,
                    with_ref: bool = False) -> slots.SlotCarry:
        env, model = self.env, self.model
        T = self.max_context
        preempt = self.on_exhaust == "preempt"
        if preempt:
            # episode-keyed initial state: slot i starts episode i, drawn
            # with the SAME key a later re-admission of episode i uses
            brng = jax.random.fold_in(rng, 2)
            keys = jax.vmap(
                lambda e: common.episode_reset_rng(brng, e))(
                    jnp.arange(B, dtype=jnp.int32))
            state = jax.vmap(
                lambda k: jax.tree.map(lambda x: x[0], env.reset(k, 1)))(
                    keys)
        else:
            state = env.reset(rng, B)
        live = jnp.arange(B) < N
        if self.cache_layout == "paged":
            n_pages = self.cache_pages
            if n_pages is None and self.shared_pages > 0:
                # sharing-aware full provisioning: the shared run is one
                # allocation, not one per slot — the default pool for
                # share_prefix must not over-provision it batch x
                from repro.models.paging import pool_pages_needed_shared
                n_pages = pool_pages_needed_shared(
                    B, T, self.shared_len, self.page_size)
            cache = model.init_cache(B, T, layout="paged",
                                     page_size=self.page_size,
                                     n_pages=n_pages,
                                     kv_dtype=self.kv_dtype)
        else:
            # default "bf16" keeps the family-generic call (SSM/hybrid
            # caches have no kv_dtype knob); anything else is opt-in and
            # signature-checked by the registry
            kw = ({} if self.kv_dtype == "bf16"
                  else {"kv_dtype": self.kv_dtype})
            cache = model.init_cache(B, T, **kw)
        return slots.SlotCarry(
            cache=cache,
            logits=jnp.zeros((B, model.cfg.vocab_size), jnp.float32),
            env_state=state,
            tokens=jnp.full((B, T), TOK_PAD, jnp.int32),
            gen_mask=jnp.zeros((B, T), bool),
            logprobs=jnp.zeros((B, T), jnp.float32),
            pos=jnp.zeros((B,), jnp.int32),
            live=live,
            truncated=jnp.zeros((B,), bool),
            n_turns=jnp.zeros((B,), jnp.int32),
            turn_lengths=jnp.zeros((B, self.max_turns), jnp.int32),
            episode=jnp.where(live, jnp.arange(B), N).astype(jnp.int32),
            launched=jnp.asarray(min(B, N), jnp.int32),
            returned=jnp.asarray(0, jnp.int32),
            store=slots.init_store(N, T, self.max_turns),
            # the reference decode cache is always dense: it exists for
            # one rollout and its footprint is the policy's dense cost —
            # pool sizing stays a policy-cache-only concern
            ref_cache=model.init_cache(B, T) if with_ref else None,
            ref_logits=(jnp.zeros((B, model.cfg.vocab_size), jnp.float32)
                        if with_ref else None),
            ref_logprobs=(jnp.zeros((B, T), jnp.float32)
                          if with_ref else None),
            pages_peak=jnp.asarray(0, jnp.int32),
            kv_dropped=jnp.asarray(0, jnp.int32),
            kv_shortfall=jnp.zeros((B,), jnp.int32),
            prefix_pages=(jnp.full((self.shared_pages,), -1, jnp.int32)
                          if self.shared_pages > 0 else None),
            preempted=(jnp.asarray(0, jnp.int32) if preempt else None),
            requeue=(jnp.zeros((N,), bool) if preempt else None),
            requeue_peak=(jnp.asarray(0, jnp.int32) if preempt else None),
            # the draft's cache is always dense (its footprint is small —
            # a truncated stack or a small model — so pool sizing stays a
            # policy-cache-only concern, like the ref cache)
            draft_cache=(dense_tf.init_cache(self._draft_cfg, B, T)
                         if self.speculation != "off" else None),
            spec_proposed=(jnp.asarray(0, jnp.int32)
                           if self.speculation != "off" else None),
            spec_accepted=(jnp.asarray(0, jnp.int32)
                           if self.speculation != "off" else None),
            spec_rounds=(jnp.asarray(0, jnp.int32)
                         if self.speculation != "off" else None),
        )

    # ------------------------------------------------------------------
    def run(self, params, rng, batch: int, *, n_episodes: Optional[int] =
            None, extra=None, ref_params=None, draft_params=None,
            params_version: int = -1):
        """Roll out ``n_episodes`` (default: ``batch``) episodes over
        ``batch`` device slots. Returns (ExperienceBatch, RolloutStats).

        ``ref_params`` folds the reference-model log-prob pass into the
        macro-step (in-graph ExpPrep); ``draft_params`` are the
        registered small model's params for ``speculation="draft"``
        (``"self"`` slices the policy's own stack in-graph and needs
        none); ``params_version`` tags the stats with the update counter
        of ``params`` for policy-lag accounting.
        """
        del extra
        B = int(batch)
        N = int(n_episodes) if n_episodes is not None else B
        assert N >= 1 and B >= 1
        with_ref = ref_params is not None
        if with_ref and self.shared_pages > 0:
            raise ValueError(
                "share_prefix with in-graph ExpPrep (ref_params) is not "
                "supported yet: the reference model's dense cache cannot "
                "fork prefix pages, so refilled slots would skip tokens "
                "the ref pass needs. Run the reference log-prob pass "
                "separately (make_ref_logprob_step) or disable "
                "share_prefix.")
        if with_ref and self.speculation != "off":
            raise ValueError(
                "speculation with in-graph ExpPrep (ref_params) is not "
                "supported: the folded reference pass consumes tokens "
                "one scan step at a time and cannot consume drafted "
                "chunks. Run the reference log-prob pass separately "
                "(make_ref_logprob_step) or turn speculation off.")
        if self.speculation == "draft" and draft_params is None:
            raise ValueError(
                "speculation='draft' requires draft_params (the "
                "registered draft_model's weights)")

        preempt = self.on_exhaust == "preempt"
        if preempt and self.cache_pages is not None \
                and self.cache_pages < self.min_pool_pages(B):
            raise ValueError(
                f"cache_pages={self.cache_pages} is below the preemption "
                f"governor's minimum viable pool "
                f"({self.min_pool_pages(B)} pages for batch {B}): the "
                f"pool must hold one full-context episode plus the "
                f"initial observation feed of every slot, or the "
                f"zero-drop guarantee cannot hold.")

        init_fn, turn_fn = self._get_compiled(B, N, with_ref)
        with span("rollout.init", rows=B):
            carry = init_fn(params, ref_params, draft_params,
                            self._init_carry(rng, B, N, with_ref))
        base = jax.random.fold_in(rng, 1)
        brng = jax.random.fold_in(rng, 2)

        # worst case: every wave of B episodes uses its full turn budget;
        # preemption additionally stalls slots and restarts episodes, so
        # its budget assumes near-serial progress (one slot at a time)
        # plus an admission turn per episode — generous, never binding
        # for a pool above min_pool_pages
        if preempt:
            max_macro = (self.max_turns + 2) * (N + B) + 8
        else:
            max_macro = self.max_turns * math.ceil(N / B) + 2
        check_drops = self.on_exhaust == "raise" and \
            self.cache_layout == "paged"
        grow = self.pool_growth == "double" and \
            self.cache_layout == "paged"
        pool_grows = 0
        if grow:
            from repro.models.paging import pool_pages_needed
            grow_cap = (self.pool_growth_max
                        if self.pool_growth_max is not None
                        else pool_pages_needed(B, self.max_context,
                                               self.page_size))
            last_dropped = last_preempted = 0
        for m in range(max_macro):
            with span("rollout.turn", turn=m):
                carry = turn_fn(params, ref_params, draft_params, carry,
                                common.turn_rng(base, m), brng)
            # ONE host sync per turn (the returned-counter read); the
            # on_exhaust="raise" drop check and the pool-growth trigger
            # ride the same sync point
            if check_drops and int(to_host(carry.kv_dropped,
                                           "kv_dropped")) > 0:
                short = np.asarray(carry.kv_shortfall)
                bad = np.nonzero(short > 0)[0]
                detail = ", ".join(
                    f"slot {int(i)}: {int(short[i])} token(s)"
                    for i in bad[:16]) + (" …" if bad.size > 16 else "")
                extra = max(1, -(-int(short.sum()) // self.page_size))
                raise RuntimeError(
                    f"KV page pool exhausted during rollout: "
                    f"{int(carry.kv_dropped)} dropped KV write(s) by "
                    f"macro-step {m}; per-slot shortfall {{{detail}}} "
                    f"(pool {int(carry.cache.refcount.shape[0])} pages, "
                    f"peak in use {int(carry.pages_peak)}). The affected "
                    f"episodes silently lost context; grow cache_pages "
                    f"by at least {extra} page(s) (see "
                    f"pool_pages_needed[_shared]), set "
                    f"pool_growth='double', use on_exhaust='preempt' to "
                    f"trade throughput for completeness, or "
                    f"on_exhaust='count' to tolerate truncation.")
            if grow:
                # grow when the pool showed distress this turn: dropped
                # writes (count mode), a preemption (preempt mode), or
                # free pages under the admission watermark. Growth is a
                # host-side pad of zeroed free pages between macro-steps;
                # jit retraces for the new capacity (cached per shape).
                cap = int(carry.cache.refcount.shape[0])
                dropped = int(to_host(carry.kv_dropped, "kv_dropped"))
                pre = (int(to_host(carry.preempted, "preempted"))
                       if preempt else 0)
                free = int(to_host(jnp.sum(
                    (carry.cache.refcount == 0).astype(jnp.int32)),
                    "free_pages"))
                if cap < grow_cap and (
                        dropped > last_dropped or pre > last_preempted
                        or free < self.admit_watermark):
                    carry = carry._replace(cache=paging.grow_pool(
                        carry.cache, min(2 * cap, grow_cap)))
                    pool_grows += 1
                last_dropped, last_preempted = dropped, pre
            if int(to_host(carry.returned, "returned")) >= N:
                break
        if preempt and int(carry.returned) < N:
            raise RuntimeError(
                f"preemption governor failed to drain the rollout: "
                f"{int(carry.returned)}/{N} episodes returned after "
                f"{max_macro} macro-steps (pool "
                f"{int(carry.cache.refcount.shape[0])} pages, "
                f"{int(carry.preempted)} preemption(s)); the pool is "
                f"likely below min_pool_pages({B}) = "
                f"{self.min_pool_pages(B)}.")

        return self._finalize(carry, N, params_version,
                              pool_grows=pool_grows)

    def _finalize(self, carry: slots.SlotCarry, N: int,
                  params_version: int = -1, pool_grows: int = 0):
        store = carry.store
        exp = ExperienceBatch(
            tokens=store.tokens,
            gen_mask=store.gen_mask,
            loss_mask=store.gen_mask,
            logprobs=store.logprobs,
            ref_logprobs=store.ref_logprobs,
            rewards=store.rewards,
            returns=store.rewards,
            advantages=reinforce_advantages(store.rewards),
            context_len=store.context_len,
            truncated=store.truncated,
        )
        # the *actual* device layout of the harvested batch: with a bound
        # mesh these are the compiled out-shardings — the Data Dispatcher's
        # real src_shardings
        self.experience_shardings = ExperienceBatch(
            *(x.sharding for x in exp))
        paged = paging.is_paged(carry.cache)
        # the harvest's scalar and per-episode reads: one sync point
        with span("sync", what="stats"):
            stats = common.summarize(
                store.turn_lengths, store.context_len, store.n_turns,
                store.truncated, store.rewards,
                episodes_started=int(carry.launched),
                episodes_returned=int(carry.returned),
                params_version=params_version,
                pages_in_use=int(carry.pages_peak),
                page_capacity=carry.cache.refcount.shape[0] if paged else 0,
                kv_dropped_writes=int(carry.kv_dropped),
                shared_prefix_len=self.shared_len,
                preemptions=(int(carry.preempted)
                             if carry.preempted is not None else 0),
                requeue_depth=(int(carry.requeue_peak)
                               if carry.requeue_peak is not None else 0),
                pool_grows=int(pool_grows),
                spec_proposed=(int(carry.spec_proposed)
                               if carry.spec_proposed is not None else 0),
                spec_accepted=(int(carry.spec_accepted)
                               if carry.spec_accepted is not None else 0),
                spec_rounds=(int(carry.spec_rounds)
                             if carry.spec_rounds is not None else 0))
        return exp, stats
