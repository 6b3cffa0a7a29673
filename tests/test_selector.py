"""Parallelism Selector unit + property tests (EARL §2, Fig. 3).

The property-based tests require ``hypothesis`` (the optional ``[test]``
extra); they skip cleanly when it is absent so the plain tests still run.
"""
import pytest

from repro.core.parallelism_selector import (ContextBuckets,
                                             ParallelismSelector,
                                             ProfileEntry, SelectorPolicy)
from repro.core.resharding import MeshConfig

TP4 = MeshConfig("tp4", dp=2, tp=4)
TP8 = MeshConfig("tp8", dp=1, tp=8)


def synth_measure(tgs_table, oom=()):
    """tgs_table: {(name, ctx): tgs}; oom: set of (name, ctx) pairs."""

    def measure(cfg, ctx):
        return ProfileEntry(cfg, ctx, tgs_table.get((cfg.name, ctx), 1.0),
                            feasible=(cfg.name, ctx) not in oom)

    return measure


def paperlike_selector(**kw):
    """Mirrors paper Fig. 3: TP4 wins short contexts, TP8 wins >=16K, and
    TP4 OOMs at 32K (the #responses=128 cell)."""
    buckets = ContextBuckets((4096, 8192, 16384, 32768))
    table = {}
    for ctx in (4096, 8192, 16384, 32768, 65536):
        table[("tp4", ctx)] = 131.0 if ctx < 16384 else 95.0
        table[("tp8", ctx)] = 100.0
    oom = {("tp4", 32768), ("tp4", 65536)}
    return ParallelismSelector([TP4, TP8], synth_measure(table, oom),
                               buckets, **kw)


class TestContextBuckets:
    def test_bucketing(self):
        b = ContextBuckets((4096, 8192, 16384, 32768))
        assert b.bucket(0) == 0
        assert b.bucket(4095) == 0
        assert b.bucket(4096) == 1
        assert b.bucket(16384) == 3
        assert b.bucket(1_000_000) == 4
        assert b.n_buckets == 5

    def test_bucket_is_monotone_total(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=200, deadline=None)
        @given(st.integers(min_value=0, max_value=10**9))
        def prop(ctx):
            b = ContextBuckets((1024, 2048, 65536))
            i = b.bucket(ctx)
            assert 0 <= i < b.n_buckets
            assert b.bucket(ctx + 1) >= i

        prop()


class TestProfiling:
    def test_policy_prefers_tp4_short_tp8_long(self):
        sel = paperlike_selector()
        pol = sel.profile()
        assert pol.best(1000).name == "tp4"
        # buckets profile at their UPPER edge (conservative: feasibility at
        # the edge covers the whole range) -> [8192,16384) adopts 16384's
        # winner, tp8
        assert pol.best(9000).name == "tp8"
        assert pol.best(20000).name == "tp8"
        assert pol.best(40000).name == "tp8"     # tp4 OOMs there

    def test_oom_config_never_selected(self):
        sel = paperlike_selector()
        pol = sel.profile()
        for b, cfg in pol.table.items():
            ctx = pol.buckets.representative(b)
            entry = pol.grid()[(cfg.name, ctx)]
            assert entry.feasible

    def test_speedup_eq1_sign_matches_paper(self):
        """Paper Eq. 1: positive => b faster. TP4 is ~31% faster short."""
        sel = paperlike_selector()
        pol = sel.profile()
        assert pol.speedup_pct("tp8", "tp4", 4096) == pytest.approx(31.0)
        assert pol.speedup_pct("tp4", "tp8", 16384) > 0
        assert pol.speedup_pct("tp4", "tp8", 32768) == float("inf")  # OOM

    def test_all_oom_bucket_raises(self):
        sel = ParallelismSelector(
            [TP4], synth_measure({}, oom={("tp4", c) for c in
                                          (4096, 8192, 16384, 32768, 65536)}),
            ContextBuckets((4096, 8192, 16384, 32768)))
        with pytest.raises(RuntimeError):
            sel.profile()


class TestRuntimeSwitching:
    def test_switch_fires_on_bucket_crossing(self):
        sel = paperlike_selector(ema_alpha=1.0)       # no smoothing
        sel.profile()
        assert sel.current.name == "tp4"
        sel.observe(2000)
        assert sel.maybe_switch(0) is None            # still tp4 bucket
        sel.observe(20000)
        sw = sel.maybe_switch(1)
        assert sw is not None and sw[1].name == "tp8"
        assert sel.current.name == "tp8"
        assert sel.maybe_switch(2) is None            # idempotent

    def test_ema_smoothing_delays_switch(self):
        sel = paperlike_selector(ema_alpha=0.1)
        sel.profile()
        sel.observe(1000)
        for _ in range(3):
            sel.observe(20000)
        # EMA still below 16384 after 3 observations at alpha=0.1
        assert sel.ema_context < 16384
        assert sel.maybe_switch() is None

    def test_switch_log_records_transition(self):
        sel = paperlike_selector(ema_alpha=1.0)
        sel.profile()
        sel.observe(33000)
        sel.maybe_switch(step=7)
        assert sel.switch_log[0]["step"] == 7
        assert sel.switch_log[0]["from"] == "tp4"
        assert sel.switch_log[0]["to"] == "tp8"

    def test_current_config_always_feasible_for_ema(self):
        """Invariant: after any observation sequence, the active config is
        the profiled best (hence feasible) for the EMA's bucket."""
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=50, deadline=None)
        @given(st.lists(st.integers(min_value=0, max_value=10**6),
                        min_size=1, max_size=50))
        def prop(contexts):
            sel = paperlike_selector(ema_alpha=0.7)
            pol = sel.profile()
            for c in contexts:
                sel.observe(c)
                sel.maybe_switch()
                assert sel.current == pol.best(sel.ema_context)

        prop()


class TestMeshConfig:
    def test_axis_names_and_shape(self):
        assert TP4.axis_names() == ("data", "model")
        assert TP4.shape() == (2, 4)
        mp = MeshConfig("mp", dp=16, tp=16, pods=2)
        assert mp.axis_names() == ("pod", "data", "model")
        assert mp.n_devices == 512


class TestCostModelMeasureIntegration:
    """End-to-end selector profiling through the real lower+compile path
    (the production measure on CPU), on an 8-device host mesh."""

    def test_profile_table_from_compiled_cost_model(self):
        from tests.test_dispatcher import run_subprocess
        out = run_subprocess("""
        import jax, jax.numpy as jnp
        from repro.configs.base import get_smoke_config
        from repro.core.parallelism_selector import (ContextBuckets,
            ParallelismSelector, make_cost_model_measure)
        from repro.core.resharding import MeshConfig, param_shardings
        from repro.core.train_step import make_prefill_step
        from repro.launch.mesh import cache_shardings, _batch_spec
        from repro.models.registry import build_model

        cfg = get_smoke_config('qwen2-0.5b')
        model = build_model(cfg)

        def lower_fn(mesh_cfg, ctx):
            mesh = mesh_cfg.make_mesh()
            params = model.abstract()
            cache = jax.eval_shape(lambda: model.init_cache(8, ctx))
            toks = jax.ShapeDtypeStruct((8, ctx), jnp.int32)
            p_sh = param_shardings(model, mesh)
            c_sh = cache_shardings(cache, mesh, seq_len=ctx,
                                   n_kv_heads=cfg.n_kv_heads)
            t_sh = _batch_spec(mesh, (8, ctx))
            jf = jax.jit(make_prefill_step(model),
                         in_shardings=(p_sh, t_sh, c_sh),
                         donate_argnums=(2,))
            with jax.set_mesh(mesh):
                return jf.lower(params, toks, cache)

        candidates = [MeshConfig('tp2', dp=4, tp=2),
                      MeshConfig('tp4', dp=2, tp=4)]
        from repro.utils.roofline import V5E
        measure = make_cost_model_measure(lower_fn, hw=V5E)
        sel = ParallelismSelector(candidates, measure,
                                  ContextBuckets((64,)))
        pol = sel.profile()
        # a full policy table exists and every entry compiled for real
        assert set(pol.table) == {0, 1}
        assert len(pol.entries) == 4
        for e in pol.entries:
            assert e.feasible and e.tgs > 0 and e.peak_bytes > 0
        print('OK', {b: c.name for b, c in pol.table.items()})
        """)
        assert "OK" in out
