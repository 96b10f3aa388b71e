"""Unified dispatch API: inspector cache, backend overrides, cost model."""
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparse.formats import CSR, HybridELL
from repro.core.sparse.random import banded_spd, hub_powerlaw, powerlaw_graph
from repro.core.tilefusion import api, fused_ref
from repro.launch.steps import make_gcn_train_step
from repro.models.gcn import GCN


@pytest.fixture(autouse=True)
def fresh_cache():
    api.clear_schedule_cache()
    yield
    api.clear_schedule_cache()


def test_cache_hit_identical_pattern_builds_once():
    a = banded_spd(256, 4, seed=0)
    e1 = api.get_schedule(a, b_col=16, c_col=16)
    stats = api.schedule_cache_stats()
    assert (stats["hits"], stats["misses"], stats["entries"]) == (0, 1, 1)
    e2 = api.get_schedule(a, b_col=16, c_col=16)
    assert e2 is e1                       # schedule built exactly once
    assert api.schedule_cache_stats()["hits"] == 1
    # same content in a fresh CSR object still hits (content-keyed)
    a_copy = CSR(a.n_rows, a.n_cols, a.indptr.copy(), a.indices.copy(),
                 a.data.copy())
    assert api.get_schedule(a_copy, b_col=16, c_col=16) is e1
    # a different cache budget is a different schedule
    api.get_schedule(a, b_col=16, c_col=16,
                     spec=api.FusionSpec(cache_size=5_000.0))
    assert api.schedule_cache_stats()["misses"] == 2


def test_cache_distinguishes_values_same_pattern():
    a = banded_spd(128, 4, seed=1)
    e1 = api.get_schedule(a, b_col=8, c_col=8)
    a_scaled = CSR(a.n_rows, a.n_cols, a.indptr, a.indices, a.data * 2.0)
    e2 = api.get_schedule(a_scaled, b_col=8, c_col=8)
    assert e2 is not e1                   # DeviceSchedule bakes in values


def test_matmul_calls_amortize_inspection():
    a = powerlaw_graph(256, 5, seed=3)
    b = jnp.ones((256, 8), jnp.float32)
    c = jnp.ones((8, 8), jnp.float32)
    for _ in range(4):
        api.tile_fused_matmul(a, b, c, backend="xla")
    stats = api.schedule_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 3


def test_backend_overrides_agree_gemm_spmm():
    a = banded_spd(512, 6, seed=1)
    rng = np.random.default_rng(1)
    b = rng.standard_normal((512, 32))
    c = rng.standard_normal((32, 16))
    want = fused_ref.unfused_gemm_spmm(a, b, c)
    bj = jnp.asarray(b, jnp.float32)
    cj = jnp.asarray(c, jnp.float32)
    for backend in api.BACKENDS:
        got = api.tile_fused_matmul(a, bj, cj, backend=backend,
                                    spec=api.FusionSpec(cache_size=50_000.0,
                                                        ct_size=128))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                                   atol=2e-3, err_msg=backend)


def test_backend_overrides_agree_spmm_spmm():
    a = powerlaw_graph(256, 5, seed=2)
    rng = np.random.default_rng(2)
    c = rng.standard_normal((256, 8))
    want = fused_ref.unfused_spmm_spmm(a, a, c)
    cj = jnp.asarray(c, jnp.float32)
    for backend in api.BACKENDS:          # pallas runs interpret off-TPU
        got = api.tile_fused_matmul(a, a, cj, backend=backend,
                                    spec=api.FusionSpec(cache_size=20_000.0,
                                                        ct_size=64))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                                   atol=2e-3, err_msg=backend)


def test_select_backend_pallas_spmm_spmm(monkeypatch):
    """Acceptance: an SpMM-SpMM schedule dispatches to the Pallas kernel on
    capable hardware (interpret mode stands in for TPU in CI), and the auto
    path executes it end to end."""
    monkeypatch.setenv("PALLAS_INTERPRET", "1")
    a = banded_spd(256, 4, seed=6)
    entry = api.get_schedule(a, b_col=16, c_col=16, b_is_sparse=True,
                             spec=api.FusionSpec(cache_size=1e8, ct_size=64))
    assert api.select_backend(entry) == "pallas"
    rng = np.random.default_rng(6)
    c = rng.standard_normal((256, 16))
    got = api.tile_fused_matmul(a, a, jnp.asarray(c, jnp.float32),
                                backend="auto",
                                spec=api.FusionSpec(cache_size=1e8,
                                                    ct_size=64))
    np.testing.assert_allclose(np.asarray(got),
                               fused_ref.unfused_spmm_spmm(a, a, c),
                               rtol=2e-3, atol=2e-3)
    # without the capability (plain CPU, no forced interpret) auto stays xla
    monkeypatch.delenv("PALLAS_INTERPRET")
    if not api._pallas_capable():
        assert api.select_backend(entry) == "xla"


def test_width_cap_and_autotune_invalidate_cache():
    """Changing the width cap or the autotune flag must miss the schedule
    cache — a capped schedule packs different device arrays, so stale reuse
    would be a silent wrong-layout bug."""
    a = powerlaw_graph(256, 5, seed=7)
    kw = dict(b_col=8, c_col=8, b_is_sparse=True)
    knobs = dict(cache_size=20_000.0)
    e_auto = api.get_schedule(a, spec=api.FusionSpec(**knobs), **kw)  # auto
    assert api.schedule_cache_stats()["misses"] == 1
    pad = api.FusionSpec(width_cap=None, **knobs)                 # pad-to-max
    e_pad = api.get_schedule(a, spec=pad, **kw)
    assert e_pad is not e_auto
    assert api.schedule_cache_stats()["misses"] == 2
    e_int = api.get_schedule(
        a, spec=api.FusionSpec(width_cap=e_auto.width_cap + 3, **knobs), **kw)
    assert e_int is not e_auto and e_int is not e_pad
    assert api.schedule_cache_stats()["misses"] == 3
    # flipping autotune on is a different entry too (its own sweep key)
    at = api.FusionSpec(autotune=True, **knobs)
    e_at = api.get_schedule(a, spec=at, **kw)
    assert e_at is not e_auto
    # every knob repeated verbatim is a pure hit: no rebuild, misses flat
    misses = api.schedule_cache_stats()["misses"]
    assert api.get_schedule(a, spec=api.FusionSpec(**knobs), **kw) is e_auto
    assert api.get_schedule(a, spec=pad, **kw) is e_pad
    assert api.get_schedule(a, spec=at, **kw) is e_at
    assert api.schedule_cache_stats()["misses"] == misses


def test_eviction_counters_monotonic(monkeypatch):
    """LRU eviction counters only ever grow, across both caches."""
    monkeypatch.setenv(api.CACHE_ENTRIES_ENV, "2")
    a = banded_spd(128, 4, seed=8)
    b = jnp.ones((128, 8), jnp.float32)
    c = jnp.ones((8, 8), jnp.float32)
    last = (0, 0)
    for ct in (16, 32, 64, 128):
        api.get_schedule(a, b_col=8, c_col=8,
                         spec=api.FusionSpec(ct_size=ct))
        api.tile_fused_matmul(banded_spd(128, 4, seed=ct), b, c,
                              backend="unfused",
                              spec=api.FusionSpec(width_cap=ct % 3 or None))
        stats = api.schedule_cache_stats()
        cur = (stats["evictions"], stats["ell_evictions"])
        assert cur >= last
        last = cur
    assert last[0] >= 2 and last[1] >= 2  # the tiny budget really evicted


def test_cost_model_falls_back_to_unfused():
    """Dense pattern + tiles far smaller than the row span: nothing fuses,
    Eq-3 predicts zero traffic saving, dispatch must pick the unfused code."""
    n = 96
    rng = np.random.default_rng(3)
    a = CSR.from_dense(rng.standard_normal((n, n)))
    entry = api.get_schedule(a, b_col=8, c_col=8,
                             spec=api.FusionSpec(ct_size=16, cache_size=1e12))
    assert entry.sched.fused_ratio < api.MIN_FUSED_RATIO
    assert api.select_backend(entry) == "unfused"
    b = rng.standard_normal((n, 8))
    c = rng.standard_normal((8, 8))
    got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                jnp.asarray(c, jnp.float32), backend="auto",
                                spec=api.FusionSpec(ct_size=16,
                                                    cache_size=1e12))
    np.testing.assert_allclose(np.asarray(got),
                               fused_ref.unfused_gemm_spmm(a, b, c),
                               rtol=2e-3, atol=2e-3)


def test_marginal_traffic_saving_falls_back_to_unfused():
    """A modeled saving inside (0, MIN_TRAFFIC_SAVING] — positive, but too
    small to cover the tile loop's off-model fixed costs — must dispatch
    unfused even though the schedule clears the fused-ratio floor (the
    hub-heavy GCN training regime where forced-fused ran ~30% slower)."""
    a = powerlaw_graph(256, 8, seed=11)
    entry = api.get_schedule(a, b_col=64, c_col=64,
                             spec=api.FusionSpec(cache_size=100_000.0))
    assert entry.sched.fused_ratio >= api.MIN_FUSED_RATIO
    assert 0.0 < entry.traffic_model["traffic_saving"] \
        <= api.MIN_TRAFFIC_SAVING
    assert api.select_backend(entry) == "unfused"


def test_auto_selects_fused_on_friendly_pattern():
    a = banded_spd(512, 4, seed=5)
    entry = api.get_schedule(a, b_col=32, c_col=32,
                             spec=api.FusionSpec(cache_size=100_000.0,
                                                 ct_size=128))
    assert api.select_backend(entry) in ("xla", "pallas")


def test_autotune_never_worse_than_default():
    """Acceptance: the Eq-3 sweep may never pick a schedule predicting more
    fast-memory traffic than the paper's ct_size=2048 heuristic."""
    mats = [banded_spd(2048, 6, seed=10), powerlaw_graph(2048, 8, seed=9),
            powerlaw_graph(1024, 4, seed=11)]
    for a in mats:
        api.clear_schedule_cache()
        e_def = api.get_schedule(
            a, b_col=32, c_col=32,
            spec=api.FusionSpec(ct_size=api.DEFAULT_CT_SIZE))
        e_at = api.get_schedule(a, b_col=32, c_col=32,
                                spec=api.FusionSpec(autotune=True))
        assert e_at.traffic_model["fused_bytes"] \
            <= e_def.traffic_model["fused_bytes"]
        assert e_at.autotuned is not None
        e_at.sched.validate()


def test_autotune_sweep_memoized():
    a = banded_spd(512, 4, seed=12)
    e1 = api.get_schedule(a, b_col=16, c_col=16,
                          spec=api.FusionSpec(autotune=True))
    sweeps = api.schedule_cache_stats()["autotune_sweeps"]
    assert sweeps == 1
    e2 = api.get_schedule(a, b_col=16, c_col=16,
                          spec=api.FusionSpec(autotune=True))
    assert e2 is e1                       # the sweep ran exactly once
    assert api.schedule_cache_stats()["autotune_sweeps"] == 1


def test_autotune_matmul_matches_reference():
    a = powerlaw_graph(512, 6, seed=13)
    rng = np.random.default_rng(13)
    b = rng.standard_normal((512, 16))
    c = rng.standard_normal((16, 8))
    want = fused_ref.unfused_gemm_spmm(a, b, c)
    for backend in ("auto", "xla"):
        got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                    jnp.asarray(c, jnp.float32),
                                    backend=backend,
                                    spec=api.FusionSpec(autotune=True))
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                                   atol=2e-3, err_msg=backend)


def test_cache_lru_eviction(monkeypatch):
    monkeypatch.setenv(api.CACHE_ENTRIES_ENV, "2")
    a = banded_spd(256, 4, seed=0)
    for ct in (32, 64, 128):              # three distinct keys, budget two
        api.get_schedule(a, b_col=8, c_col=8,
                         spec=api.FusionSpec(ct_size=ct))
    stats = api.schedule_cache_stats()
    assert stats["entries"] == 2 and stats["evictions"] == 1
    # the evicted (oldest) key re-inspects; the fresh ones still hit
    api.get_schedule(a, b_col=8, c_col=8, spec=api.FusionSpec(ct_size=128))
    assert api.schedule_cache_stats()["hits"] == 1
    api.get_schedule(a, b_col=8, c_col=8, spec=api.FusionSpec(ct_size=32))
    assert api.schedule_cache_stats()["misses"] == 4


def test_ell_cache_reported_and_bounded(monkeypatch):
    monkeypatch.setenv(api.CACHE_ENTRIES_ENV, "1")
    b = jnp.ones((128, 8), jnp.float32)
    c = jnp.ones((8, 8), jnp.float32)
    for seed in (0, 1):
        api.tile_fused_matmul(banded_spd(128, 4, seed=seed), b, c,
                              backend="unfused")
    stats = api.schedule_cache_stats()
    assert stats["ell_entries"] == 1      # bounded and visible
    assert stats["ell_evictions"] >= 1


def test_invalid_backend_rejected():
    a = banded_spd(64, 2, seed=4)
    with pytest.raises(ValueError):
        api.tile_fused_matmul(a, jnp.ones((64, 4)), jnp.ones((4, 4)),
                              backend="mkl")


# ---------------------------------------------------------------------------
# FusionSpec: spec= is the only knob surface, and the resolved spec is the key
# ---------------------------------------------------------------------------

def test_default_spec_and_spec_none_cut_the_same_cache_key():
    """``spec=None`` and ``FusionSpec()`` resolve to the SAME schedule-cache
    entry — the resolved spec really is the key — and a field change is a
    fresh entry."""
    a = banded_spd(256, 4, seed=20)
    e_none = api.get_schedule(a, b_col=8, c_col=8)
    e_spec = api.get_schedule(a, b_col=8, c_col=8, spec=api.FusionSpec())
    assert e_spec is e_none               # pure hit, no rebuild
    stats = api.schedule_cache_stats()
    assert (stats["hits"], stats["misses"]) == (1, 1)
    assert stats["spec_entries"] == 1
    e2 = api.get_schedule(a, b_col=8, c_col=8,
                          spec=api.FusionSpec(ct_size=64))
    assert e2 is not e_none
    assert api.schedule_cache_stats()["spec_entries"] == 2


def test_knob_keywords_and_non_spec_rejected():
    """Knobs ride only on ``spec=``: a knob keyword is an unknown keyword,
    and ``spec=`` takes nothing but a FusionSpec."""
    a = banded_spd(64, 2, seed=22)
    with pytest.raises(TypeError, match="unexpected keyword"):
        api.get_schedule(a, b_col=4, c_col=4, ct_size=32)
    with pytest.raises(TypeError, match="unexpected keyword"):
        api.tile_fused_matmul(a, jnp.ones((64, 4)), jnp.ones((4, 4)),
                              ct_size=32)
    with pytest.raises(TypeError, match="FusionSpec"):
        api.get_schedule(a, b_col=4, c_col=4, spec={"p": 2})


def test_spec_validates_overlap_and_n_repl():
    with pytest.raises(ValueError, match="overlap"):
        api.FusionSpec(overlap="yes")
    with pytest.raises(ValueError, match="n_repl"):
        api.FusionSpec(n_repl=0)
    # inert distribution knobs collapse on a trivial mesh: mesh=None specs
    # share one entry regardless of overlap/n_repl values
    a = banded_spd(128, 4, seed=23)
    e1 = api.get_schedule(a, b_col=8, c_col=8,
                          spec=api.FusionSpec(overlap=True, n_repl=2))
    e2 = api.get_schedule(a, b_col=8, c_col=8,
                          spec=api.FusionSpec(overlap=False, n_repl=None))
    assert e2 is e1
    assert api.schedule_cache_stats()["spec_entries"] == 1


def test_inspect_and_pack_counters_after_a_miss_then_a_hit():
    a = powerlaw_graph(512, 8, seed=3)
    b, c = jnp.ones((512, 16)), jnp.ones((16, 8))
    for _ in range(2):                    # a miss, then a hit
        api.get_schedule(a, b_col=16, c_col=8)
        api.tile_fused_matmul(a, b, c, backend="unfused")
    st = api.schedule_cache_stats()
    assert st["inspect_s"] > 0 and st["pack_s"] > 0
    assert (st["misses"], st["ell_misses"]) == (1, 1)
    assert st["hits"] >= 1 and st["ell_hits"] >= 1
    api.clear_schedule_cache()
    st = api.schedule_cache_stats()
    assert st["inspect_s"] == st["pack_s"] == 0
    assert st["ell_hits"] == st["ell_misses"] == 0


def test_spill_fold_counters_after_a_miss_a_hit_and_a_clear():
    """``spill_lanes`` / ``spill_virtual_rows`` sum the fold over ELL
    misses; a hit changes neither."""
    a = hub_powerlaw(512, seed=0)
    cap = api._resolve_width_cap(a, "auto")
    hell = HybridELL.from_csr_rows(a, np.arange(a.n_rows), cap=cap)
    fold = hell.spill_fold()
    assert 0 < fold.n_virtual < hell.n_spill
    b, c = jnp.ones((512, 16)), jnp.ones((16, 8))
    want = dict(spill_lanes=hell.n_spill, spill_virtual_rows=fold.n_virtual)
    for misses in (1, 1):                 # a miss, then a hit
        api.tile_fused_matmul(a, b, c, backend="unfused")
        st = api.schedule_cache_stats()
        assert st["ell_misses"] == misses
        assert {k: st[k] for k in want} == want
    assert api.schedule_cache_stats()["ell_hits"] == 1
    api.clear_schedule_cache()
    st = api.schedule_cache_stats()
    assert st["spill_lanes"] == st["spill_virtual_rows"] == 0


def test_op1_pack_counts_into_pack_s():
    a = banded_spd(256, 4, seed=2)
    c = jnp.ones((256, 8))
    api.tile_fused_matmul(a, a, c, backend="xla")
    st = api.schedule_cache_stats()
    assert st["pack_s"] > 0 and st["ell_misses"] == 0
    pack_s = st["pack_s"]
    api.tile_fused_matmul(a, a, c, backend="xla")   # the pack is memoized
    assert api.schedule_cache_stats()["pack_s"] == pack_s


@pytest.mark.parametrize("backend,graph,inner", [
    ("unfused", "powerlaw", {"repro.ell_body", "repro.spill", "repro.gemm"}),
    ("xla", "banded", {"repro.wf0", "repro.wf1", "repro.ell_body",
                       "repro.gemm"}),
])
def test_scopes_name_every_layer_of_the_compiled_gcn_step(backend, graph,
                                                          inner):
    """Each layer boundary of the GCN step is a ``repro.`` scope in the
    optimized HLO's ``op_name``, backward included."""
    n = 512
    adj = (powerlaw_graph(n, 8, seed=1) if graph == "powerlaw"
           else banded_spd(n, 4, seed=1))
    cfg = types.SimpleNamespace(n_nodes=n, in_dim=16, hidden_dim=32,
                                out_dim=8, n_layers=2)
    model = GCN(cfg, adj, spec=api.FusionSpec(ct_size=128))
    step = make_gcn_train_step(model, lr=0.1, backend=backend)
    params = model.init_params(jax.random.PRNGKey(0))
    text = step.lower(params, jnp.ones((n, 16)),
                      jnp.zeros((n,), jnp.int32)).compile().as_text()
    paths = {tuple(re.findall(r"repro\.[\w.]+", name))
             for name in re.findall(r'op_name="([^"]*)"', text)}
    scoped = {s for p in paths for s in p}
    assert {"repro.gcn.layer0", "repro.gcn.layer1", "repro.gcn.loss",
            "repro.sgd.update", "repro.backward"} | inner <= scoped
    assert ("repro.gcn.layer0", "repro.backward") in {p[:2] for p in paths}
