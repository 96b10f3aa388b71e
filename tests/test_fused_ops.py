"""Fused executors vs oracle — the paper's correctness contract."""
import jax.numpy as jnp
import numpy as np
from _prop import given, settings, st

from repro.core.sparse.formats import CSR
from repro.core.sparse.random import powerlaw_graph
from repro.core.tilefusion import (build_schedule, fused_ops, fused_ref,
                                   to_device_schedule)


def random_csr(n, density, seed):
    rng = np.random.default_rng(seed)
    m = max(int(density * n * n), 1)
    return CSR.from_coo(n, n, rng.integers(0, n, m), rng.integers(0, n, m),
                        rng.standard_normal(m))


@settings(max_examples=12, deadline=None)
@given(n=st.integers(16, 160), seed=st.integers(0, 6),
       bcol=st.sampled_from([4, 16]), ccol=st.sampled_from([4, 8]),
       uniform=st.booleans())
def test_fused_gemm_spmm_matches_oracle(n, seed, bcol, ccol, uniform):
    a = random_csr(n, 0.05, seed)
    sched = build_schedule(a, b_col=bcol, c_col=ccol, p=2,
                           cache_size=4_000.0, ct_size=32,
                           uniform_split=uniform)
    ds = to_device_schedule(a, sched)
    rng = np.random.default_rng(seed)
    b = rng.standard_normal((n, bcol))
    c = rng.standard_normal((bcol, ccol))
    want = fused_ref.unfused_gemm_spmm(a, b, c)
    # numpy schedule walker (checks the no-sync invariant internally)
    got_np = fused_ref.run_gemm_spmm(a, b, c, sched, check=True)
    np.testing.assert_allclose(got_np, want, rtol=1e-9, atol=1e-9)
    # jax executor
    got = fused_ops.fused_gemm_spmm(ds, jnp.asarray(b, jnp.float32),
                                    jnp.asarray(c, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-3)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(16, 120), seed=st.integers(0, 5),
       ccol=st.sampled_from([4, 8]), uniform=st.booleans())
def test_fused_spmm_spmm_matches_oracle(n, seed, ccol, uniform):
    a = random_csr(n, 0.05, seed)
    sched = build_schedule(a, b_col=ccol, c_col=ccol, p=2,
                           cache_size=4_000.0, ct_size=32, b_is_sparse=True,
                           uniform_split=uniform)
    ds = to_device_schedule(a, sched)
    rng = np.random.default_rng(seed + 100)
    c = rng.standard_normal((n, ccol))
    want = fused_ref.unfused_spmm_spmm(a, a, c)
    got_np = fused_ref.run_spmm_spmm(a, a, c, sched, check=True)
    np.testing.assert_allclose(got_np, want, rtol=1e-9, atol=1e-9)
    got = fused_ops.fused_spmm_spmm(ds, a, jnp.asarray(c, jnp.float32))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-3)


def test_baselines_match_oracle():
    a = powerlaw_graph(256, 6, seed=1)
    rng = np.random.default_rng(1)
    b = rng.standard_normal((256, 16))
    c = rng.standard_normal((16, 8))
    want = fused_ref.unfused_gemm_spmm(a, b, c)
    bj, cj = jnp.asarray(b, jnp.float32), jnp.asarray(c, jnp.float32)
    ell = fused_ops.csr_to_ell(a)
    np.testing.assert_allclose(
        np.asarray(fused_ops.unfused_gemm_spmm(ell, bj, cj)), want,
        rtol=2e-3, atol=2e-3)
    parts = fused_ops.overlapped_tiles(a, 4)
    np.testing.assert_allclose(
        np.asarray(fused_ops.overlapped_gemm_spmm(a, parts, bj, cj)), want,
        rtol=2e-3, atol=2e-3)
    waves = fused_ops.atomic_tiles(a, 4)
    np.testing.assert_allclose(
        np.asarray(fused_ops.atomic_gemm_spmm(a, waves, bj, cj)), want,
        rtol=2e-3, atol=2e-3)


def _device_schedule_with_tiles(i_starts, i_lens, t_pad):
    from repro.core.tilefusion.schedule import DeviceSchedule
    n_t = len(i_starts)
    return DeviceSchedule(
        n_i=int(sum(i_lens)), n_j=4, t_pad=t_pad,
        i_starts=np.asarray(i_starts, np.int32),
        i_lens=np.asarray(i_lens, np.int32),
        j_rows0=np.full((n_t, 1), 4, np.int32),
        ell_cols0=np.zeros((n_t, 1, 1), np.int32),
        ell_vals0=np.zeros((n_t, 1, 1), np.float32),
        j_rows1=np.full((0, 1), 4, np.int32),
        ell_cols1=np.zeros((0, 1, 1), np.int32),
        ell_vals1=np.zeros((0, 1, 1), np.float32),
    )


def test_is_uniform_empty_schedule():
    """Zero wavefront-0 tiles is trivially uniform (the old and/if-else
    precedence only got this right by accident)."""
    assert fused_ops._is_uniform(_device_schedule_with_tiles([], [], 8))


def test_is_uniform_grid_and_non_grid():
    assert fused_ops._is_uniform(
        _device_schedule_with_tiles([0, 8, 16], [8, 8, 5], 8))
    # non-contiguous starts -> not uniform
    assert not fused_ops._is_uniform(
        _device_schedule_with_tiles([0, 16], [8, 8], 8))
    # short tile in the middle -> not uniform
    assert not fused_ops._is_uniform(
        _device_schedule_with_tiles([0, 8, 16], [8, 4, 8], 8))


def test_overlapped_redundancy_positive():
    """CA-style tiling replicates work (the paper's critique)."""
    a = powerlaw_graph(512, 8, seed=2)
    red = fused_ops.overlapped_redundancy(a, 8)
    assert red > 1.0  # deps replicated across partitions
