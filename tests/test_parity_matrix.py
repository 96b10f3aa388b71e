"""Cross-backend parity property suite — every (op-pair × backend × pattern).

One parametrized harness pins the whole dispatch matrix: both op pairs
({GeMM-SpMM, SpMM-SpMM}) × every backend ({pallas (interpret on CPU), xla,
unfused, sharded, reference}) × the pattern zoo ({banded, blockdiag,
powerlaw, empty-rows, single-hub-row, 1×1}), all asserted allclose against
the ``fused_ref`` numpy oracle.  The hybrid width cap is left at its "auto"
default so every cell — including the single-hub-row power-law case —
exercises the capped body + spill-lane path.

The ``sharded`` cell runs ``tile_fused_matmul(..., mesh=...)`` over every
device this platform has: on a plain 1-device run that exercises the
trivial-mesh fallback, and on the CI multi-device leg
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the real 8-way
shard_map partition of every cell.

Runs under ``tests/_prop.py``: real hypothesis when installed, a seeded
deterministic parametrize sweep otherwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st
from jax.sharding import Mesh

from repro.core.sparse.formats import CSR
from repro.core.sparse.random import (banded_spd, block_diag_noise,
                                      hub_powerlaw, powerlaw_graph)
from repro.core.tilefusion import api, fused_ref

KNOBS = dict(p=2, cache_size=30_000.0, ct_size=32)

#: Sharded cells: the flattened 1-D mesh always; 2-D factorizations (row
#: shards × column replicas under ``shard_layout="auto"``) join on the
#: forced-8-device CI leg so every op-pair × pattern also runs the 4×2
#: and 2×4 partitions.
SHARDED_CELLS = {"sharded": None}
if len(jax.devices()) >= 8:
    SHARDED_CELLS["sharded-4x2"] = (4, 2)
    SHARDED_CELLS["sharded-2x4"] = (2, 4)

#: Explicit override backends, the serving tier (bucketed + incremental
#: schedule reuse), and the numpy schedule-walking oracle.
BACKENDS = ("pallas", "xla", "unfused", *SHARDED_CELLS, "serving",
            "reference")


def _host_mesh(shape=None) -> Mesh:
    """All of this platform's devices on one 1-D axis (8 on the CI
    multi-device leg, 1 on a plain run — the trivial-mesh fallback), or a
    2-D mesh of the given shape over a device subset."""
    if shape is None:
        return Mesh(np.array(jax.devices()), ("shards",))
    n = int(np.prod(shape))
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), ("x", "y"))


def _empty_rows(n: int, seed: int) -> CSR:
    """Banded pattern with every other row (and its columns) zeroed — the
    vacuously-fusable empty-row edge the extents sentinel must handle."""
    dense = banded_spd(n, 3, seed=seed).to_dense()
    dense[::2, :] = 0.0
    return CSR.from_dense(dense)


PATTERNS = {
    "banded": lambda n, seed: banded_spd(n, 4, seed=seed),
    "blockdiag": lambda n, seed: block_diag_noise(n, block=32, seed=seed),
    "powerlaw": lambda n, seed: powerlaw_graph(n, 5, seed=seed),
    "empty-rows": _empty_rows,
    # one artificially boosted max-degree row: pad-to-max width explodes
    # and the hybrid spill lanes must carry the tail
    "single-hub-row": lambda n, seed: hub_powerlaw(n, 4, seed=seed),
    "1x1": lambda n, seed: CSR.from_dense(np.ones((1, 1))),
}


def _run_cell(a: CSR, op_pair: str, backend: str, c_col: int,
              rng) -> tuple:
    """Execute one matrix cell; returns (got, want) numpy arrays."""
    n = a.n_rows
    c_sp = rng.standard_normal((n, c_col))
    b = rng.standard_normal((n, 8))
    c_ge = rng.standard_normal((8, c_col))
    if backend == "reference":
        entry = api.get_schedule(a, b_col=c_col if op_pair == "spmm" else 8,
                                 c_col=c_col,
                                 b_is_sparse=(op_pair == "spmm"),
                                 spec=api.FusionSpec(**KNOBS))
        if op_pair == "spmm":
            got = fused_ref.run_spmm_spmm(a, a, c_sp, entry.sched, check=True)
            want = fused_ref.unfused_spmm_spmm(a, a, c_sp)
        else:
            got = fused_ref.run_gemm_spmm(a, b, c_ge, entry.sched, check=True)
            want = fused_ref.unfused_gemm_spmm(a, b, c_ge)
        return np.asarray(got), want
    if backend == "serving":
        # the tier cell runs twice: once cold (bucketed rebuild) and once
        # on a perturbed pattern (the incremental-patch path when the
        # dirty fraction allows), each against its own oracle
        from repro.core.sparse.random import perturb_rows
        from repro.core.tilefusion.serving import ServingTier
        a2 = perturb_rows(a, rng.choice(a.n_rows, 1, replace=False),
                          seed=int(rng.integers(1 << 31)))
        if op_pair == "spmm":
            tier = ServingTier(b_col=c_col, c_col=c_col, b_is_sparse=True,
                               **KNOBS)
            pairs = [(tier.matmul(a, a, c_sp),
                      fused_ref.unfused_spmm_spmm(a, a, c_sp)),
                     (tier.matmul(a2, a2, c_sp),
                      fused_ref.unfused_spmm_spmm(a2, a2, c_sp))]
        else:
            tier = ServingTier(b_col=8, c_col=c_col, **KNOBS)
            pairs = [(tier.matmul(a, b, c_ge),
                      fused_ref.unfused_gemm_spmm(a, b, c_ge)),
                     (tier.matmul(a2, b, c_ge),
                      fused_ref.unfused_gemm_spmm(a2, b, c_ge))]
        return (np.concatenate([np.asarray(g) for g, _ in pairs]),
                np.concatenate([w for _, w in pairs]))
    kwargs = dict(KNOBS)
    if backend in SHARDED_CELLS:
        kwargs["mesh"] = _host_mesh(SHARDED_CELLS[backend])
        backend = "sharded"
    if op_pair == "spmm":
        got = api.tile_fused_matmul(a, a, jnp.asarray(c_sp, jnp.float32),
                                    backend=backend,
                                    spec=api.FusionSpec(**kwargs))
        want = fused_ref.unfused_spmm_spmm(a, a, c_sp)
    else:
        got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                    jnp.asarray(c_ge, jnp.float32),
                                    backend=backend,
                                    spec=api.FusionSpec(**kwargs))
        want = fused_ref.unfused_gemm_spmm(a, b, c_ge)
    return np.asarray(got), want


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("op_pair", ["gemm", "spmm"])
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 4), c_col=st.sampled_from([4, 8]))
def test_parity_cell(op_pair, pattern, seed, c_col):
    a = PATTERNS[pattern](64, seed)
    rng = np.random.default_rng(1000 * seed + c_col)
    for backend in BACKENDS:
        got, want = _run_cell(a, op_pair, backend, c_col, rng)
        np.testing.assert_allclose(
            got, want, rtol=2e-3, atol=2e-3,
            err_msg=f"{op_pair}/{backend}/{pattern}/seed{seed}")


@pytest.mark.parametrize("op_pair", ["gemm", "spmm"])
def test_reduce_scatter_combine_matches_psum(op_pair):
    """The row-remapped reduce-scatter combine is numerically equivalent to
    the full-D psum it replaces: same per-row arithmetic, only the combine
    collective differs — so the two runs must agree to float roundoff, on
    every mesh this platform expresses (trivial fallback included)."""
    a = hub_powerlaw(96, 4, seed=1)        # hub row: spill lanes cross too
    rng = np.random.default_rng(1)
    outs = {}
    for combine in ("psum", "reduce_scatter"):
        mesh = _host_mesh()
        spec = api.FusionSpec(mesh=mesh, shard_combine=combine, **KNOBS)
        if op_pair == "spmm":
            c = rng.standard_normal((96, 8))
            got = api.tile_fused_matmul(a, a, jnp.asarray(c, jnp.float32),
                                        backend="sharded", spec=spec)
            want = fused_ref.unfused_spmm_spmm(a, a, c)
        else:
            b = rng.standard_normal((96, 8))
            c = rng.standard_normal((8, 8))
            got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                        jnp.asarray(c, jnp.float32),
                                        backend="sharded", spec=spec)
            want = fused_ref.unfused_gemm_spmm(a, b, c)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                                   atol=2e-3, err_msg=combine)
        outs[combine] = np.asarray(got)
        if len(jax.devices()) > 1:
            entry = api.get_schedule(
                a, b_col=8, c_col=8, b_is_sparse=(op_pair == "spmm"),
                spec=spec)
            assert entry.shard is not None
            assert entry.shard.combine == combine
        rng = np.random.default_rng(1)     # same operands for both modes
    np.testing.assert_allclose(outs["reduce_scatter"], outs["psum"],
                               rtol=1e-5, atol=1e-5)


def test_hub_row_spills_under_auto_cap():
    """The single-hub-row cell really exercises the spill lanes: the auto
    width cap is far below the hub degree, so the schedule (or the op-1
    pack) must carry spill entries — and parity above proves they land."""
    a = hub_powerlaw(96, 4, seed=0)
    api.clear_schedule_cache()
    entry = api.get_schedule(a, b_col=8, c_col=8, b_is_sparse=True,
                             spec=api.FusionSpec(**KNOBS))
    counts = np.diff(a.indptr)
    assert entry.width_cap is not None
    assert entry.width_cap < int(counts.max())
    ds = entry.dsched
    from repro.core.tilefusion import fused_ops
    _, _, spill_flat, _, _ = fused_ops._op1_ell(a, ds,
                                                width_cap=ds.width_cap)
    assert ds.spill_rows1.size + spill_flat.size > 0


@pytest.mark.parametrize("reorder", ["auto", "rcm", "similarity"])
@pytest.mark.parametrize("op_pair", ["gemm", "spmm"])
def test_reorder_under_dispatch_parity(op_pair, reorder):
    """``spec.reorder`` is invisible to callers: the dispatch permutes the
    row-indexed operands in and the output back out, so every backend's
    result on a reordered schedule must equal the unpermuted oracle —
    including "auto" entries where the Eq-3 floor declined and no
    permutation is active."""
    import dataclasses as _dc
    spec = api.FusionSpec(**KNOBS, reorder=reorder)
    for pattern, seed in (("powerlaw", 0), ("blockdiag", 2), ("banded", 1)):
        a = PATTERNS[pattern](64, seed)
        rng = np.random.default_rng(10 * seed + 1)
        c_sp = rng.standard_normal((64, 6))
        b = rng.standard_normal((64, 8))
        c_ge = rng.standard_normal((8, 6))
        for backend in ("xla", "unfused", "auto", "pallas"):
            if op_pair == "spmm":
                got = api.tile_fused_matmul(
                    a, a, jnp.asarray(c_sp, jnp.float32), backend=backend,
                    spec=spec)
                want = fused_ref.unfused_spmm_spmm(a, a, c_sp)
            else:
                got = api.tile_fused_matmul(
                    a, jnp.asarray(b, jnp.float32),
                    jnp.asarray(c_ge, jnp.float32), backend=backend,
                    spec=spec)
                want = fused_ref.unfused_gemm_spmm(a, b, c_ge)
            np.testing.assert_allclose(
                np.asarray(got), want, rtol=2e-3, atol=2e-3,
                err_msg=f"{op_pair}/{backend}/{pattern}/reorder={reorder}")
        # pin that forced modes really ran permuted (not a no-op pass)
        if reorder != "auto":
            entry = api.get_schedule(
                a, b_col=6 if op_pair == "spmm" else 8, c_col=6,
                b_is_sparse=(op_pair == "spmm"),
                spec=_dc.replace(spec, dtype_bytes=4))
            assert entry.reorder == reorder
            assert entry.reorder_perm is not None
