"""HybridELL: packer parity vs the loop reference + hub-row memory bound.

The memory regression test pins the format's reason to exist: on a
power-law graph with one artificially boosted hub row, the pad-to-max
packer allocates ``n_rows × max_degree`` (the failing case, asserted
explicitly), while the hybrid pack stays width-capped and within 1.5× of
the nonzero count.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from repro.core.sparse.formats import (CSR, HybridELL, TileELL,
                                       hybrid_width_cap)
from repro.core.sparse.random import banded_spd, hub_powerlaw, powerlaw_graph
from repro.core.tilefusion import (api, build_schedule, fused_ops, fused_ref,
                                   reference, to_device_schedule)
from repro.core.tilefusion.cost_model import hybrid_packed_elements


def random_csr(n, density, seed):
    rng = np.random.default_rng(seed)
    m = max(int(density * n * n), 1)
    return CSR.from_coo(n, n, rng.integers(0, n, m), rng.integers(0, n, m),
                        rng.standard_normal(m))


# --------------------------------------------------------------------------
# Satellite: hub-safe memory regression (powerlaw_graph(n=8192) + hub row)
# --------------------------------------------------------------------------
def test_hybrid_pack_memory_bounded_on_hub_powerlaw():
    a = hub_powerlaw(8192, seed=0)
    counts = np.diff(a.indptr).astype(np.int64)
    max_deg = int(counts.max())
    assert max_deg >= 8192 // 2 - 1           # the hub really dominates

    # the failing case first: pad-to-max allocates n × max_degree, blowing
    # far past the 1.5×-nnz budget the hybrid format is pinned to
    pad_elements = a.n_rows * max_deg
    assert pad_elements > 1.5 * a.nnz, \
        "pad-to-max unexpectedly within budget — hub row lost?"

    cap = hybrid_width_cap(counts)            # traffic-optimal auto cap
    hell = HybridELL.from_csr_rows(a, np.arange(a.n_rows), cap=cap)
    assert hell.width <= cap                  # packed width obeys the cap
    assert hell.packed_elements() <= 1.5 * a.nnz
    # nothing lost: body nonzero slots + spill lanes account for every entry
    assert int((hell.vals != 0).sum()) + hell.n_spill == a.nnz
    # cost-model pricing agrees with the packer's actual footprint
    spill3 = hybrid_packed_elements(counts, cap) - a.n_rows * hell.width
    assert spill3 == 3 * hell.n_spill


def test_device_schedule_wf1_capped_on_hub_powerlaw():
    """The width cap reaches the schedule: wavefront-1 ELL body width stays
    at the cap and the hub tail rides the spill lanes."""
    a = hub_powerlaw(2048, seed=1)
    cap = hybrid_width_cap(np.diff(a.indptr))
    sched = build_schedule(a, b_col=16, c_col=16, p=4, cache_size=50_000.0,
                           ct_size=128, uniform_split=True)
    ds_pad = to_device_schedule(a, sched)
    ds_cap = to_device_schedule(a, sched, width_cap=cap)
    assert ds_cap.ell_cols1.shape[2] <= cap
    assert ds_cap.spill_rows1.size > 0
    assert ds_cap.ell_cols1.size + ds_cap.spill_rows1.size \
        < ds_pad.ell_cols1.size
    # the traffic model is cap-invariant (same nonzeros, same D1 spill rows)
    tm_pad = ds_pad.hbm_traffic_model(16, 16)
    tm_cap = ds_cap.hbm_traffic_model(16, 16)
    assert tm_pad["fused_bytes"] == tm_cap["fused_bytes"]
    assert tm_pad["d1_spill_rows"] == tm_cap["d1_spill_rows"]


# --------------------------------------------------------------------------
# Packer parity: vectorized HybridELL pinned by the loop reference
# --------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(n=st.integers(8, 150), density=st.floats(0.005, 0.1),
       seed=st.integers(0, 6), cap=st.sampled_from([None, 1, 2, 5, 1000]))
def test_hybrid_packer_matches_loop_reference(n, density, seed, cap):
    a = random_csr(n, density, seed)
    rows = np.arange(a.n_rows, dtype=np.int64)
    got = HybridELL.from_csr_rows(a, rows, cap=cap)
    want = reference.hybrid_ell_from_csr_rows_ref(a, rows, cap=cap)
    assert got.width == want.width
    assert np.array_equal(got.cols, want.cols)
    assert np.array_equal(got.vals, want.vals)
    assert np.array_equal(got.spill_rows, want.spill_rows)
    assert np.array_equal(got.spill_cols, want.spill_cols)
    assert np.array_equal(got.spill_vals, want.spill_vals)
    # uncapped hybrid degenerates to the pad-to-max TileELL body
    if cap == 1000:
        tile = TileELL.from_csr_rows(a, rows)
        assert got.n_spill == 0
        assert np.array_equal(got.cols, tile.cols)


@settings(max_examples=6, deadline=None)
@given(n=st.integers(16, 120), density=st.floats(0.01, 0.08),
       seed=st.integers(0, 5))
def test_op1_ell_matches_loop_reference_uncapped(n, density, seed):
    """The shared-packer ``_op1_ell`` reproduces the retained loop
    reference bit-for-bit in the pad-to-max case (no duplicated ELL
    logic left behind)."""
    from repro.core.tilefusion import fused_ops
    a = random_csr(n, density, seed)
    sched = build_schedule(a, b_col=8, c_col=8, p=2, cache_size=5_000.0,
                           ct_size=16, b_is_sparse=True, uniform_split=True)
    ds = to_device_schedule(a, sched)
    cols, vals, spill_flat, _, _ = fused_ops._op1_ell(a, ds)
    ref_cols, ref_vals = reference.op1_ell_ref(a, ds)
    assert spill_flat.size == 0
    assert np.array_equal(cols, ref_cols)
    assert np.array_equal(vals, ref_vals)


# --------------------------------------------------------------------------
# Spill fold: virtual rows, one sorted update each, in the unfused SpMM
# --------------------------------------------------------------------------
def _signed_values(a: CSR, seed: int) -> CSR:
    """``a``'s pattern with values that are never zero, so a packed slot
    with value 0 can only be padding."""
    rng = np.random.default_rng(seed)
    vals = rng.uniform(0.5, 1.5, a.nnz) * rng.choice([-1.0, 1.0], a.nnz)
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices, vals)


def _edge_rows() -> CSR:
    """Empty rows, a row whose tail is exactly one full virtual row at cap
    4 (degree 8), one with a one-lane tail (degree 5), one at the cap."""
    n, rows, cols = 64, [], []
    for r, deg in ((3, 8), (5, 5), (9, 4), (40, 1)):
        rows += [r] * deg
        cols += list(range(2 * r % 50, 2 * r % 50 + deg))
    return CSR.from_coo(n, n, np.asarray(rows), np.asarray(cols),
                        np.ones(len(rows)))


#: name -> (graph, cap)
FOLD_CASES = {
    "powerlaw-cap1": (lambda: powerlaw_graph(512, 8, seed=3), 1),
    "powerlaw-cap2": (lambda: powerlaw_graph(512, 8, seed=3), 2),
    "powerlaw-cap7": (lambda: powerlaw_graph(512, 8, seed=3), 7),
    # the hub's tail (about 256 lanes) is wider than 4**3 at cap 4
    "hub-wider-than-cap-cubed": (lambda: hub_powerlaw(512, seed=0), 4),
    "empty-and-one-virtual-row": (_edge_rows, 4),
    "no-spill": (lambda: banded_spd(256, 4, seed=1), 16),
}


def _fold_case(name: str) -> tuple:
    make, cap = FOLD_CASES[name]
    return _signed_values(make(), seed=len(name)), cap


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_spill_fold_reproduces_every_lane_once(case):
    """The virtual rows hold exactly the spill lanes' (row, col, val)
    triples, each once; padding is col 0 / val 0; a row with ``k`` lanes
    owns ``ceil(k / width)`` virtual rows, and the owners ascend."""
    a, cap = _fold_case(case)
    hell = HybridELL.from_csr_rows(a, np.arange(a.n_rows), cap=cap)
    fold = hell.spill_fold()
    w = hell.width
    assert fold.vcols.shape == fold.vvals.shape == (fold.n_virtual, w)
    lanes = np.bincount(hell.spill_rows, minlength=a.n_rows)
    assert np.array_equal(np.bincount(fold.rows, minlength=a.n_rows),
                          -(-lanes // w))
    assert (np.diff(fold.rows) >= 0).all()
    live = fold.vvals != 0
    assert not fold.vcols[~live].any()
    # live slots come first in every virtual row
    assert live[:, 0].all()
    assert (live[:, :-1] >= live[:, 1:]).all()
    got = zip(np.broadcast_to(fold.rows[:, None], live.shape)[live].tolist(),
              fold.vcols[live].tolist(), fold.vvals[live].tolist())
    want = zip(hell.spill_rows.tolist(), hell.spill_cols.tolist(),
               hell.spill_vals.tolist())
    assert sorted(got) == sorted(want)
    if case == "empty-and-one-virtual-row":        # row 3: tail of exactly w
        assert fold.rows.tolist() == [3, 5] and live[0].all()


@pytest.mark.parametrize("c_col", [40, 256])
@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_spmm_hybrid_fold_matches_float64_oracle(case, c_col):
    """``spmm_hybrid`` (body + spill fold) against a float64 product, to
    float32 rounding of each row's sum of absolute terms."""
    a, cap = _fold_case(case)
    x = np.random.default_rng(c_col).standard_normal((a.n_cols, c_col))
    got = fused_ops.spmm_hybrid(fused_ops.csr_to_ell(a, cap),
                                jnp.asarray(x, jnp.float32))
    dense = a.to_dense()
    scale = np.abs(dense) @ np.abs(x)
    np.testing.assert_array_less(np.abs(np.asarray(got, np.float64)
                                        - dense @ x),
                                 64 * np.finfo(np.float32).eps * scale
                                 + 1e-30)


def test_unfused_executors_and_grad_fold_on_a_hub_graph():
    """Both unfused executors and ``jax.grad`` through the unfused backend
    (the custom_vjp's transposed products) match ``fused_ref`` on a graph
    whose hub spills into many virtual rows."""
    a = _signed_values(hub_powerlaw(512, seed=2), seed=5)
    rng = np.random.default_rng(0)
    b = rng.standard_normal((512, 24))
    c = rng.standard_normal((24, 40))
    c_sp = rng.standard_normal((512, 40))
    ell = fused_ops.csr_to_ell(a, 4)
    assert ell.vrows.shape[0] > 4 ** 2
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(fused_ops.unfused_gemm_spmm(ell, f32(b), f32(c))),
        fused_ref.unfused_gemm_spmm(a, b, c), rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(
        np.asarray(fused_ops.unfused_spmm_spmm(ell, ell, f32(c_sp))),
        fused_ref.unfused_spmm_spmm(a, a, c_sp), rtol=2e-4, atol=2e-2)

    api.clear_schedule_cache()
    w = rng.standard_normal((512, 40))
    got = jax.grad(lambda b_, c_: jnp.sum(f32(w) * api.tile_fused_matmul(
        a, b_, c_, backend="unfused")), argnums=(0, 1))(f32(b), f32(c))
    ad = a.to_dense()
    at_w = ad.T @ w                   # Ḋ = w: dB = Aᵀ·w·Cᵀ, dC = Bᵀ·Aᵀ·w
    np.testing.assert_allclose(np.asarray(got[0]), at_w @ c.T,
                               rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(np.asarray(got[1]), b.T @ at_w,
                               rtol=2e-4, atol=2e-2)
    assert api.schedule_cache_stats()["spill_lanes"] > 0
    api.clear_schedule_cache()


def _instructions(text: str) -> list:
    """(opcode, result type, op_name) of every computing instruction (the
    program's inputs carry their argument names)."""
    out = []
    for line in text.splitlines():
        m = re.search(r"= (\S+) ([\w-]+)\(.*op_name=\"([^\"]*)\"", line)
        if m and m.group(2) != "parameter":
            out.append(m.groups())
    return out


def test_compiled_spill_fold_updates_once_per_virtual_row():
    """On a hub graph the compiled ``spmm_hybrid`` holds one scatter, which
    adds the virtual rows (not the lanes) into the body's rows, and every
    instruction the fold adds to the body-only program is in the
    ``repro.spill`` scope; with no spill lanes that scope is absent."""
    a = hub_powerlaw(512, seed=0)
    x = jnp.ones((512, 40), jnp.float32)
    ell = fused_ops.csr_to_ell(a, 4)
    n_virtual = ell.vrows.shape[0]
    n_lanes = HybridELL.from_csr_rows(a, np.arange(a.n_rows), cap=4).n_spill
    body_only = ell._replace(vcols=ell.vcols[:0], vvals=ell.vvals[:0],
                             vrows=ell.vrows[:0])
    text = fused_ops.spmm_hybrid.lower(ell, x).compile().as_text()
    base = fused_ops.spmm_hybrid.lower(body_only, x).compile().as_text()
    ops = _instructions(text)
    scatters = [op for op in ops if op[1] == "scatter"]
    assert len(scatters) == 1 and "repro.spill" in scatters[0][2]
    # no operation is sized by the lane count
    assert f"[{n_lanes}," not in text
    added = {name for *_, name in ops} - {name for *_, name in
                                          _instructions(base)}
    assert added and all("repro.spill" in name for name in added)
    assert "repro.spill" not in base
    assert any(f"[{n_virtual},40]" in ty for ty, op, name in ops
               if "repro.spill" in name)

    grid = banded_spd(256, 4, seed=1)
    grid_ell = fused_ops.csr_to_ell(grid, 16)
    assert grid_ell.vrows.shape[0] == 0
    text = fused_ops.spmm_hybrid.lower(
        grid_ell, jnp.ones((256, 40), jnp.float32)).compile().as_text()
    assert "repro.spill" not in text
