"""Sharded tile-fusion dispatch: partition, halo, cache keying.

Host-side structure tests (the partitioner and ``ShardedSchedule`` builder
are pure numpy) run everywhere; execution parity over a *real* multi-device
mesh runs in-process when the platform has >1 device (the CI leg forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and is additionally
pinned by a subprocess test that forces an 8-device host platform
regardless of how the suite itself was launched.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.sparse.random import banded_spd, hub_powerlaw, powerlaw_graph
from repro.core.tilefusion import api, fused_ref, sharded
from repro.core.tilefusion.cost_model import shard_comm_model
from repro.core.tilefusion.scheduler import balanced_contiguous_partition

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KNOBS = dict(p=2, cache_size=30_000.0, ct_size=32)


@pytest.fixture(autouse=True)
def fresh_cache():
    api.clear_schedule_cache()
    yield
    api.clear_schedule_cache()


def _mesh(n: int | None = None) -> Mesh:
    devs = jax.devices()
    n = len(devs) if n is None else min(n, len(devs))
    return Mesh(np.array(devs[:n]), ("shards",))


# --------------------------------------------------------------------------
# Partitioner (host-side, device-count independent)
# --------------------------------------------------------------------------
def test_balanced_partition_contiguous_and_balanced():
    costs = np.array([5.0, 1.0, 1.0, 1.0, 5.0, 1.0, 1.0, 1.0])
    bounds = balanced_contiguous_partition(costs, 4)
    assert bounds[0] == 0 and bounds[-1] == costs.size
    assert (np.diff(bounds) >= 0).all()
    sums = np.add.reduceat(costs, bounds[:-1][np.diff(bounds) > 0])
    # bottleneck can never beat the largest single tile, and the balanced
    # split must do no worse than one hot shard carrying everything
    assert sums.max() >= costs.max()
    assert sums.max() < costs.sum()


def test_balanced_partition_more_shards_than_tiles():
    bounds = balanced_contiguous_partition(np.array([3.0, 2.0]), 8)
    assert bounds[0] == 0 and bounds[-1] == 2
    assert (np.diff(bounds) >= 0).all()
    assert np.diff(bounds).sum() == 2       # every tile assigned once


def test_balanced_partition_empty():
    bounds = balanced_contiguous_partition(np.zeros(0), 4)
    assert bounds.shape == (5,) and (bounds == 0).all()


def test_mesh_partition_resolves_layouts():
    """The mesh-aware front end: one layout rule decides row shards vs
    column replicas vs depth layers, and the row bounds follow it."""
    from repro.core.tilefusion.scheduler import (balanced_mesh_partition,
                                                 resolve_mesh_layout)
    costs = np.ones(8)
    # 1d flattens every axis into row shards
    bounds, n_row, n_repl, n_depth = balanced_mesh_partition(
        costs, (4, 2), "1d")
    assert (n_row, n_repl, n_depth) == (8, 1, 1) and bounds.shape == (9,)
    # 1.5d partitions over the leading axis only
    bounds, n_row, n_repl, n_depth = balanced_mesh_partition(
        costs, (4, 2), "1.5d")
    assert (n_row, n_repl, n_depth) == (4, 2, 1) and bounds.shape == (5,)
    assert np.diff(bounds).sum() == 8
    # 2.5d peels the axes past the second into depth layers
    assert resolve_mesh_layout((2, 2, 2), "2.5d") == (2, 2, 2)
    assert resolve_mesh_layout((2, 2, 2, 2), "2.5d") == (2, 2, 4)
    # nothing to column-replicate: depth folds into the replica slot
    assert resolve_mesh_layout((4, 1, 2), "2.5d") == (4, 2, 1)
    # degenerate cases walk down the ladder; bad layouts fail loudly
    assert resolve_mesh_layout((8,), "1.5d") == (8, 1, 1)
    assert resolve_mesh_layout(8, "1d") == (8, 1, 1)
    assert resolve_mesh_layout((4, 1), "1.5d") == (4, 1, 1)
    assert resolve_mesh_layout((4, 2), "2.5d") == (4, 2, 1)
    assert resolve_mesh_layout((8,), "2.5d") == (8, 1, 1)
    with pytest.raises(ValueError):
        resolve_mesh_layout((4, 2), "3d")


def test_shard_comm_model_prices_halo_vs_replication():
    m = shard_comm_model(8, halo_rows=16, n_i=256, c_col=8, n_j=512)
    assert m["halo_bytes"] < m["replicate_bytes"]
    assert m["halo_fraction"] == 16 / 256
    # the psum output combine moves full-D partials — the dominant term
    # for small halos, and priced on n_j (D rows), not n_i
    assert m["combine_bytes"] == 512 * 8 * 4 * (7 / 8) * 8
    assert m["combine_bytes"] > m["halo_bytes"]
    # the row-remapped reduce-scatter moves each owned block once instead
    # of every row to every device: strictly cheaper on a multi-shard mesh
    assert m["combine_bytes_reduce_scatter"] < m["combine_bytes"]
    assert m["combine"] == "reduce_scatter"
    assert m["layout"] == "1d" and m["n_repl"] == 1
    # single shard: no remote bytes at all
    m1 = shard_comm_model(1, halo_rows=16, n_i=256, c_col=8)
    assert m1["halo_bytes"] == 0.0 and m1["replicate_bytes"] == 0.0
    assert m1["combine_bytes"] == 0.0
    assert m1["combine_bytes_reduce_scatter"] == 0.0


def test_shard_comm_model_combine_preference_monotone():
    """``shard_comm_model`` must prefer the reduce-scatter combine exactly
    when the psum's combine bytes dominate — and the preference gap must
    grow monotonically with the output size that drives those bytes
    (synthetic byte-count fixtures, no devices needed)."""
    gaps = []
    for n_j in (64, 256, 1024, 4096):
        m = shard_comm_model(8, halo_rows=4, n_i=4096, c_col=32, n_j=n_j,
                             combine_rows=n_j + 8)    # ≈ n_j, padded
        # combine dominates the halo by construction
        assert m["combine_bytes"] > m["halo_bytes"]
        assert m["combine"] == "reduce_scatter"
        gaps.append(m["combine_bytes"] - m["combine_bytes_reduce_scatter"])
    assert all(g2 > g1 for g1, g2 in zip(gaps, gaps[1:]))
    # degenerate ownership (one shard owns everything, maximal padding):
    # reduce-scatter buys nothing, psum keeps the simpler collective
    worst = shard_comm_model(8, halo_rows=4, n_i=64, c_col=32, n_j=64,
                             combine_rows=64 * 8)
    assert worst["combine"] == "psum"


def test_choose_mesh_layout_prefers_replication_when_halo_dominates():
    """``choose_mesh_layout`` must flip a 2-D mesh from pure-1D to the
    replicated 1.5D layout exactly when the halo bytes it saves outgrow
    the operand copies it costs — monotonically in the halo size."""
    from repro.core.tilefusion.cost_model import choose_mesh_layout

    def pick(halo_rows):
        return choose_mesh_layout((4, 2), halo_rows=halo_rows, n_i=4096,
                                  n_j=4096, c_col=64,
                                  operand_bytes=64 * 1024 * 1024)

    layouts = [pick(h)["layout"] for h in (0, 64, 4096 * 4, 4096 * 64)]
    assert layouts[0] == "1d"              # nothing to save: don't copy A/B
    assert layouts[-1] == "1.5d"           # halo dominates: replicate
    # monotone: once replication pays, more halo never flips it back
    flips = [a != b for a, b in zip(layouts, layouts[1:])]
    assert sum(flips) <= 1
    # 1-D meshes have no replication axis to choose
    assert choose_mesh_layout((8,), halo_rows=10**9, n_i=4096, n_j=4096,
                              c_col=64, operand_bytes=1.0)["layout"] == "1d"
    # candidates expose both prices for the benchmark's derived columns
    cands = pick(4096 * 64)["candidates"]
    assert cands["1.5d"]["comm_bytes"] < cands["1d"]["comm_bytes"]
    assert cands["1.5d"]["replication_cost_bytes"] > 0.0


# --------------------------------------------------------------------------
# ShardedSchedule structure (host-side)
# --------------------------------------------------------------------------
def test_sharded_schedule_structure():
    a = powerlaw_graph(256, 5, seed=3)
    entry = api.get_schedule(a, b_col=8, c_col=8, **KNOBS)
    shard = sharded.build_sharded_schedule(
        a, entry.sched, entry.dsched, 4, b_col=8, c_col=8,
        b_is_sparse=False, width_cap=entry.width_cap)
    assert shard is not None and shard.n_shards == 4
    ds = entry.dsched
    # every wf0 tile assigned to exactly one shard, in order
    assert shard.tile_bounds[0] == 0
    assert shard.tile_bounds[-1] == ds.n_tiles0
    counts = shard.shard_tile_counts()
    assert counts.sum() == ds.n_tiles0
    # halo = exactly the wf1 dependency set, owned by row-block ranges
    halo = shard.halo_rows
    np.testing.assert_array_equal(halo, ds.wf1_dep_rows())
    row_bounds = shard.tile_bounds * shard.t_pad
    pos_seen = np.sort(shard.send_pos[shard.send_pos < shard.halo_size])
    np.testing.assert_array_equal(pos_seen, np.arange(shard.halo_size))
    for s in range(4):
        sl = shard.send_local.reshape(4, -1)[s]
        sp = shard.send_pos[0, s]           # (Z, S, Hs); Z == 1 here
        real = sp < shard.halo_size
        # each contributed halo row is inside the shard's own row block
        glob = sl[real] + row_bounds[s]
        assert ((glob >= row_bounds[s]) & (glob < row_bounds[s + 1])).all()
        np.testing.assert_array_equal(glob, halo[sp[real]])


def test_sharded_schedule_requires_uniform_grid():
    a = powerlaw_graph(128, 4, seed=1)
    entry = api.get_schedule(a, b_col=8, c_col=8, uniform_split=False,
                             p=2, cache_size=2_000.0, ct_size=32)
    if not api.fused_ops._is_uniform(entry.dsched):
        assert sharded.build_sharded_schedule(
            a, entry.sched, entry.dsched, 4, b_col=8, c_col=8,
            b_is_sparse=False, width_cap=entry.width_cap) is None


# --------------------------------------------------------------------------
# Cache keying: mesh shape is part of the schedule key
# --------------------------------------------------------------------------
def test_mesh_shape_misses_schedule_cache():
    a = banded_spd(128, 4, seed=0)
    e_plain = api.get_schedule(a, b_col=8, c_col=8, **KNOBS)
    assert api.schedule_cache_stats()["misses"] == 1
    assert api.schedule_cache_stats()["mesh_entries"] == 0

    mesh1 = _mesh(1)
    # a trivial mesh keys exactly like no mesh: pure hit, not a new entry
    assert api.get_schedule(a, b_col=8, c_col=8, mesh=mesh1,
                            **KNOBS) is e_plain
    assert api.schedule_cache_stats()["misses"] == 1

    if len(jax.devices()) < 2:
        pytest.skip("needs >1 device for non-trivial mesh keys "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh_n = _mesh()
    e_mesh = api.get_schedule(a, b_col=8, c_col=8, mesh=mesh_n, **KNOBS)
    assert e_mesh is not e_plain            # same content, new mesh: miss
    assert e_mesh.shard is not None
    stats = api.schedule_cache_stats()
    assert stats["misses"] == 2 and stats["mesh_entries"] == 1
    # same mesh shape under a different Mesh object: hit
    assert api.get_schedule(a, b_col=8, c_col=8, mesh=_mesh(),
                            **KNOBS) is e_mesh
    # a different mesh *shape* over the same devices: miss again
    devs = jax.devices()
    mesh_2d = Mesh(np.array(devs).reshape(2, -1), ("x", "y"))
    e_2d = api.get_schedule(a, b_col=8, c_col=8, mesh=mesh_2d, **KNOBS)
    assert e_2d is not e_mesh
    stats = api.schedule_cache_stats()
    assert stats["misses"] == 3 and stats["mesh_entries"] == 2


def test_trivial_mesh_falls_back_single_device():
    a = banded_spd(64, 4, seed=2)
    rng = np.random.default_rng(2)
    b = rng.standard_normal((64, 8))
    c = rng.standard_normal((8, 8))
    got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                jnp.asarray(c, jnp.float32),
                                backend="sharded", mesh=_mesh(1), **KNOBS)
    np.testing.assert_allclose(np.asarray(got),
                               fused_ref.unfused_gemm_spmm(a, b, c),
                               rtol=2e-3, atol=2e-3)
    entry = api.get_schedule(a, b_col=8, c_col=8, mesh=_mesh(1), **KNOBS)
    assert entry.shard is None and entry.mesh_key is None


# --------------------------------------------------------------------------
# Multi-device execution (in-process; real on the forced-8-device CI leg)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("op_pair", ["gemm", "spmm"])
def test_sharded_parity_multi_device(op_pair):
    if len(jax.devices()) < 2:
        pytest.skip("single-device platform; the CI multi-device leg sets "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    mesh = _mesh()
    a = hub_powerlaw(96, 4, seed=0)         # hub row: spill lanes cross too
    rng = np.random.default_rng(0)
    if op_pair == "spmm":
        c = rng.standard_normal((96, 8))
        got = api.tile_fused_matmul(a, a, jnp.asarray(c, jnp.float32),
                                    backend="sharded", mesh=mesh, **KNOBS)
        want = fused_ref.unfused_spmm_spmm(a, a, c)
    else:
        b = rng.standard_normal((96, 8))
        c = rng.standard_normal((8, 8))
        got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                    jnp.asarray(c, jnp.float32),
                                    backend="sharded", mesh=mesh, **KNOBS)
        want = fused_ref.unfused_gemm_spmm(a, b, c)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3, atol=2e-3)
    entry = api.get_schedule(a, b_col=8, c_col=8,
                             b_is_sparse=(op_pair == "spmm"), mesh=mesh,
                             **KNOBS)
    assert entry.shard is not None
    assert api.select_backend(entry) == "sharded"
    assert entry.traffic_model["sharded"]["halo_rows"] \
        == entry.shard.halo_size


def test_auto_with_mesh_dispatches_sharded_even_unfusable():
    """``backend="auto"`` with a non-trivial mesh must honor the mesh even
    when the Eq-3 model would pick the unfused fallback on one device — a
    fusion-free schedule still distributes op-1 and wavefront-1 work."""
    if len(jax.devices()) < 2:
        pytest.skip("single-device platform")
    from repro.core.sparse.formats import CSR
    rng = np.random.default_rng(4)
    a = CSR.from_dense(rng.standard_normal((64, 64)))   # dense: fuses nothing
    entry = api.get_schedule(a, b_col=8, c_col=8, mesh=_mesh(), **KNOBS)
    assert entry.sched.fused_ratio < api.MIN_FUSED_RATIO
    assert entry.shard is not None
    assert api.select_backend(entry) == "sharded"
    b = rng.standard_normal((64, 8))
    c = rng.standard_normal((8, 8))
    got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                jnp.asarray(c, jnp.float32),
                                backend="auto", mesh=_mesh(), **KNOBS)
    np.testing.assert_allclose(np.asarray(got),
                               fused_ref.unfused_gemm_spmm(a, b, c),
                               rtol=2e-3, atol=2e-3)


def test_ell_rows_carry_matches_body_under_check_vma():
    """``_ell_rows`` scans a carry over the ELL slots; under
    ``shard_map(check_vma=True)`` its operands vary over the mesh axes, so
    the carry must too — a plain zeros init fails the scan's type check
    (the layout every sync sharded executor runs)."""
    from repro.core.tilefusion import fused_ops
    rng = np.random.default_rng(5)
    cols = rng.integers(0, 6, (4, 3)).astype(np.int32)
    vals = rng.standard_normal((4, 3)).astype(np.float32)
    table = rng.standard_normal((6, 2)).astype(np.float32)
    g = jax.shard_map(lambda c, v, t: fused_ops._ell_rows(c, v, t[0]),
                      mesh=_mesh(1), in_specs=(P("shards"),) * 3,
                      out_specs=P("shards"), check_vma=True)
    got = jax.jit(g)(cols, vals, table[None])
    want = np.einsum("jw,jwc->jc", vals, table[cols])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# Forced 8-device host platform (subprocess: env must be set before jax
# initializes, so this covers multi-device even on a 1-device tier-1 run)
# --------------------------------------------------------------------------
_FORCED_SCRIPT = r"""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
assert len(jax.devices()) == 8, jax.devices()
mesh = Mesh(np.array(jax.devices()), ("shards",))

# 1) sharded tile-fusion parity on the 8-way mesh, both op pairs
from repro.core.sparse.random import hub_powerlaw
from repro.core.tilefusion import api, fused_ref
a = hub_powerlaw(96, 4, seed=0)
rng = np.random.default_rng(0)
knobs = dict(p=2, cache_size=30_000.0, ct_size=32)
b = rng.standard_normal((96, 8)); cg = rng.standard_normal((8, 8))
got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                            jnp.asarray(cg, jnp.float32),
                            backend="sharded", mesh=mesh, **knobs)
np.testing.assert_allclose(np.asarray(got),
                           fused_ref.unfused_gemm_spmm(a, b, cg),
                           rtol=2e-3, atol=2e-3)
cs = rng.standard_normal((96, 8))
got = api.tile_fused_matmul(a, a, jnp.asarray(cs, jnp.float32),
                            backend="sharded", mesh=mesh, **knobs)
np.testing.assert_allclose(np.asarray(got),
                           fused_ref.unfused_spmm_spmm(a, a, cs),
                           rtol=2e-3, atol=2e-3)
entry = api.get_schedule(a, b_col=8, c_col=8, mesh=mesh, **knobs)
assert entry.shard.n_shards == 8

# 2) 2-D mesh cells: both layouts x both combines on a real 4x2 partition
mesh2d = Mesh(np.array(jax.devices()).reshape(4, 2), ("x", "y"))
want_g = fused_ref.unfused_gemm_spmm(a, b, cg)
outs = []
for layout in ("1d", "1.5d"):
    for combine in ("psum", "reduce_scatter"):
        got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                    jnp.asarray(cg, jnp.float32),
                                    backend="sharded", mesh=mesh2d,
                                    shard_layout=layout,
                                    shard_combine=combine, **knobs)
        np.testing.assert_allclose(np.asarray(got), want_g,
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"{layout}/{combine}")
        outs.append(np.asarray(got))
for o in outs[1:]:   # all four runs agree to roundoff, not just to the ref
    np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)
e15 = api.get_schedule(a, b_col=8, c_col=8, mesh=mesh2d,
                       shard_layout="1.5d", **knobs)
assert e15.shard.n_shards == 4 and e15.shard.n_repl == 2
assert e15.shard.layout == "1.5d"
stats = api.schedule_cache_stats()
assert stats["layout_15d"] >= 1 and stats["layout_1d"] >= 1, stats

# 3) 2.5D cell: a real 2x2x2 cube, depth-2 staged halo exchange, sync and
# async overlap both matching the oracle and each other exactly
import dataclasses
mesh3d = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("x", "y", "z"))
spec = api.FusionSpec(mesh=mesh3d, shard_layout="2.5d", overlap=False,
                      **knobs)
e25 = api.get_schedule(a, b_col=8, c_col=8, spec=spec)
assert e25.shard.n_shards == 2 and e25.shard.n_repl == 2
assert e25.shard.n_depth == 2 and e25.shard.layout == "2.5d"
pair = {}
for ov in (False, True):
    s = dataclasses.replace(spec, overlap=ov)
    got = api.tile_fused_matmul(a, jnp.asarray(b, jnp.float32),
                                jnp.asarray(cg, jnp.float32),
                                backend="sharded", spec=s)
    np.testing.assert_allclose(np.asarray(got), want_g, rtol=2e-3,
                               atol=2e-3, err_msg=f"2.5d/ov={ov}")
    pair[ov] = np.asarray(got)
    got_s = api.tile_fused_matmul(a, a, jnp.asarray(cs, jnp.float32),
                                  backend="sharded", spec=s)
    np.testing.assert_allclose(np.asarray(got_s),
                               fused_ref.unfused_spmm_spmm(a, a, cs),
                               rtol=2e-3, atol=2e-3,
                               err_msg=f"2.5d-spmm/ov={ov}")
np.testing.assert_allclose(pair[True], pair[False], rtol=1e-6, atol=1e-6)
e_on = api.get_schedule(a, b_col=8, c_col=8,
                        spec=dataclasses.replace(spec, overlap=True))
assert e_on.shard.overlap and e_on.shard.n_depth == 2
stats = api.schedule_cache_stats()
assert stats["layout_25d"] >= 1, stats
assert stats["spec_entries"] >= 1, stats
print("FORCED8 OK")
"""


def _run_forced_host(script: str, n_devices: int, *args: str):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(REPO_ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]).rstrip(os.pathsep))
    out = subprocess.run([sys.executable, "-c", script, *args], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_forced_8_device_host_mesh():
    assert "FORCED8 OK" in _run_forced_host(_FORCED_SCRIPT, 8)


# the chip smoke's four-chip phase, on a forced 4-device host: both op
# pairs on a 2x2 mesh, 1d and 1.5d, overlap off (check_vma=True) and on
_FORCED4_SCRIPT = r"""
import importlib.util, os, sys
import jax
assert len(jax.devices()) == 4, jax.devices()
spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(sys.argv[1], "chip_smoke.py"))
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
a = smoke.powerlaw_graph(2048, smoke.ARXIV_AVG_DEGREE, seed=0)
smoke.phase_sharded(jax.devices(), a, width=16, seed=0)
print("FORCED4 OK")
"""


def test_forced_4_device_2x2_mesh_smoke_phase():
    out = _run_forced_host(_FORCED4_SCRIPT, 4, REPO_ROOT)
    assert "FORCED4 OK" in out
    assert out.count("vs oracle and single-device") == 8, out
