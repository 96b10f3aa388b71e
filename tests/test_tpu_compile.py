"""Ahead-of-time compiles of the tile-fusion Pallas kernels for TPU v5e.

JAX's TPU compiler compiles for a chip that is described, not attached:
each test lowers one kernel at the shapes ``chip_smoke.py`` runs, taken
from real ``get_schedule`` entries, and compiles it for one v5e chip.  What
Mosaic refuses (primitives it cannot lower, more scoped VMEM than a kernel
may use) fails here instead of on the chip.  Nothing runs, so this says
nothing about results or times.

The topology is described only inside a fixture: one process at a time may
load the TPU library, so describing it at import would break collection
under several test workers.  Keep every test that needs it in this file.
"""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tilefusion import api, fused_ops
from repro.kernels import spmm, tile_fused_gemm_spmm, tile_fused_spmm_spmm
from repro.kernels.config import VMEM_BUDGET

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    old_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"     # else libtpu logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    if old_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def aot(one_chip):
    """``compile(fn, *shapes)`` for one v5e chip, with the persistent
    compilation cache off: a program compiled for a described chip is
    written to it but cannot be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def arxiv_entries(smoke):
    """Phase 1: the GCN layer entries at ogbn-arxiv shape."""
    model = smoke.GCN(smoke.gcn_config(smoke.ARXIV_NODES,
                                       smoke.ARXIV_AVG_DEGREE),
                      smoke.arxiv_graph(0))
    return model.entries


@pytest.fixture(scope="module")
def banded_entries(smoke):
    """Phase 2: the banded GCN layer entries at the smoke's row count."""
    n = smoke.pallas_gcn_rows(0)
    model = smoke.GCN(smoke.gcn_config(n, smoke.BANDWIDTH),
                      smoke.banded_spd(n, smoke.BANDWIDTH, 0))
    return model.entries


F32, I32 = jnp.float32, jnp.int32


def _gemm_wf0_shapes(e):
    ds = e.dsched
    n_t, t = ds.n_tiles0, ds.t_pad
    ell = ds.ell_cols0.shape
    return ((ell, I32), (ell, F32), ((n_t * t, e.b_col), F32),
            ((e.b_col, e.c_col), F32))


def _wf1_shapes(e):
    t1, j1, w1 = e.dsched.ell_cols1.shape
    return (((t1 * j1, w1), I32), ((t1 * j1, w1), F32),
            ((e.dsched.n_i, e.c_col), F32))


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("phase", ["arxiv_entries", "banded_entries"])
@pytest.mark.parametrize("layer", [0, 1])
def test_gemm_spmm_wf0_compiles(aot, request, phase, layer):
    e = request.getfixturevalue(phase)[layer]
    assert api.pallas_vmem_bytes(e)["wf0"] <= VMEM_BUDGET
    _assert_kernel(aot(functools.partial(
        tile_fused_gemm_spmm.tile_fused_gemm_spmm_wf0, t=e.dsched.t_pad,
        interpret=False), *_gemm_wf0_shapes(e)))


@pytest.mark.parametrize("layer", [0, 1])
def test_wf1_spmm_compiles_where_admitted(aot, banded_entries, layer):
    e = banded_entries[layer]
    assert api.pallas_fits_vmem(e)
    _assert_kernel(aot(functools.partial(spmm.spmm_ell, interpret=False),
                       *_wf1_shapes(e)))


def test_spmm_spmm_wf0_compiles(aot, smoke):
    c_col = smoke.CONFIG.hidden_dim
    n = smoke.pallas_spmm_spmm_rows(0, c_col)
    a = smoke.banded_spd(n, smoke.BANDWIDTH, 0)
    e = api.get_schedule(a, b_col=c_col, c_col=c_col, b_is_sparse=True)
    assert api.pallas_fits_vmem(e)
    ds = e.dsched
    o_cols = fused_ops._op1_ell(a, ds, width_cap=ds.width_cap)[0]
    ell = ds.ell_cols0.shape
    _assert_kernel(aot(functools.partial(
        tile_fused_spmm_spmm.tile_fused_spmm_spmm_wf0, t=ds.t_pad,
        interpret=False),
        (o_cols.shape, I32), (o_cols.shape, F32),
        ((ds.n_tiles0 * ds.t_pad, c_col), F32), (ell, I32), (ell, F32),
        ((a.n_cols, c_col), F32)))


@pytest.mark.parametrize("layer", [0, 1])
def test_vmem_check_rejects_arxiv_wf1(arxiv_entries, layer):
    """At ogbn-arxiv shape the wavefront-1 kernel stages all of D1 plus an
    n-wide one-hot, which Mosaic cannot allocate (it runs out of VMEM
    after about two minutes of compiling): the check must reject it, so
    auto never hands it over."""
    vmem = api.pallas_vmem_bytes(arxiv_entries[layer])
    assert vmem["wf1"] > VMEM_BUDGET >= vmem["wf0"]
    assert not api.pallas_fits_vmem(arxiv_entries[layer])
