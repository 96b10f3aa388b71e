"""Data pipeline, optimizer, checkpoint, GCN, roofline parser."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.data import DataConfig, SyntheticStream
from repro.optim import OptConfig, adamw
from repro.roofline import collective_bytes


# ------------------------------------------------------------------ data ---
def test_data_deterministic_and_resumable():
    cfg = DataConfig(vocab_size=1000, seq_len=16, global_batch=8, seed=3)
    s1 = SyntheticStream(cfg)
    s2 = SyntheticStream(cfg)
    b1 = s1.batch_at(7)
    b2 = s2.batch_at(7)          # fresh object, same step -> same batch
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(s1.batch_at(8)["tokens"], b1["tokens"])


def test_data_sharding_consistent():
    """Concatenated shards == the single-host global batch (elastic safety)."""
    cfg = DataConfig(vocab_size=1000, seq_len=8, global_batch=8, seed=1)
    whole = SyntheticStream(cfg).batch_at(5)["tokens"]
    parts = [SyntheticStream(cfg, shard_index=i, shard_count=4).batch_at(5)
             ["tokens"] for i in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), whole)


# ----------------------------------------------------------------- optim ---
def test_adamw_converges_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    opt_cfg = OptConfig(lr=0.1, warmup_steps=1, total_steps=200,
                        weight_decay=0.0)
    state = adamw.init(params)
    grad = jax.grad(lambda p: jnp.sum(p["w"] ** 2))
    for _ in range(150):
        params, state, _ = adamw.update(opt_cfg, grad(params), state, params)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clipping():
    params = {"w": jnp.ones(4)}
    state = adamw.init(params)
    huge = {"w": jnp.full(4, 1e9)}
    _, _, m = adamw.update(OptConfig(clip_norm=1.0), huge, state, params)
    assert float(m["grad_norm"]) > 1e8  # reported pre-clip


# ------------------------------------------------------------- checkpoint ---
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": [jnp.ones((4,), jnp.bfloat16),
                  jnp.asarray(3, jnp.int32)]}
    ckpt.save(str(tmp_path), 5, tree, extra={"step": 5})
    assert ckpt.latest_step(str(tmp_path)) == 5
    got, extra = ckpt.restore(str(tmp_path), 5, tree)
    assert extra["step"] == 5
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_prune(tmp_path):
    tree = {"a": jnp.zeros(2)}
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, tree)
    ckpt.prune(str(tmp_path), keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    assert len([d for d in os.listdir(tmp_path) if d.startswith("step_")]) == 2


@pytest.mark.slow
def test_preemption_restart_exact_resume(tmp_path):
    """Kill at step 6, restart, final state equals uninterrupted run."""
    env = dict(os.environ, PYTHONPATH="src")
    base = [sys.executable, "-m", "repro.launch.train", "--arch",
            "qwen2.5-3b", "--reduced", "--steps", "8", "--batch", "2",
            "--seq", "16", "--ckpt-every", "3", "--log-every", "100"]
    d1 = str(tmp_path / "interrupted")
    r = subprocess.run(base + ["--ckpt-dir", d1, "--simulate-preemption",
                               "6"], env=env, capture_output=True, text=True,
                       cwd="/root/repo")
    assert r.returncode == 17, r.stdout + r.stderr
    r = subprocess.run(base + ["--ckpt-dir", d1], env=env,
                       capture_output=True, text=True, cwd="/root/repo")
    assert r.returncode == 0, r.stdout + r.stderr
    d2 = str(tmp_path / "clean")
    r2 = subprocess.run(base + ["--ckpt-dir", d2], env=env,
                        capture_output=True, text=True, cwd="/root/repo")
    assert r2.returncode == 0
    got, _ = ckpt.restore(d1, 8, None) if False else (None, None)
    # compare final checkpoints leaf by leaf
    import glob
    import numpy as np
    f1 = sorted(glob.glob(os.path.join(d1, "step_00000008", "*.npy")))
    f2 = sorted(glob.glob(os.path.join(d2, "step_00000008", "*.npy")))
    assert f1 and len(f1) == len(f2)
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.load(a), np.load(b))


# --------------------------------------------------------------- roofline ---
def test_collective_bytes_parser_synthetic():
    hlo = """
  %ag = bf16[4,128]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[16,16]{1,0} all-reduce(%y), to_apply=%add
  %t = (f32[8]{0}, f32[8]{0}) all-to-all(%a, %b)
  %cp = u8[100]{0} collective-permute(%z)
  %not_a_collective = f32[9]{0} add(%p, %q)
"""
    got = collective_bytes(hlo)
    assert got["bytes"]["all-gather"] == 4 * 128 * 2
    assert got["bytes"]["all-reduce"] == 16 * 16 * 4
    assert got["bytes"]["all-to-all"] == 8 * 4 * 2
    assert got["bytes"]["collective-permute"] == 100
    assert got["counts"]["all-reduce"] == 1


def test_collective_bytes_parser_real_module():
    """Parse an actually-lowered sharded module."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = len(jax.devices())
    if n < 1:
        pytest.skip("no devices")
    mesh = jax.make_mesh((1,), ("d",))
    sh = NamedSharding(mesh, P())
    f = jax.jit(lambda x: x @ x.T, in_shardings=(sh,))
    txt = f.lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile().as_text()
    out = collective_bytes(txt)   # no collectives on 1 device
    assert out["total_bytes"] >= 0


# -------------------------------------------------------------------- gcn ---
def test_gcn_fused_equals_unfused_and_learns():
    from repro.configs.gcn import REDUCED
    from repro.core.sparse.random import powerlaw_graph
    from repro.models.gcn import GCN
    model = GCN(REDUCED, powerlaw_graph(REDUCED.n_nodes, 6, seed=0))
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (REDUCED.n_nodes, REDUCED.in_dim)), jnp.float32)
    y_f = model.forward(params, x, backend="xla")
    y_u = model.forward(params, x, backend="unfused")
    np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                               rtol=2e-3, atol=2e-3)
    labels = jnp.asarray(rng.integers(0, REDUCED.out_dim, REDUCED.n_nodes))
    lg = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, x, labels, backend="xla")))
    p = params
    l0, _ = lg(p)
    for _ in range(20):
        loss, g = lg(p)
        p = jax.tree.map(lambda a, b: a - 0.5 * b, p, g)
    assert float(loss) < float(l0)


def test_gcn_pallas_kernel_path():
    """The paper's app through the paper's Pallas kernel (interpret mode)."""
    from repro.configs.gcn import REDUCED
    from repro.core.sparse.random import powerlaw_graph
    from repro.models.gcn import GCN
    model = GCN(REDUCED, powerlaw_graph(REDUCED.n_nodes, 6, seed=3))
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(
        (REDUCED.n_nodes, REDUCED.in_dim)), jnp.float32)
    y_pallas = model.forward(params, x, backend="pallas")
    y_unfused = model.forward(params, x, backend="unfused")
    np.testing.assert_allclose(np.asarray(y_pallas), np.asarray(y_unfused),
                               rtol=2e-3, atol=2e-3)


def test_opt_shardings_zero1():
    """ZeRO-1: moments gain a data-axis dim the param sharding left free."""
    from repro.launch.partitioning import opt_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = {"w": jax.ShapeDtypeStruct((4, 8), jnp.float32)}
    p_sh = {"w": NamedSharding(mesh, P(None, "model"))}
    o_sh = opt_shardings(p_sh, params, mesh)
    # dim 1 taken by model; dim 0 (size 4, divisible by data=1) gets data
    assert o_sh["w"].spec == P("data", "model")


def test_moe_shard_map_trivial_mesh_matches_local():
    """shard_map MoE on a 1x1 mesh == the local path (numerics identical)."""
    from repro.configs import get_config
    from repro.models.layers import moe_apply, moe_init
    from repro.models.sharding import ShardingRules
    cfg = get_config("granite-moe-3b-a800m", reduced=True)
    p = moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y_local = moe_apply(p, cfg, x, rules=None)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    rules = ShardingRules(batch_axes=("data",), mesh=mesh)
    with mesh:
        y_sm = moe_apply(p, cfg, x, rules=rules)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_local),
                               rtol=2e-5, atol=2e-5)


def test_model_flops_kinds():
    from repro.configs import get_config, get_shape
    from repro.roofline import model_flops
    cfg = get_config("stablelm-1.6b")
    tr = model_flops(cfg, get_shape("train_4k"))
    pf = model_flops(cfg, get_shape("prefill_32k"))
    de = model_flops(cfg, get_shape("decode_32k"))
    assert tr == 6 / 2 * pf  # same token count, 6Nd vs 2Nd
    assert de < pf           # one token per sequence
