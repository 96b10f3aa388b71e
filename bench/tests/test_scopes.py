"""The program's scopes in the optimized HLO, and the reduction that puts
device time and idle gaps down to them."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from bench import scopes, tracing
from repro.trace import scope

TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_tpu.xplane.pb")


def scoped_fn():
    """A custom_vjp product scanned over slots inside a layer scope, then
    an unscoped sum: the shapes of the program's GCN step in small."""
    @jax.custom_vjp
    def prod(x):
        return jnp.sin(x)

    def fwd(x):
        return prod(x), x

    def bwd(x, g):
        with scope("backward"):
            return (g * jnp.cos(x),)
    prod.defvjp(fwd, bwd)

    def f(x, ws):
        with scope("layer"):
            def body(c, w):
                with scope("ell_body"):
                    return c + prod(w * c), None
            acc, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(acc * acc)
    return jax.jit(jax.grad(f))


def compiled_text():
    x, ws = jnp.ones(8), jnp.ones((3, 8))
    return scoped_fn().lower(x, ws).compile().as_text()


def test_scope_path_sees_through_transform_wrappers():
    assert scopes.scope_path(
        "jit(step)/transpose(jvp(repro.gcn.layer0))/repro.backward/mul") == (
        "repro.gcn.layer0", "repro.backward")
    assert scopes.scope_path("jit(f)/jvp(repro.layer)/while/body/"
                             "closed_call/repro.ell_body/add") == (
        "repro.layer", "repro.ell_body")
    assert scopes.scope_path("jit(scatter-add)/scatter-add") == ()


def test_types_lose_layouts_and_tuples_read_to_their_close():
    assert scopes.shape_of("f32[8,128]{1,0:T(8,128)S(1)}") == "f32[8,128]"
    typ, rest = scopes.split_type(
        "(f32[2]{0}, /*index=1*/u32[]{:S(2)}) copy-start(f32[2]{0} %x)")
    assert scopes.shape_of(typ) == "(f32[2],u32[])"
    assert rest.startswith(" copy-start")
    assert scopes.parse_event(
        "%fusion.50 = f32[169343,256]{1,0:T(8,128)} fusion(%a), "
        "kind=kLoop") == ("fusion.50", "f32[169343,256]")


def test_compiled_hlo_maps_instructions_to_scopes():
    name, instrs = scopes.parse_hlo(compiled_text())
    assert name.startswith("jit_")
    paths = {p for _, p in instrs.values()}
    # the forward scan's body, the backward scan, and the custom_vjp bwd
    # inside it, each keep the enclosing layer scope
    assert ("repro.layer", "repro.ell_body") in paths
    assert ("repro.layer", "repro.ell_body", "repro.backward") in paths
    whiles = [p for i, (_, p) in instrs.items() if i.startswith("while")]
    assert whiles and all(p[:1] == ("repro.layer",) for p in whiles)
    # parameters and the unscoped loss carry no repro. scope
    assert () in paths
    assert all(p == () for i, (_, p) in instrs.items()
               if i.startswith(("Arg_", "param")))


def test_read_dump_finds_the_modules_xla_wrote(tmp_path):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = {scopes.dump_flags(str(tmp_path))!r}
        import jax, jax.numpy as jnp
        from repro.trace import scope
        @jax.jit
        def f(x):
            with scope("layer"):
                return jnp.sin(x) * 2
        f(jnp.ones(4)).block_until_ready()
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=120)
    hlo = scopes.read_dump(str(tmp_path))
    assert "jit_f" in hlo
    assert any(p == ("repro.layer",) for m in hlo["jit_f"]
               for _, p in m.values())


def op(name, module, instr, shape, s, e):
    return (name, module, instr, shape, s, e)


HLO = {"jit_step": [{"while.1": ("f32[8]", ("repro.layer",)),
                     "mul.2": ("f32[8]", ("repro.layer", "repro.spill")),
                     "copy.3": ("f32[8]", ()),
                     "fusion.4": ("f32[8]", ("repro.loss",))}]}


def test_scope_time_is_a_union_clipped_to_the_window():
    m = "jit_step(123)"
    ev = {"devices": {TPU0: [
        op("w", m, "while.1", "f32[8]", 0, 10),     # a while ...
        op("b", m, "mul.2", "f32[8]", 2, 5),        # ... and its body op
        op("b", m, "mul.2", "f32[8]", 4, 6),        # overlapping: once
        op("c", m, "copy.3", "f32[8]", 12, 14),     # no scope
        op("l", m, "fusion.4", "f32[8]", 18, 25),   # past the window's end
    ]}, "spans": [("bench.window", 0, 20), ("repro.dispatch", 14, 18.5),
                  ("repro.dispatch", 30, 31)]}
    r = scopes.reduce(ev, HLO)
    assert r["busy_s"] == pytest.approx(14e-9)      # 0-10, 12-14, 18-20
    assert r["scope_s"]["repro.layer"] == pytest.approx(10e-9)
    assert r["scope_s"]["repro.spill"] == pytest.approx(4e-9)
    assert r["scope_s"]["repro.loss"] == pytest.approx(2e-9)
    assert r["unscoped_s"] == pytest.approx(2e-9)
    assert r["scopes"][0] == ["repro.layer", pytest.approx(10e-9)]
    assert ["repro.layer/repro.spill", pytest.approx(4e-9)] in r["scopes"]
    assert all(p for p, _ in r["scopes"])           # unscoped is apart
    # 14-18 lies in a program span, 10-12 only in the window
    assert r["idle_gaps"] == [["repro.dispatch", pytest.approx(4e-9)],
                              ["bench.window", pytest.approx(2e-9)]]
    # only the span inside the window counts
    assert r["spans"] == {"repro.dispatch": {"count": 1,
                                             "s": pytest.approx(4.5e-9)}}
    n = scopes.layer_numbers(r, {"inspect_s": 1.5, "pack_s": 0.25})
    assert n["spill_share"] == pytest.approx(100 * 4 / 14)
    assert n["unscoped_share"] == pytest.approx(100 * 2 / 14)
    assert n["dispatch_ms"] == pytest.approx(4.5e-6)
    assert n["inspect_s"] == pytest.approx(1.75)
    assert n["backward_share"] is None and n["ell_share"] is None


def test_scope_time_is_averaged_over_chips():
    m = "jit_step(1)"
    ev = {"devices": {TPU0: [op("w", m, "while.1", "f32[8]", 0, 10)],
                      TPU1: [op("w", m, "while.1", "f32[8]", 0, 30)]},
          "spans": [("bench.window", 0, 40)]}
    r = scopes.reduce(ev, HLO)
    assert r["scope_s"]["repro.layer"] == pytest.approx(20e-9)
    assert r["unscoped_s"] == pytest.approx(0.0)


def test_operations_the_dump_cannot_name_are_unscoped():
    ev = {"devices": {TPU0: [
        op("w", "jit_step(1)", "while.1", "f32[16]", 0, 4),   # other shape
        op("x", "jit_other(2)", "while.1", "f32[8]", 4, 6),   # no module
        op("y", None, "mul.2", "f32[8]", 6, 8),               # outside any
    ]}, "spans": [("bench.window", 0, 8)]}
    r = scopes.reduce(ev, HLO)
    assert r["scope_s"] == {} and r["scopes"] == []
    assert r["unscoped_s"] == pytest.approx(r["busy_s"])


def test_the_module_that_ran_is_picked_among_same_named_ones():
    hlo = {"jit_step": [{"fusion.1": ("f32[4]", ("repro.a",))},
                        {"fusion.1": ("f32[8]", ("repro.b",))}]}
    ev = {"devices": {TPU0: [op("f", "jit_step(9)", "fusion.1", "f32[8]",
                                0, 2)]},
          "spans": [("bench.window", 0, 2)]}
    assert scopes.reduce(ev, hlo)["scope_s"] == {
        "repro.b": pytest.approx(2e-9)}


def test_recorded_tpu_trace_reduces_as_before_and_unscoped():
    """The v5e fixture has no program scopes: the reduction keeps
    ``tracing``'s busy and window seconds and puts all of it unscoped."""
    ev = scopes.load(RECORDED)
    before = tracing.reduce(tracing.load(RECORDED))
    r = scopes.reduce(ev, {})
    assert r["busy_s"] == before["busy_s"]
    assert r["window_s"] == before["window_s"]
    assert r["device_ops"] == before["device_ops"]
    assert r["unscoped_s"] == pytest.approx(r["busy_s"])
    assert r["scopes"] == [] and r["scope_s"] == {}
    # every operation ran inside a module event of the trace
    modules = {module for _, module, *_ in ev["devices"][TPU0]}
    assert modules == {"jit__lambda(12587813017320962265)",
                       "jit__lambda(3943643956871236492)"}
