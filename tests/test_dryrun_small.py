"""Dry-run machinery tests on the single host device (full meshes are
exercised by launch/dryrun.py with the 512-device flag; here we verify the
cell construction, sharding specs and the HLO analyzer)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.launch.cells import arch_shape_cells, input_specs
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import model_flops_for, roofline_terms
from repro.launch.shardings import param_specs, zero_specs
from repro.utils import hlo as H


def test_cells_enumeration():
    cells = arch_shape_cells()
    assert len(cells) == 44  # 11 archs x 4 shapes
    skips = [c for c in cells if c[2]]
    assert len(skips) == 9   # long_500k for full-attention archs
    for arch, shape, why in skips:
        assert shape == "long_500k"
        assert get_config(arch).family not in ("ssm", "hybrid")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_no_allocation(arch):
    cfg = get_config(arch)
    for shape in SHAPES.values():
        specs = input_specs(cfg, shape)
        for v in jax.tree.leaves(specs):
            assert isinstance(v, jax.ShapeDtypeStruct)


def test_param_specs_shard_big_leaves():
    cfg = get_config("qwen2-72b")
    from repro.models import get_model
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    specs = param_specs(cfg, shapes, mesh)
    flat_sh, _ = jax.tree_util.tree_flatten_with_path(shapes)
    flat_sp = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_sh) == len(flat_sp)
    # embedding sharded on vocab
    assert "model" in tuple(specs["embed"])
    assert "model" in tuple(specs["lm_head"])
    # attention projections sharded
    assert "model" in tuple(specs["layers"]["attn"]["wq"])
    assert "model" in tuple(specs["layers"]["mlp"]["w_down"])
    # norms replicated
    assert tuple(specs["final_norm"]) == (None,)


def test_zero_specs_add_data_axis():
    cfg = get_config("qwen2-1.5b")
    from repro.models import get_model
    shapes = jax.eval_shape(get_model(cfg).init, jax.random.PRNGKey(0))
    mesh = make_host_mesh()
    pspecs = param_specs(cfg, shapes, mesh)
    zspecs = zero_specs(pspecs, shapes, mesh)
    # with dp size 1, nothing changes
    assert jax.tree.all(jax.tree.map(
        lambda a, b: tuple(a) == tuple(b), pspecs, zspecs,
        is_leaf=lambda x: isinstance(x, P)))


def test_model_flops_sane():
    f = model_flops_for("qwen2-72b", "train_4k")
    # 6 * 72.7e9 * (4096*256) tokens
    assert 4e17 < f < 5e17
    f2 = model_flops_for("qwen2-moe-a2.7b", "train_4k")
    # active params only
    assert f2 < model_flops_for("qwen2-72b", "train_4k") / 10


def test_roofline_terms_math():
    rec = {"flops_per_device": 197e12, "bytes_per_device": 819e9,
           "collective_bytes": {"total": 50e9},
           "score_bytes_per_device": 0.0}
    t = roofline_terms(rec, model_flops=197e12 * 256, chips=256)
    assert abs(t["compute_s"] - 1.0) < 1e-9
    assert abs(t["memory_s"] - 1.0) < 1e-9
    assert abs(t["collective_s"] - 1.0) < 1e-9
    assert abs(t["useful_ratio"] - 1.0) < 1e-9


def test_hlo_analyzer_scan_trip_counts():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, ws)
        return c
    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, 128, 128), jnp.float32)
    c = jax.jit(f).lower(x, ws).compile()
    a = H.analyze(c.as_text(), while_trip_count=1)  # parsed from HLO cond
    assert abs(a["flops"] - 6 * 2 * 64 * 128 * 128) < 1e5


def test_hlo_analyzer_nested_scans():
    def f(x, ws):
        def outer(c, w):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        c, _ = jax.lax.scan(outer, x, ws)
        return c
    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, 64, 64), jnp.float32)
    c = jax.jit(f).lower(x, ws).compile()
    a = H.analyze(c.as_text())
    assert abs(a["flops"] - 4 * 3 * 2 * 32 * 64 * 64) < 1e5


def test_hlo_analyzer_collectives():
    mesh = make_host_mesh()
    from jax.sharding import NamedSharding

    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, None)))
    # single-device: no collectives expected — analyzer returns zeros
    c = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    a = H.analyze(c.as_text())
    assert a["collective_bytes"]["total"] == 0.0


def test_reduced_smoke_cell_lowers_on_host_mesh():
    """End-to-end mini dry-run: reduced config on the 1x1 mesh."""
    from repro.configs import reduced_config
    from repro.models import get_model
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_loop import make_train_step
    cfg = reduced_config(get_config("gemma-2b"))
    model = get_model(cfg)
    mesh = make_host_mesh()
    params_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_abs = jax.eval_shape(
        lambda p: init_opt_state(p, OptimizerConfig()), params_abs)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    step = make_train_step(model, OptimizerConfig())
    with mesh:
        lowered = jax.jit(step).lower(params_abs, opt_abs, batch_abs)
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    assert float(ca.get("flops", 0)) > 0
