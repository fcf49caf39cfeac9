"""Sharding rules: spec validity on the production mesh shapes and
single-device vs sharded numerical equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.configs import get_config, list_archs
from repro.launch import steps as steps_lib
from repro.models import model as M
from repro.models.config import ModelConfig, get_shape


@pytest.mark.parametrize("arch", list_archs())
def test_param_specs_divisible_on_production_mesh(arch):
    """Every sharded dim of every full-config param must divide the
    production mesh axis (data=16, model=16)."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    specs = sharding.param_specs(params)
    axis_size = {"data": 16, "model": 16}

    def check(path, leaf, spec):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            assert leaf.shape[dim] % axis_size[ax] == 0, (
                arch, [str(p) for p in path], leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        check, params, specs,
        is_leaf=lambda x: isinstance(x, P))


def test_data_axes_fallbacks():
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    assert sharding.data_axes(mesh, 8) == ("data",)
    assert sharding.data_axes(mesh, 3) is None


def _tiny_cfg():
    return ModelConfig(name="shard-t", family="dense", n_layers=2,
                       d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
                       d_ff=64, vocab_size=64, dtype="float32",
                       tp_divisor=2).validate()


def test_sharded_loss_matches_single_device():
    """The same train_loss on a 2x2 mesh must equal the unsharded value."""
    cfg = _tiny_cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    batch = {"tokens": toks, "labels": toks}
    loss_ref, _ = M.train_loss(params, cfg, batch)

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    specs = sharding.param_specs(params)
    p_sh = jax.device_put(params, sharding.to_named(mesh, specs))
    dp = sharding.data_axes(mesh, 4)
    b_sh = jax.device_put(batch, sharding.to_named(
        mesh, sharding.batch_specs(batch, dp)))
    sharder = sharding.make_sharder(mesh, dp)
    with mesh:
        loss_sh, _ = jax.jit(
            lambda p, b: M.train_loss(p, cfg, b, sharder))(p_sh, b_sh)
    np.testing.assert_allclose(float(loss_ref), float(loss_sh),
                               rtol=2e-5)


def test_sharded_grads_match_single_device():
    cfg = _tiny_cfg()
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, 64)
    batch = {"tokens": toks, "labels": toks}

    grad_fn = jax.grad(lambda p, b: M.train_loss(p, cfg, b)[0])
    g_ref = grad_fn(params, batch)

    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    specs = sharding.param_specs(params)
    p_sh = jax.device_put(params, sharding.to_named(mesh, specs))
    dp = sharding.data_axes(mesh, 4)
    b_sh = jax.device_put(batch, sharding.to_named(
        mesh, sharding.batch_specs(batch, dp)))
    with mesh:
        g_sh = jax.jit(grad_fn)(p_sh, b_sh)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sh)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def test_build_step_structs_no_allocation():
    """build_step must work from ShapeDtypeStructs only (dry-run contract)."""
    cfg = get_config("tinyllama-1.1b", smoke=True)
    shape = get_shape("train_4k")
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    fn, args, in_sh, out_sh = steps_lib.build_step(cfg, shape, mesh)
    flat = jax.tree.leaves(args)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in flat)


def test_cache_specs_keys():
    cfg = get_config("hymba-1.5b", smoke=True)
    cache = jax.eval_shape(lambda: M.init_decode_cache(cfg, 4, 64))
    specs = sharding.cache_specs(cache, "data")
    assert specs["kv"]["k"] == P(None, "data", None, "model", None)
    assert specs["ssm"]["h"] == P(None, "data", "model", None, None)
    assert specs["kv"]["positions"] == P("data", None)
    assert specs["kv"]["index"] == P("data")
