"""Structural congruence of :mod:`repro.dist.sharding` spec trees.

The spec trees must mirror the ``init_params`` / ``init_cache`` pytrees
exactly — ``jax.tree.map`` across (tree, specs) is how every consumer zips
them — and every rule must degrade to replication on a mesh the dim sizes
don't divide (the 1-device CPU mesh exercises exactly that path).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.dist import sharding as shd
from repro.models import decoder
from repro.models.common import init_params, param_shapes

ARCHS = ["glm4-9b", "mixtral-8x7b", "deepseek-v2-236b", "mamba2-780m",
         "gemma3-27b"]


def cpu_mesh() -> Mesh:
    n = jax.device_count()
    return jax.make_mesh((n, 1), ("data", "model"))


def test_mesh_axes_split():
    ax = shd.MeshAxes.for_mesh(cpu_mesh())
    assert ax.batch == ("data",) and ax.model == "model"
    devs = np.array(jax.devices()).reshape(1, jax.device_count(), 1)
    ax3 = shd.MeshAxes.for_mesh(Mesh(devs, ("pod", "data", "model")))
    assert ax3.batch == ("pod", "data") and ax3.model == "model"
    # a mesh with no model axis is pure data parallelism, never megatron
    dp = Mesh(devs.reshape(1, -1), ("pod", "data"))
    ax_dp = shd.MeshAxes.for_mesh(dp)
    assert ax_dp.batch == ("pod", "data") and ax_dp.model_size(dp) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_param_shardings_congruent_with_init_params(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    mesh = cpu_mesh()
    params = init_params(cfg, jax.random.PRNGKey(0))
    shards = shd.param_shardings(cfg, mesh)
    assert jax.tree.structure(params) == jax.tree.structure(shards)
    assert all(isinstance(s, NamedSharding) for s in jax.tree.leaves(shards))
    # congruent trees zip: this is the exact device_put pattern consumers use
    placed = jax.tree.map(jax.device_put, params, shards)
    assert jax.tree.structure(placed) == jax.tree.structure(params)


@pytest.mark.parametrize("arch", ARCHS)
def test_cache_pspecs_congruent_with_init_cache(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    mesh = cpu_mesh()
    batch = 4
    tree = jax.eval_shape(lambda: decoder.init_cache(cfg, batch, 32, jnp.float32))
    specs = shd.cache_pspecs(cfg, mesh, tree, batch)
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P))
    # zip the struct tree with the spec tree -> sharded struct tree
    structs = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        tree, specs)
    assert jax.tree.structure(structs) == jax.tree.structure(tree)


def test_param_specs_follow_megatron_rules():
    """On a divisible mesh the name rules shard the intended dims."""
    cfg = dataclasses.replace(get_smoke_config("mixtral-8x7b"), dtype="float32")
    devs = np.array(jax.devices()).reshape(1, jax.device_count())
    mesh = Mesh(devs, ("data", "model"))  # model == device_count
    msize = int(mesh.shape["model"])
    specs = shd.param_pspecs(cfg, mesh)
    shapes = param_shapes(cfg, model_size=msize)

    flat_specs = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    flat_shapes = jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple))
    for (path, spec), shape in zip(flat_specs, flat_shapes):
        name = path[-1].key
        sharded_dims = [i for i, a in enumerate(spec) if a is not None]
        if msize == 1:
            assert sharded_dims == [], (name, spec)
            continue
        for i in sharded_dims:          # every sharded dim must divide
            assert shape[i] % msize == 0, (name, shape, spec)
        if name in ("wq", "wk", "wv") and shape[-1] % msize == 0:
            assert spec[len(shape) - 1] == "model", (name, spec)
        if name == "wo" and shape[-2] % msize == 0:
            assert spec[len(shape) - 2] == "model", (name, spec)
        if name in ("ln_attn", "ln_mlp", "final_norm", "router"):
            assert sharded_dims == [], (name, spec)


def test_batch_pspecs_cover_train_and_decode_inputs():
    cfg = dataclasses.replace(get_smoke_config("glm4-9b"), dtype="float32")
    mesh = cpu_mesh()
    n_data = int(mesh.shape["data"])
    train = {
        "tokens": jax.ShapeDtypeStruct((8 * n_data, 32), jnp.int32),
        "positions": jax.ShapeDtypeStruct((8 * n_data, 32), jnp.int32),
        "labels": jax.ShapeDtypeStruct((8 * n_data, 32), jnp.int32),
    }
    ps = shd.batch_pspecs(cfg, mesh, train)
    assert set(ps) == set(train)
    if n_data > 1:
        assert ps["tokens"][0] == ("data",)
    decode = {
        "tokens": jax.ShapeDtypeStruct((8 * n_data,), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }
    ps = shd.batch_pspecs(cfg, mesh, decode)
    assert ps["pos"] == P()
    # M-RoPE positions [3, B, S]: the batch dim is dim 1, never the sections
    mrope = {"positions": jax.ShapeDtypeStruct((3, 8 * n_data, 32), jnp.int32)}
    ps = shd.batch_pspecs(cfg, mesh, mrope)
    assert ps["positions"][0] is None


class _Key:
    def __init__(self, k):
        self.key = k


def _leaf_spec(names, shape, bdim, ssize, msize=1):
    path = tuple(_Key(n) for n in names)
    leaf = jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
    return shd._cache_leaf_spec(path, leaf, bdim, ("data",), "model", msize,
                                "seq", ssize)


def test_mesh_axes_seq_split():
    """A ``seq`` axis is recognized and kept out of the batch axes."""
    devs = np.array(jax.devices()).reshape(1, jax.device_count(), 1)
    mesh = Mesh(devs, ("data", "seq", "model"))
    ax = shd.MeshAxes.for_mesh(mesh)
    assert ax.batch == ("data",) and ax.seq == "seq"
    assert ax.seq_size(mesh) == jax.device_count()
    # a seq-less mesh reports seq_size 1
    m2 = cpu_mesh()
    ax2 = shd.MeshAxes.for_mesh(m2)
    assert ax2.seq is None and ax2.seq_size(m2) == 1


def test_seq_rule_shards_attention_seq_dims():
    """GQA k/v and MLA c_kv/k_pe shard their seq dim over the seq axis —
    in both unrolled (bdim 0) and group-stacked (bdim 1) layouts — while
    the mamba conv/ssm state and indivisible lengths stay whole."""
    # GQA prefix [B, S, n_kv, hd] and body [G, B, S, n_kv, hd]
    s = _leaf_spec(("attn", "k"), (4, 32, 2, 16), 0, ssize=4)
    assert s[1] == "seq"
    s = _leaf_spec(("attn", "v"), (2, 4, 32, 2, 16), 1, ssize=4)
    assert s[2] == "seq"
    # MLA latent caches [B, S, r]
    s = _leaf_spec(("attn", "c_kv"), (4, 32, 24), 0, ssize=4)
    assert s[1] == "seq"
    s = _leaf_spec(("attn", "k_pe"), (2, 4, 32, 8), 1, ssize=4)
    assert s[2] == "seq"
    # indivisible seq length: replicated, not rejected
    s = _leaf_spec(("attn", "k"), (4, 30, 2, 16), 0, ssize=4)
    assert s[1] is None
    # seq axis of size 1 (smoke mesh): no seq sharding
    s = _leaf_spec(("attn", "k"), (4, 32, 2, 16), 0, ssize=1)
    assert s[1] is None
    # mamba state has no seq dim to shard
    s = _leaf_spec(("mamba", "conv"), (4, 3, 96), 0, ssize=3)
    assert all(a in (None, "data") for a in s)
    s = _leaf_spec(("mamba", "ssm"), (4, 8, 16, 16), 0, ssize=4)
    assert s[1] is None


def test_seq_rule_composes_with_kv_head_sharding():
    """On a seq+model mesh a GQA cache shards seq AND kv heads at once."""
    s = _leaf_spec(("attn", "k"), (4, 32, 4, 16), 0, ssize=4, msize=2)
    assert s[1] == "seq" and s[2] == "model"


@pytest.mark.parametrize("arch", ["glm4-9b", "deepseek-v2-236b"])
def test_cache_pspecs_congruent_on_seq_mesh(arch):
    """cache_pspecs stays congruent with init_cache on a seq-bearing mesh
    (1-device host: the seq axis is size 1, so everything replicates but
    the tree structure and the zip must hold)."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    devs = np.array(jax.devices()).reshape(jax.device_count(), 1, 1)
    mesh = Mesh(devs, ("data", "seq", "model"))
    tree = jax.eval_shape(lambda: decoder.init_cache(cfg, 4, 32, jnp.float32))
    specs = shd.cache_pspecs(cfg, mesh, tree, 4)
    assert jax.tree.structure(tree) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P))
    structs = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        tree, specs)
    assert jax.tree.structure(structs) == jax.tree.structure(tree)


def test_indivisible_dims_fall_back_to_replication():
    """A model-axis size that divides nothing must yield pure replication."""
    cfg = dataclasses.replace(
        get_smoke_config("glm4-9b"), dtype="float32",
        d_model=60, n_heads=3, n_kv_heads=3, head_dim=20, d_ff=90,
        vocab_size=255,
    )
    shapes = param_shapes(cfg)
    flat = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    for path, shape in flat:
        spec = shd._param_spec(path, shape, "model", 7)  # 7 divides no dim
        assert all(a is None for a in spec), (path, shape, spec)
        spec2 = shd._param_spec(path, shape, "model", 2)  # 60/90 divide by 2
        for i, a in enumerate(spec2):
            if a is not None:
                assert shape[i] % 2 == 0, (path, shape, spec2)
