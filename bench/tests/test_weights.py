"""The seeded weights: one jit, any slice reproducible from the seed."""
import jax
import jax.numpy as jnp
import numpy as np

import weights


def numpy_draw(key, shape, fan_in, offset=0):
    """The hash in numpy (float32, before the cast): the draw's oracle."""
    n = int(np.prod(shape))
    idx = (np.arange(n, dtype=np.uint64) + offset).astype(np.uint32)
    with np.errstate(over="ignore"):
        h = idx * np.uint32(0x9E3779B9) + np.uint32(key)
        h ^= h >> 16
        h *= np.uint32(0x85EBCA6B)
        h ^= h >> 13
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> 16
    u = (h >> 8).astype(np.float32) * np.float32(2.0 ** -24)
    scale = np.float32(np.sqrt(3.0) / np.sqrt(fan_in))
    return ((np.float32(2.0) * u - np.float32(1.0)) * scale).reshape(shape)


def test_device_draw_matches_numpy_and_slices():
    key = weights.leaf_key(2 ** 31 + 17, "blocks/pos0/attn/wq")
    whole = np.asarray(jax.jit(lambda: weights.draw(
        key, (3, 8, 16), 8, jnp.float32))())
    np.testing.assert_array_equal(whole,
                                  numpy_draw(key, (3, 8, 16), 8))
    layer = np.asarray(jax.jit(lambda: weights.draw(
        key, (8, 16), 8, jnp.float32, offset=2 * 8 * 16))())
    np.testing.assert_array_equal(layer, whole[2])
    assert abs(whole.std() * np.sqrt(8) - 1.0) < 0.15


def test_make_params_gains_and_seeds():
    shapes = {"blocks": {"pos0": {"ln_attn": (2, 4), "w": (2, 4, 4)}},
              "final_norm": (4,), "embed": (16, 4)}
    a = weights.make_params(shapes, 5, jnp.bfloat16)
    b = weights.make_params(shapes, 6, jnp.bfloat16)
    assert a["embed"].dtype == jnp.bfloat16
    assert bool((a["final_norm"] == 1).all())
    assert bool((a["blocks"]["pos0"]["ln_attn"] == 1).all())
    assert not bool((a["embed"] == b["embed"]).all())
    # one program for every seed, so a new seed finds it compiled
    names, leaf_shapes, _ = weights.leaf_names(shapes)
    build = weights.builder(names, leaf_shapes, jnp.bfloat16)
    assert len({build.lower(weights.leaf_keys(s, names)).as_text()
                for s in (5, 2 ** 33 + 1)}) == 1
