"""Seeded weights: a counter-based hash of (seed, leaf name, element index).

The benchmark stands in for a checkpoint.  Every weight is a pure function
of the run's ``--seed``, the leaf's name and the element's flat index, so

* the whole tree is drawn on the device in one jitted call, in the type it
  is served in, with nothing staged on the host;
* the plain reference (``bench/reference``) regenerates any slice of any
  leaf, layer by layer, from the seed alone and gets the same values,
  without reading anything the program made.

A matrix leaf of shape ``[..., fan_in, fan_out]`` is uniform on
``[-sqrt(3) s, sqrt(3) s]`` with ``s = 1 / sqrt(fan_in)`` (the same standard
deviation as a scaled normal init); a leaf whose name starts with ``ln_`` or
is ``final_norm`` is all ones (RMSNorm gains).
"""
from __future__ import annotations

import hashlib
import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp


def leaf_key(seed: int, name: str) -> int:
    """A 32-bit key for one leaf, from a seed of any size and its name."""
    digest = hashlib.sha256(f"{int(seed)}/{name}".encode()).digest()
    return int.from_bytes(digest[:4], "little")


def is_gain(name: str) -> bool:
    last = name.rsplit("/", 1)[-1]
    return last.startswith("ln_") or last == "final_norm"


def _fmix32(h):
    # murmur3's finalizer: a bijection on uint32 with full avalanche
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def draw(key: int, shape: Sequence[int], fan_in: int, dtype, offset=0):
    """Elements ``offset ..`` of a leaf drawn with ``key``, in ``shape``.

    ``offset`` is the flat index of the first element, so a slice of a
    leaf (one layer of a stacked leaf) draws exactly the values the whole
    leaf holds there.  Traceable: call it inside ``jax.jit``.
    """
    shape = tuple(int(s) for s in shape)
    idx = jnp.asarray(offset, jnp.uint32)
    stride = 1
    for dim in range(len(shape) - 1, -1, -1):
        iota = jax.lax.broadcasted_iota(jnp.uint32, shape, dim)
        idx = idx + iota * jnp.uint32(stride)
        stride *= shape[dim]
    if stride >= 2 ** 32:
        raise ValueError(f"leaf of {stride} elements overflows the index")
    h = _fmix32(idx * jnp.uint32(0x9E3779B9) + jnp.asarray(key, jnp.uint32))
    u = (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    scale = math.sqrt(3.0) / math.sqrt(fan_in)
    return ((2.0 * u - 1.0) * scale).astype(dtype)


def leaf_names(shapes) -> Tuple[list, list, object]:
    """``(names, shapes, treedef)`` of a shape tree; names are paths such
    as ``blocks/pos0/attn/wq``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    names = ["/".join(str(getattr(k, "key", k)) for k in path)
             for path, _ in flat]
    return names, [s for _, s in flat], treedef


def leaf_keys(seed: int, names) -> jax.Array:
    return jnp.asarray([leaf_key(seed, n) for n in names], jnp.uint32)


def builder(names, leaf_shapes, dtype):
    """The one jitted draw of a tree's leaves from their keys.  The keys
    are its argument, not constants in it, so one compiled program serves
    every seed."""

    @jax.jit
    def build(keys):
        out = []
        for i, (name, shape) in enumerate(zip(names, leaf_shapes)):
            if is_gain(name):
                out.append(jnp.ones(shape, dtype))
            else:
                out.append(draw(keys[i], shape, shape[-2], dtype))
        return out

    return build


def make_params(shapes, seed: int, dtype) -> Dict:
    """The whole tree for a shape tree, drawn on the device in one jit."""
    names, leaf_shapes, treedef = leaf_names(shapes)
    build = builder(names, leaf_shapes, dtype)
    return jax.tree_util.tree_unflatten(treedef,
                                        build(leaf_keys(seed, names)))
