"""Per-arch smoke tests (reduced configs): train step + decode consistency."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.configs.shapes import SHAPES, input_specs, skip_reason
from repro.models import decoder
from repro.models.common import init_params, layer_plan, param_shapes

CTX = decoder.RunCtx(mesh=None, use_kernel="ref")


def _batch(cfg, key, b=2, s=32):
    batch = {}
    if cfg.family in ("vlm", "audio"):
        batch["embeds"] = jax.random.normal(key, (b, s, cfg.d_model), jnp.float32)
    else:
        batch["tokens"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        batch["positions"] = jnp.broadcast_to(pos[None], (3, b, s))
    batch["labels"] = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)
    logits = decoder.forward(cfg, CTX, params, {k: v for k, v in batch.items()
                                                if k != "labels"})
    assert logits.shape == (2, 32, cfg.vocab_size)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: decoder.loss_fn(cfg, CTX, p, batch), has_aux=True)(params)
    assert np.isfinite(float(loss))
    for g in jax.tree.leaves(grads):
        assert np.all(np.isfinite(np.asarray(g, np.float32)))


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_config(a).causal])
def test_decode_matches_forward(arch):
    """prefill(S) + decode(S) logits == forward(S+1) last-position logits."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    key = jax.random.PRNGKey(1)
    params = init_params(cfg, key)
    b, s = 2, 33
    batch = _batch(cfg, key, b=b, s=s)
    batch.pop("labels")
    full = decoder.forward(cfg, CTX, params, batch)

    def cut(x, n):
        if x.ndim == 3 and x.shape[0] == 3:     # mrope positions
            return x[:, :, :n]
        return x[:, :n]

    prompt = {k: cut(v, s - 1) for k, v in batch.items()}
    logits0, caches = decoder.prefill(cfg, CTX, params, prompt)
    np.testing.assert_allclose(
        np.asarray(logits0), np.asarray(full[:, s - 2]), rtol=2e-3, atol=2e-3)

    # move the prompt cache into a longer ring and take one decode step
    ring = decoder.init_cache(cfg, b, s + 4, jnp.float32)

    def merge(dst, src):
        if src is None:
            return dst
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                            (0,) * dst.ndim)

    caches = jax.tree.map(merge, ring, caches)
    if "tokens" in batch:
        tok = batch["tokens"][:, s - 1]
    else:
        tok = batch["embeds"][:, s - 1:s]
    logits1, _ = decoder.decode_step(
        cfg, CTX, params, caches, tok, jnp.asarray(s - 1, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits1), np.asarray(full[:, s - 1]), rtol=2e-3, atol=2e-3)


def test_decode_vector_positions_match_scalar():
    """Continuous batching: per-row pos == scalar pos when rows align."""
    cfg = dataclasses.replace(get_smoke_config("glm4-9b"), dtype="float32")
    key = jax.random.PRNGKey(2)
    params = init_params(cfg, key)
    b, s = 3, 16
    batch = _batch(cfg, key, b=b, s=s)
    batch.pop("labels")
    _, caches = decoder.prefill(cfg, CTX, params, batch)
    ring = decoder.init_cache(cfg, b, s + 4, jnp.float32)
    caches = jax.tree.map(
        lambda d, c: d if c is None else jax.lax.dynamic_update_slice(
            d, c.astype(d.dtype), (0,) * d.ndim), ring, caches)
    tok = jnp.asarray([1, 2, 3], jnp.int32)
    l_scalar, _ = decoder.decode_step(cfg, CTX, params, caches, tok,
                                      jnp.asarray(s, jnp.int32))
    l_vec, _ = decoder.decode_step(cfg, CTX, params, caches, tok,
                                   jnp.full((b,), s, jnp.int32))
    np.testing.assert_allclose(np.asarray(l_scalar), np.asarray(l_vec),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_shapes_consistent(arch):
    """Full configs: layer plan covers n_layers; param tree constructible."""
    cfg = get_config(arch)
    plan = layer_plan(cfg)
    assert plan.prefix + plan.period * plan.n_groups + plan.suffix == cfg.n_layers
    shapes = param_shapes(cfg, model_size=16)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda s: isinstance(s, tuple)))
    assert n > 0
    # headline parameter counts are in the right ballpark
    expected = {
        "qwen2-vl-2b": (1.2e9, 2.6e9), "glm4-9b": (8e9, 10.5e9),
        "phi4-mini-3.8b": (3.0e9, 4.6e9), "minitron-4b": (3.6e9, 5.0e9),
        "gemma3-27b": (2.2e10, 3.0e10), "deepseek-v2-236b": (2.1e11, 2.5e11),
        "mixtral-8x7b": (4.2e10, 5.0e10), "hubert-xlarge": (0.8e9, 1.3e9),
        "mamba2-780m": (6.5e8, 9.5e8), "zamba2-1.2b": (1.0e9, 1.6e9),
        "nemotron-3-nano-30b": (3.0e10, 3.3e10),
    }[arch]
    assert expected[0] < cfg.param_count() < expected[1], cfg.param_count()


def test_input_specs_grid():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for shape in SHAPES:
            if skip_reason(arch, cfg, shape):
                continue
            specs = input_specs(cfg, shape)
            assert specs, (arch, shape)
            for v in specs.values():
                assert isinstance(v, jax.ShapeDtypeStruct)
