"""Compile the main path's kernels and decode step for a TPU v5e, no chip.

The TPU compiler is installed with jax; it compiles for a described
``v5e:2x2`` topology whether or not a chip is attached, and refuses what the
chip would refuse (unaligned block shapes, unsupported vector ops, programs
that do not fit the device).  Interpret-mode tests cannot see any of that.
The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and a test worker keeps it
until it exits.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 2**30           # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the compiler otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 -- any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def test_lease_validate_compiles_for_v5e(one_chip):
    from repro.kernels.lease_validate import lease_validate

    i32 = jnp.int32
    n, b, r, w = 65536, 256, 64, 8
    c = _compile(lease_validate, *(_spec(one_chip, s, i32) for s in (
        (n,), (b, r), (b, r), (n,), (b, w))))
    assert "tpu_custom_call" in c.as_text()


def test_lease_validate_compiles_for_v5e_per_entry_locks(one_chip):
    """The TPC-C cell's widest certify call: 1,140,088 store versions, 16
    rows of 32 reads and 16 writes, and one lock bit a write entry."""
    from repro.kernels.lease_validate import lease_validate

    i32 = jnp.int32
    n, b, r, w = 1140088, 16, 32, 16
    c = _compile(lease_validate, *(_spec(one_chip, s, i32) for s in (
        (n,), (b, r), (b, r), (b * w,), (b, w))))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles_for_v5e(one_chip):
    from repro.kernels.flash_attention import flash_attention

    b, s, hq, hkv, d = 4, 1024, 32, 2, 128
    bf = jnp.bfloat16

    def attend(q, k, v, qp, kp):
        return flash_attention(q, k, v, q_positions=qp, kv_positions=kp)

    c = _compile(attend, _spec(one_chip, (b, s, hq, d), bf),
                 _spec(one_chip, (b, s, hkv, d), bf),
                 _spec(one_chip, (b, s, hkv, d), bf),
                 _spec(one_chip, (b, s), jnp.int32),
                 _spec(one_chip, (b, s), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_for_v5e(one_chip):
    from repro.configs import get_config
    from repro.kernels.ssd_scan import ssd_scan

    cfg = get_config("mamba2-780m")
    ssm = cfg.ssm
    b, s = 1, 4096
    h, p, n = ssm.n_heads(cfg.d_model), ssm.head_dim, ssm.d_state
    bf, f32 = jnp.bfloat16, jnp.float32

    def scan(x, dt, a, bm, cm):
        return ssd_scan(x, dt, a, bm, cm, chunk=ssm.chunk)

    c = _compile(scan, _spec(one_chip, (b, s, h, p), bf),
                 _spec(one_chip, (b, s, h), f32), _spec(one_chip, (h,), f32),
                 _spec(one_chip, (b, s, 1, n), bf),
                 _spec(one_chip, (b, s, 1, n), bf))
    assert "tpu_custom_call" in c.as_text()


def test_glm4_cut_decode_step_fits_one_chip(one_chip):
    """One layer of chip_smoke's glm4-9b decode step (published widths, one
    pod of 16 slots x 4096 tokens, bf16) compiles for v5e; scaled to the
    smoke's 16 layers and two pods, its memory fits the chip."""
    from repro.configs import get_config
    from repro.models import decoder
    from repro.models.common import init_params

    full = get_config("glm4-9b")
    cfg = dataclasses.replace(full, n_layers=1)
    slots, max_len, layers, pods = 16, 4096, 16, 2
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=cfg.compute_dtype()),
        jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(
        lambda: decoder.init_cache(cfg, slots, max_len, jnp.bfloat16)))
    ctx = decoder.RunCtx(mesh=None, use_kernel="ref")

    def step(params, caches, tokens, pos):
        return decoder.decode_step(cfg, ctx, params, caches, tokens, pos)

    ids = _spec(one_chip, (slots,), jnp.int32)
    ma = _compile(step, params, caches, ids, ids).memory_analysis()
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    p_bytes, c_bytes = nbytes(params), nbytes(caches)
    assert ma.argument_size_in_bytes >= p_bytes + c_bytes
    # per layer: the layer's weights, and its cache read and rewritten
    layer_w = p_bytes - 2 * full.vocab_size * full.d_model * 2
    total = (p_bytes + (layers - 1) * layer_w              # weights
             + (pods + 1) * layers * c_bytes               # caches + step out
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def _dsv2_cut(n_layers):
    """DeepSeek-V2 at published widths holding routing group 0 (experts
    0-19), as the benchmark's cell runs it, ``n_layers`` deep."""
    from repro.configs import get_config

    full = get_config("deepseek-v2-236b")
    return dataclasses.replace(full, n_layers=n_layers, moe=dataclasses.replace(
        full.moe, held_first=0, n_held=20))


def test_dsv2_mla_decode_step_fits_one_chip(one_chip):
    """The dense first layer and one MoE layer of DeepSeek-V2's decode step
    (published widths, 256 slots x 2048 positions, bf16) compile for v5e;
    MLA's absorbed decode reads the latent cache in bf16 (no float32 copy
    of it); scaled to the cell's five layers, its memory fits the chip."""
    from repro.models import decoder
    from repro.models.common import init_params

    cfg = _dsv2_cut(2)
    slots, max_len, layers = 256, 2048, 5
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=cfg.compute_dtype()),
        jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(
        lambda: decoder.init_cache(cfg, slots, max_len, jnp.bfloat16)))
    ctx = decoder.RunCtx(mesh=None, use_kernel="ref")

    def step(params, caches, tokens, pos):
        return decoder.decode_step(cfg, ctx, params, caches, tokens, pos,
                                   return_stats=True)

    ids = _spec(one_chip, (slots,), jnp.int32)
    compiled = _compile(step, params, caches, ids, ids)
    text = compiled.as_text()
    assert f"f32[{slots},{max_len},512]" not in text
    assert f"f32[{slots},{max_len},64]" not in text
    ma = compiled.memory_analysis()
    nbytes = lambda t: sum(x.size * x.dtype.itemsize  # noqa: E731
                           for x in jax.tree.leaves(t))
    p_bytes, c_bytes = nbytes(params), nbytes(caches)
    assert ma.argument_size_in_bytes >= p_bytes + c_bytes
    moe_layer = nbytes(params["blocks"])
    total = (p_bytes + (layers - 2) * moe_layer             # weights
             + 2 * (layers / 2) * c_bytes                  # caches + step out
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total


def test_dsv2_expert_share_kernel_compiles_for_v5e(one_chip):
    """The expert share of one DeepSeek-V2 MoE layer (20 held experts of
    160, top-6 over 8 groups, 256 tokens) through the grouped-matmul
    kernel compiles for v5e."""
    from repro.models import moe
    from repro.models.common import param_shapes

    cfg = _dsv2_cut(2)
    shapes = param_shapes(cfg)["blocks"]["pos0"]["moe"]
    p = jax.tree.map(lambda s: _spec(one_chip, s[1:], jnp.bfloat16), shapes,
                     is_leaf=lambda s: isinstance(s, tuple))
    x = _spec(one_chip, (256, 1, cfg.d_model), jnp.bfloat16)
    c = _compile(lambda p, x: moe.moe_share(p, x, cfg, interpret=False), p, x)
    assert "tpu_custom_call" in c.as_text()
