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
import re

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


def _nbytes(tree):
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _served_step(one_chip, cfg, slots, max_len):
    """``RealBackend``'s jitted decode step (caches donated) compiled for one
    v5e at ``slots x max_len``; returns it with the parameter and cache
    shapes.  The backend itself holds a one-slot store: only its jit is
    used, lowered at full size from shapes."""
    from repro.models import decoder
    from repro.models.common import init_params
    from repro.serve.engine import RealBackend

    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        t)
    params = on_chip(jax.eval_shape(
        lambda k: init_params(cfg, k, dtype=cfg.compute_dtype()),
        jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(
        lambda: decoder.init_cache(cfg, slots, max_len, jnp.bfloat16)))
    be = RealBackend(cfg, decoder.RunCtx(mesh=None, use_kernel="ref"),
                     params, n_pods=1, n_slots=1, max_len=8)
    ids = _spec(one_chip, (slots,), jnp.int32)
    return be._step.lower(params, caches, ids, ids).compile(), params, caches


def test_glm4_cut_decode_step_fits_one_chip(one_chip):
    """One layer of a glm4-9b decode step (published widths, one pod of
    16 slots x 4096 tokens, bf16) compiles for v5e; scaled to 16 layers
    and two pods, its memory fits the chip."""
    from repro.configs import get_config

    full = get_config("glm4-9b")
    cfg = dataclasses.replace(full, n_layers=1)
    slots, max_len, layers, pods = 16, 4096, 16, 2
    compiled, params, caches = _served_step(one_chip, cfg, slots, max_len)
    ma = compiled.memory_analysis()
    p_bytes, c_bytes = _nbytes(params), _nbytes(caches)
    assert ma.argument_size_in_bytes >= p_bytes + c_bytes
    assert ma.alias_size_in_bytes >= c_bytes
    # per layer: the layer's weights, and its cache, written in place
    layer_w = p_bytes - 2 * full.vocab_size * full.d_model * 2
    total = (p_bytes + (layers - 1) * layer_w              # weights
             + pods * layers * c_bytes                     # donated caches
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
    cfg = _dsv2_cut(2)
    slots, max_len, layers = 256, 2048, 5
    compiled, params, caches = _served_step(one_chip, cfg, slots, max_len)
    text = compiled.as_text()
    assert f"f32[{slots},{max_len},512]" not in text
    assert f"f32[{slots},{max_len},64]" not in text
    ma = compiled.memory_analysis()
    p_bytes, c_bytes = _nbytes(params), _nbytes(caches)
    assert ma.argument_size_in_bytes >= p_bytes + c_bytes
    assert ma.alias_size_in_bytes >= c_bytes
    moe_layer = _nbytes(params["blocks"])
    total = (p_bytes + (layers - 2) * moe_layer             # weights
             + (layers / 2) * c_bytes                      # donated cache
             + ma.temp_size_in_bytes)
    assert total < HBM_BYTES, total


# One instruction of compiled HLO text (array-shaped results only) and the
# header of a computation.
_INST = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+\[[\d,]*\])(\{[^}]*\})? "
                   r"([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")


def _squeeze(shape):
    return tuple(d for d in shape if d != 1)


def _dims(hlo_shape):
    """The sizes of an HLO array shape (``bf16[1,32,64]``), unit dims dropped."""
    return _squeeze(int(d) for d in re.findall(r"\d+", hlo_shape.split("[")[1]))


def _layer_copies(text, layer_shapes):
    """The instructions of compiled HLO that copy one layer's cache within
    device memory: every dynamic update whose update or result is a
    layer's cache, and every kernel (an instruction of a computation that
    no fusion calls) that slices a layer's cache out into HBM.

    A layer's slice staged into on-chip memory (memory space ``S(1)``)
    for the attention's dot is the one read of it the step must make, and
    is not counted."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head:
            cur = comps.setdefault(head.group(1), {})
        elif cur is not None and (m := _INST.match(line)):
            name, shape, layout, op, rest = m.groups()
            cur[name] = (shape, layout or "", op, rest)
    fused = {c for line in text.splitlines() if " fusion(" in line
             for c in _CALLS.findall(line)}

    def ops_in(comp):
        for _, _, op, rest in comps.get(comp, {}).values():
            yield op
            for c in _CALLS.findall(rest):
                yield from ops_in(c)

    found = []
    for cname, insts in comps.items():
        for name, (shape, layout, op, rest) in insts.items():
            if op == "dynamic-update-slice":
                update = insts.get(rest.split(", ")[1].lstrip("%"), ("x[]",))
                if {_dims(shape), _dims(update[0])} & layer_shapes:
                    found.append(name)
            elif (cname not in fused and _dims(shape) in layer_shapes
                  and "S(1)" not in layout):
                inner = [op] + [o for c in _CALLS.findall(rest)
                                for o in ops_in(c)]
                if "dynamic-slice" in inner:
                    found.append(name)
    return found


@pytest.mark.parametrize("model", ["glm4-9b", "deepseek-v2"])
def test_served_decode_step_writes_caches_in_place(one_chip, model):
    """The decode step as ``RealBackend`` jits it, at the cells' sizes
    (glm4-9b: 2 scanned layers, 32 slots x 4096; DeepSeek-V2: the dense
    layer and 2 MoE layers, 256 slots x 2048), compiled for v5e: every
    cache buffer is donated and aliased to its output, no kernel slices a
    layer's cache out of the stack or writes one back, and the per-slot
    row writes lower to no loop (the layer scan is the only one)."""
    from repro.configs import get_config

    if model == "glm4-9b":
        cfg = dataclasses.replace(get_config("glm4-9b"), n_layers=2)
        slots, max_len = 32, 4096
    else:
        cfg, slots, max_len = _dsv2_cut(3), 256, 2048
    compiled, _, caches = _served_step(one_chip, cfg, slots, max_len)
    assert compiled.memory_analysis().alias_size_in_bytes >= _nbytes(caches)
    # a body leaf is [layers, slots, positions, ...]; the others one layer's
    layer_shapes = {_squeeze(x.shape[1:]) for x in jax.tree.leaves(caches["body"])}
    layer_shapes |= {_squeeze(x.shape) for k in ("prefix", "suffix")
                     for x in jax.tree.leaves(caches[k])}
    text = compiled.as_text()
    assert _layer_copies(text, layer_shapes) == []
    assert text.count(" while(") == 1


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
