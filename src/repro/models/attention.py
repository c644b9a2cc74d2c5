"""Attention mixers: GQA (causal / bidirectional / sliding-window), MLA.

All functions are pure; KV caches are explicit pytrees threaded by the
caller.  Three entry points per mixer:

* ``*_train``   — full-sequence forward (no cache), used by train steps and
  encoder forwards;
* ``*_prefill`` — full-sequence forward that also returns the populated cache;
* ``*_decode``  — single-token step consuming/updating the cache.

The inner attention product dispatches to the Pallas flash kernel on TPU
(``repro.kernels.flash_attention``) and to the fused-mask jnp reference on
other backends; :func:`repro.kernels.kernel_mode` makes that choice.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, apply_rope, rms_norm, yarn_mscale

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def write_rows(buf: jax.Array, new: jax.Array, index, layer=None) -> jax.Array:
    """Write each slot's one new row ``new`` [B, 1, ...] into the cache
    ``buf`` at its position ``index`` (scalar or [B]): one scatter at
    (slot, index), or at (layer, slot, index) into a layer-stacked
    [G, B, S, ...] buffer, which XLA updates in place.  A position past
    the end writes the last row, as ``dynamic_update_slice`` clamps."""
    b, s = new.shape[:2]
    if s != 1:
        raise ValueError(f"one new row per slot, got {s}")
    slots = jnp.arange(b, dtype=jnp.int32)
    pos = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    at = (slots, pos) if layer is None else (layer, slots, pos)
    return buf.at[at].set(new[:, 0].astype(buf.dtype), mode="clip",
                          unique_indices=True)


def layer_view(buf: jax.Array, layer=None) -> jax.Array:
    """The one layer's [B, S, ...] cache: ``buf`` itself, or its ``layer``
    entry of a layer-stacked buffer."""
    if layer is None:
        return buf
    return jax.lax.dynamic_index_in_dim(buf, layer, 0, keepdims=False)


def _shard_kv(ctx, arr: jax.Array) -> jax.Array:
    """Constrain a KV buffer [B, S, ...] onto the ctx mesh's cache layout.

    Delegates to :func:`repro.dist.sharding.kv_buffer_spec` — the same rule
    ``cache_pspecs`` allocates with — so the in-step constraint and the
    ``KVStore`` placement cannot drift apart.  With the constraint inside
    the jitted step, GSPMD keeps the cache resident in its sharded
    placement across decode steps and partitions the score/context
    products over the seq shards, gathering only the O(S·d) softmax
    statistics instead of re-laying-out the cache.
    """
    if ctx is None or ctx.mesh is None:
        return arr
    from repro.dist.sharding import kv_buffer_spec

    spec = kv_buffer_spec(
        arr.shape, bdim=0, batch=ctx.batch_axes,
        model=ctx.model_axis, msize=ctx.model_size,
        seq=ctx.seq_axis, ssize=ctx.seq_size)
    return jax.lax.with_sharding_constraint(
        arr, jax.sharding.NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Masking
# ---------------------------------------------------------------------------

def attn_mask(
    q_pos: jax.Array,            # [B, Sq] absolute positions of the queries
    kv_pos: jax.Array,           # [B, Skv]
    causal: bool,
    sliding_window: Optional[int],
) -> jax.Array:
    """Boolean [B, Sq, Skv] mask (True = attend)."""
    dq = q_pos[:, :, None]
    dk = kv_pos[:, None, :]
    m = jnp.ones((q_pos.shape[0], q_pos.shape[1], kv_pos.shape[1]), bool)
    if causal:
        m &= dk <= dq
    if sliding_window is not None:
        m &= dk > dq - sliding_window
    return m


def _sdpa_ref(
    q: jax.Array,                # [B, Sq, Hq, D]
    k: jax.Array,                # [B, Skv, Hkv, D]
    v: jax.Array,                # [B, Skv, Hkv, Dv]
    mask: jax.Array,             # [B, Sq, Skv] bool
    scale: float,
    logit_softcap: float = 0.0,
) -> jax.Array:
    """Pure-jnp grouped-query attention (the oracle path)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if logit_softcap > 0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, v.shape[-1]).astype(q.dtype)


def sdpa(
    q, k, v, *,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    causal: bool,
    sliding_window: Optional[int] = None,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    use_kernel: str = "auto",
) -> jax.Array:
    """Scaled dot-product attention with GQA + optional flash kernel."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    from repro.kernels import kernel_mode

    interpret = kernel_mode(use_kernel)
    if interpret is not None and q.shape[1] > 1:
        from repro.kernels import flash_attention as fa

        return fa.flash_attention(
            q, k, v,
            q_positions=q_positions, kv_positions=kv_positions,
            causal=causal, sliding_window=sliding_window,
            logit_softcap=logit_softcap, scale=scale, interpret=interpret,
        )
    mask = attn_mask(q_positions, kv_positions, causal, sliding_window)
    return _sdpa_ref(q, k, v, mask, scale, logit_softcap)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------

def _split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def gqa_project_qkv(
    p: Dict[str, jax.Array],
    x: jax.Array,                  # [B, S, d]
    cfg: ModelConfig,
    positions: jax.Array,          # [B, S] or [3, B, S]
    rope_theta: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = _split_heads(x @ p["wq"], cfg.n_heads)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps, gemma=cfg.gemma_norm)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps, gemma=cfg.gemma_norm)
    q = apply_rope(q, positions, rope_theta, cfg.partial_rotary, cfg.mrope_sections)
    k = apply_rope(k, positions, rope_theta, cfg.partial_rotary, cfg.mrope_sections)
    return q, k, v


def gqa_attention(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    is_global: bool = True,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    return_cache: bool = False,
    use_kernel: str = "auto",
    ctx=None,
    layer=None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """One GQA attention block (no residual / norm — the caller owns those).

    ``cache`` (decode): dict(k=[B, S_max, Hkv, D], v=...), or with
    ``layer`` the whole layer-stacked [G, B, S_max, Hkv, D] buffers.  ``x``
    is [B, 1, d]; its k/v rows are written at ``cache_index`` and the
    returned cache is the written buffers (the stack, with ``layer``).

    When the head count does not divide the model mesh axis (e.g. 24 heads
    on a 16-way axis), head TP is impossible without splitting head_dim —
    which GSPMD resolves by all-reducing the full [S, S] score matrix.
    Instead we switch to *sequence-parallel attention*: the query sequence
    dim is sharded over the model axis (k/v stay whole), so the quadratic
    score work is partitioned with only O(S·d)-sized gathers.
    """
    theta = cfg.rope_theta
    window = None
    if not is_global and cfg.sliding_window is not None:
        window = cfg.sliding_window
    elif is_global and cfg.rope_theta_global is not None:
        theta = cfg.rope_theta_global

    q, k, v = gqa_project_qkv(p, x, cfg, positions, theta)
    q_pos = positions[0] if positions.ndim == 3 else positions

    seq_parallel = (
        ctx is not None and ctx.mesh is not None and x.shape[1] > 1
        and cfg.n_heads % ctx.model_size != 0
        and x.shape[1] % ctx.model_size == 0
    )
    if seq_parallel:
        q = ctx.shard_act(q, ctx.batch_axes, ctx.model_axis, None, None)
        k = ctx.shard_act(k, ctx.batch_axes, None, None, None)
        v = ctx.shard_act(v, ctx.batch_axes, None, None, None)

    new_cache = None
    if cache is not None and cache_index is not None:
        # decode: append to the cache ring.  cache_index is a scalar (all
        # sequences aligned) or a [B] vector (continuous batching).
        b = x.shape[0]
        k_buf = write_rows(cache["k"], k, cache_index, layer)
        v_buf = write_rows(cache["v"], v, cache_index, layer)
        if return_cache:
            new_cache = {"k": k_buf, "v": v_buf}
        k_all = _shard_kv(ctx, layer_view(k_buf, layer))
        v_all = _shard_kv(ctx, layer_view(v_buf, layer))
        skv = k_all.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32)[None, :],
                                  (b, skv))
        # entries beyond the current write point are invalid -> mask via pos
        valid_upto = cache_index + x.shape[1]
        if jnp.ndim(valid_upto) == 1:
            valid_upto = valid_upto[:, None]
        kv_pos = jnp.where(kv_pos < valid_upto, kv_pos, jnp.int32(2**30))
        out = sdpa(
            q, k_all, v_all,
            q_positions=q_pos, kv_positions=kv_pos,
            causal=cfg.causal, sliding_window=window,
            logit_softcap=0.0, use_kernel=use_kernel,
        )
    else:
        if return_cache:
            # the prefill cache leaves in the long-context layout (seq
            # sharded) even though the score product below keeps k/v whole
            new_cache = {"k": _shard_kv(ctx, k), "v": _shard_kv(ctx, v)}
        out = sdpa(
            q, k, v,
            q_positions=q_pos, kv_positions=q_pos,
            causal=cfg.causal, sliding_window=window,
            logit_softcap=0.0, use_kernel=use_kernel,
        )
    b, s = x.shape[:2]
    out = out.reshape(b, s, -1)
    if seq_parallel:
        # the output projection is row-local on the S-sharded activations;
        # GSPMD re-gathers S at the residual boundary (Megatron-SP style)
        out = ctx.shard_act(out, ctx.batch_axes, ctx.model_axis, None)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 multi-head latent attention
# ---------------------------------------------------------------------------
#
# The KV cache stores only the compressed latent c_kv [B, S, kv_lora] and the
# decoupled rope key k_pe [B, S, rope_dim] — 576 values/token/layer — which is
# the paper-exact memory saving that makes 500k-token decode shardable.

def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5``, times YaRN's ``mscale_all_dim``
    correction squared when the rotary is scaled."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_attention(
    p: Dict[str, jax.Array],
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    *,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    return_cache: bool = False,
    use_kernel: str = "auto",
    is_global: bool = True,
    ctx=None,
    layer=None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """``cache`` and ``layer`` as :func:`gqa_attention`'s, with the latent
    ``c_kv`` [.., B, S_max, kv_lora] and rope key ``k_pe`` leaves."""
    with jax.named_scope("repro.mla"):
        return _mla(p, x, cfg, positions, cache=cache,
                    cache_index=cache_index, return_cache=return_cache,
                    use_kernel=use_kernel, ctx=ctx, layer=layer)


def _mla(p, x, cfg, positions, *, cache, cache_index, return_cache,
         use_kernel, ctx, layer):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    rope = functools.partial(apply_rope, theta=cfg.rope_theta,
                             scaling=cfg.rope_scaling)

    # --- queries (low-rank) -------------------------------------------------
    cq = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["wq_b"]).reshape(b, s, h, qk_dim)
    q_nope, q_pe = q[..., : m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    q_pe = rope(q_pe, positions)

    # --- compressed KV latent ------------------------------------------------
    ckv_full = x @ p["wkv_a"]                              # [B,S,kv_lora+rope]
    c_kv = rms_norm(ckv_full[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(ckv_full[..., None, m.kv_lora_rank:], positions)
    k_pe = k_pe[..., 0, :]                                 # [B,S,rope_dim]

    q_pos = positions[0] if positions.ndim == 3 else positions
    if cache is not None and cache_index is not None:
        c_buf = write_rows(cache["c_kv"], c_kv, cache_index, layer)
        pe_buf = write_rows(cache["k_pe"], k_pe, cache_index, layer)
        new_cache = {"c_kv": c_buf, "k_pe": pe_buf} if return_cache else None
        c_all = _shard_kv(ctx, layer_view(c_buf, layer))
        pe_all = _shard_kv(ctx, layer_view(pe_buf, layer))
        skv = c_all.shape[1]
        kv_pos = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32)[None, :], (b, skv))
        valid_upto = cache_index + s
        if jnp.ndim(valid_upto) == 1:
            valid_upto = valid_upto[:, None]
        kv_pos = jnp.where(kv_pos < valid_upto, kv_pos, jnp.int32(2**30))
        c_kv_use, k_pe_use = c_all, pe_all
    else:
        new_cache = ({"c_kv": _shard_kv(ctx, c_kv),
                      "k_pe": _shard_kv(ctx, k_pe)} if return_cache else None)
        skv = s
        kv_pos = q_pos
        c_kv_use, k_pe_use = c_kv, k_pe

    # --- expand latent to per-head K/V (absorbed form for decode) -----------
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim)
    w_k = wkv_b[..., : m.qk_nope_head_dim]                 # [r, h, dk]
    w_v = wkv_b[..., m.qk_nope_head_dim:]                  # [r, h, dv]
    scale = mla_softmax_scale(cfg)
    if s == 1 and cache is not None:
        # decode: absorb w_k into the query -> score directly in latent space,
        # never materializing [B, Skv, h, dk].  FLOPs/token: h*(dk*r + r) per
        # key instead of expanding the whole cache.  Operands stay in the
        # cache's type, products accumulate in float32: the cache is read
        # as it is stored, never copied wider
        cdt = c_kv_use.dtype
        f32 = jnp.float32
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope.astype(cdt), w_k.astype(cdt),
                           preferred_element_type=f32).astype(cdt)
        logits = jnp.einsum("bqhr,bkr->bhqk", q_lat, c_kv_use,
                            preferred_element_type=f32)
        logits += jnp.einsum("bqhd,bkd->bhqk", q_pe.astype(cdt), k_pe_use,
                             preferred_element_type=f32)
        logits *= scale
        mask = attn_mask(q_pos, kv_pos, cfg.causal, None)
        logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
        pr = jax.nn.softmax(logits, axis=-1).astype(cdt)
        ctx_lat = jnp.einsum("bhqk,bkr->bhqr", pr, c_kv_use,
                             preferred_element_type=f32)
        ctx_lat = ctx_lat.transpose(0, 2, 1, 3).astype(cdt)  # [B,1,h,r]
        out = jnp.einsum("bqhr,rhd->bqhd", ctx_lat, w_v.astype(cdt),
                         preferred_element_type=f32).astype(x.dtype)
    else:
        k_nope = jnp.einsum("bkr,rhd->bkhd", c_kv_use, w_k.astype(c_kv_use.dtype))
        v_full = jnp.einsum("bkr,rhd->bkhd", c_kv_use, w_v.astype(c_kv_use.dtype))
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe_use[:, :, None, :], (b, skv, h, m.qk_rope_head_dim))],
            axis=-1,
        )
        q_full = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = sdpa(
            q_full, k_full, v_full,
            q_positions=q_pos, kv_positions=kv_pos,
            causal=cfg.causal, sliding_window=None,
            scale=scale, use_kernel=use_kernel,
        )
    return out.reshape(b, s, -1) @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# Cache allocation
# ---------------------------------------------------------------------------

def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict[str, Any]:
    """Zeroed per-layer cache entry for one attention layer."""
    if cfg.mla is not None:
        m = cfg.mla
        return {
            "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_pe": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
