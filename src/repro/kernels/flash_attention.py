"""Blocked online-softmax (flash) attention — Pallas TPU kernel.

Design (TPU-native, not a CUDA port):

* grid ``(B, Hq, nQ, nK)`` — the kv-block axis is the innermost (minor) grid
  dim, so VMEM scratch (running max ``m``, normalizer ``l``, accumulator
  ``acc``) persists across the kv sweep of one q block: the classic
  flash-attention recurrence expressed through TPU grid semantics rather
  than a thread-block loop.
* BlockSpec tiles q/k/v into VMEM at MXU-aligned shapes (multiples of 128
  on the contraction dims).
* masking is *position-based*: q/kv absolute positions ride in as tiny VMEM
  blocks (q positions as a ``[Bq, 1]`` column, kv positions as a ``[1, Bk]``
  row, so the mask is a plain broadcast compare), so the same kernel serves
  causal, sliding-window, bidirectional (encoder) and padded-cache
  attention; GQA is an index-map (kv head = q head // group) — no head
  replication in HBM.

``flash_attention`` (bottom) is the public wrapper: layout transposes,
padding to block multiples, and the pallas_call.  The pure-jnp oracle is
``repro.kernels.ref.sdpa_ref``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
VALID_POS_LIMIT = 2 ** 29          # kv positions >= this are padding


def _flash_kernel(
    qpos_ref, kpos_ref, q_ref, k_ref, v_ref,   # inputs
    o_ref,                                      # output
    m_scr, l_scr, acc_scr,                      # VMEM scratch
    *, scale: float, causal: bool, window: Optional[int],
    softcap: float, nk: int,
):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)         # [Bq, Dk]
    k = k_ref[0, 0].astype(jnp.float32)         # [Bk, Dk]
    v = v_ref[0, 0].astype(jnp.float32)         # [Bk, Dv]
    qp = qpos_ref[0]                            # [Bq, 1]
    kp = kpos_ref[0]                            # [1, Bk]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    ) * scale                                    # [Bq, Bk]
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)

    mask = kp < VALID_POS_LIMIT
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_scr[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] / safe[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "logit_softcap", "scale",
                     "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,                 # [B, Sq, Hq, Dk]
    k: jax.Array,                 # [B, Skv, Hkv, Dk]
    v: jax.Array,                 # [B, Skv, Hkv, Dv]
    *,
    q_positions: jax.Array,       # [B, Sq]
    kv_positions: jax.Array,      # [B, Skv]
    causal: bool = True,
    sliding_window: Optional[int] = None,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, dk = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    group = hq // hkv
    scale = scale if scale is not None else dk ** -0.5

    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(8, skv))
    pad_q = (-sq) % bq
    pad_k = (-skv) % bk

    # layout: [B, H, S, D]
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    qp, kp = q_positions.astype(jnp.int32), kv_positions.astype(jnp.int32)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, pad_q)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kp = jnp.pad(kp, ((0, 0), (0, pad_k)),
                     constant_values=2 ** 30)    # padding -> invalid
    nq = qt.shape[2] // bq
    nk = kt.shape[2] // bk
    qp = qp[:, :, None]                          # [B, Sq, 1]
    kp = kp[:, None, :]                          # [B, 1, Skv]

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=sliding_window,
        softcap=logit_softcap, nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1), lambda ib, ih, iq, ik: (ib, iq, 0)),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, iq, ik: (ib, 0, ik)),
            pl.BlockSpec((1, 1, bq, dk), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, dk),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dv),
                         lambda ib, ih, iq, ik, g=group: (ib, ih // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, qt.shape[2], dv), q.dtype),
        scratch_shapes=[
            _vmem((bq,), jnp.float32),
            _vmem((bq,), jnp.float32),
            _vmem((bq, dv), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, qt, kt, vt)
    out = jnp.moveaxis(out, 1, 2)
    if pad_q:
        out = out[:, :sq]
    return out


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
