"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid ``(B, nHeadBlocks, nChunks)`` with the chunk axis innermost: the
inter-chunk recurrent state (``[Hb, P, N]`` fp32) lives in VMEM scratch and
persists across the chunk sweep — the sequential recurrence is expressed
through TPU grid semantics, while each chunk's quadratic intra-chunk term
is MXU work on VMEM tiles.  Head-blocking keeps the [Hb, L, L] decay
matrices inside VMEM.

Restriction: ``n_groups == 1`` (true for every assigned SSM arch); the
general grouped case falls back to the jnp oracle
(:func:`repro.kernels.ref.ssd_ref`).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bdot(lhs, rhs, contract):
    """Head-batched f32 matmul: batch dim 0, contracting ``contract``."""
    return jax.lax.dot_general(
        lhs, rhs, (contract, ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _ssd_kernel(
    x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, h0_ref,   # inputs
    y_ref, hout_ref,                                         # outputs
    state_scr,                                               # VMEM [Hb, P, N]
    *, nc: int,
):
    inc = pl.program_id(2)

    @pl.when(inc == 0)
    def _init():
        state_scr[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # [Hb, L, P]
    dt_row = dtr_ref[0].astype(jnp.float32)   # [Hb, 1, L]
    dt_col = dtc_ref[0].astype(jnp.float32)   # [Hb, L, 1]
    a = a_ref[...].astype(jnp.float32)        # [Hb, 1, 1]
    hb, l, _ = x.shape
    n = b_ref.shape[-1]
    bm = jnp.broadcast_to(b_ref[0].astype(jnp.float32)[None], (hb, l, n))
    cm = jnp.broadcast_to(c_ref[0].astype(jnp.float32)[None], (hb, l, n))

    # inclusive cumsum of the log-decay along L, in both layouts, as
    # triangular matmuls (row i / column j of ``tri`` marks j <= i)
    ii = jax.lax.broadcasted_iota(jnp.int32, (hb, l, l), 1)
    jj = jax.lax.broadcasted_iota(jnp.int32, (hb, l, l), 2)
    tri = jj <= ii
    trif = tri.astype(jnp.float32)
    dacum_col = _bdot(trif, dt_col * a, ((2,), (1,)))        # [Hb, L, 1]
    dacum_row = _bdot(dt_row * a, trif, ((2,), (2,)))        # [Hb, 1, L]

    # --- intra-chunk quadratic term -------------------------------------
    # seg[h, i, j] = dacum[h, i] - dacum[h, j]  (i >= j)
    seg = dacum_col - dacum_row                               # [Hb, L, L]
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
    cb = _bdot(cm, bm, ((2,), (2,)))                          # [Hb, L, L]
    w = cb * decay * dt_row                                   # [Hb, L(i), L(j)]
    y_diag = _bdot(w, x, ((2,), (1,)))                        # [Hb, L, P]

    # --- contribution of the carried state --------------------------------
    state = state_scr[...]                                     # [Hb, P, N]
    y_off = _bdot(cm, state, ((2,), (2,))) * jnp.exp(dacum_col)

    # --- state update -------------------------------------------------------
    last = dacum_col[:, l - 1:, :]                             # [Hb, 1, 1]
    xw = x * (jnp.exp(last - dacum_col) * dt_col)              # [Hb, L, P]
    upd = _bdot(jnp.swapaxes(xw, 1, 2), bm, ((2,), (1,)))      # [Hb, P, N]
    # the chunk's total decay as an [Hb, 1, N] row (a matmul against ones):
    # Mosaic cannot broadcast an [Hb, 1, 1] scalar over sublanes and lanes
    ones = jnp.ones((hb, l, n), jnp.float32)
    keep = jnp.exp(_bdot(dt_row * a, ones, ((2,), (1,))))     # [Hb, 1, N]
    state_scr[...] = state * keep + upd

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    @pl.when(inc == nc - 1)
    def _finish():
        hout_ref[0] = state_scr[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "block_heads", "interpret"),
)
def ssd_scan(
    x: jax.Array,       # [B, S, H, P]
    dt: jax.Array,      # [B, S, H]  (softplus'd)
    a: jax.Array,       # [H]
    b_mat: jax.Array,   # [B, S, 1, N]
    c_mat: jax.Array,   # [B, S, 1, N]
    *,
    chunk: int = 256,
    h0: Optional[jax.Array] = None,     # [B, H, P, N]
    block_heads: int = 8,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y [B, S, H, P], final_state [B, H, P, N] fp32)."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    if g != 1:
        from . import ref

        return ref.ssd_ref(x, dt, a, b_mat, c_mat, chunk=chunk, h0=h0)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    hb = min(block_heads, h)
    while h % hb:
        hb -= 1
    nh = h // hb
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    # head-major layouts keep every block's last two dims tile-aligned:
    # x/y as [B, H, S, P], dt as a lane-dense row and a sublane column
    xt = jnp.moveaxis(x, 2, 1)
    dtt = jnp.moveaxis(dt, 2, 1)
    kernel = functools.partial(_ssd_kernel, nc=nc)
    y, hout = pl.pallas_call(
        kernel,
        grid=(bsz, nh, nc),
        in_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, hb, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, hb, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((hb, 1, 1), lambda ib, ih, ic: (ih, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda ib, ih, ic: (ib, ic, 0)),
            pl.BlockSpec((1, hb, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, hb, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[_vmem((hb, p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dtt[:, :, None, :], dtt[:, :, :, None], a.reshape(h, 1, 1),
      b_mat[:, :, 0], c_mat[:, :, 0], h0)
    return jnp.moveaxis(y, 1, 2), hout


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)
