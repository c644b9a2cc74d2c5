"""Mamba2 (state-space duality) mixer: chunked SSD scan + recurrent decode.

The SSD forward follows the Mamba2 paper's chunked decomposition: within a
chunk of length L the output is a (masked, decay-weighted) quadratic form —
attention-shaped, MXU-friendly; across chunks a small [H, P, N] state is
carried by an associative recurrence.  The Pallas kernel twin
(``repro.kernels.ssd_scan``) tiles chunks into VMEM; this module holds the
pure-jnp oracle and the layer plumbing (conv, gating, projections, caches).

Decode is O(1)/token: the recurrent form ``h ← h·exp(dtA) + dt·x⊗B`` over the
cached state, which is why SSM archs are the `long_500k`-capable family.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import layer_view
from .common import ModelConfig, rms_norm


# ---------------------------------------------------------------------------
# Chunked SSD scan (oracle; kernel twin in repro.kernels.ssd_scan)
# ---------------------------------------------------------------------------

def segsum(log_a: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum log_a[..., j+1..i] (−inf j>i)."""
    l = log_a.shape[-1]
    cs = jnp.cumsum(log_a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,      # [B, S, H, P]
    dt: jax.Array,     # [B, S, H]   (already softplus'd, >0)
    a: jax.Array,      # [H]         (negative: -exp(A_log))
    b_mat: jax.Array,  # [B, S, G, N]
    c_mat: jax.Array,  # [B, S, G, N]
    chunk: int,
    h0: Optional[jax.Array] = None,   # [B, H, P, N] initial state
    return_final_state: bool = False,
):
    """Chunked state-space-duality scan; S must be a multiple of ``chunk``."""
    bsz, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    assert s % chunk == 0, f"seq {s} not a multiple of chunk {chunk}"
    nc = s // chunk
    hpg = h // g
    f32 = jnp.float32

    xr = x.reshape(bsz, nc, chunk, h, p).astype(f32)
    dtr = dt.reshape(bsz, nc, chunk, h).astype(f32)
    br = b_mat.reshape(bsz, nc, chunk, g, n).astype(f32)
    cr = c_mat.reshape(bsz, nc, chunk, g, n).astype(f32)
    # expand groups -> heads
    be = jnp.repeat(br, hpg, axis=3)           # [B,nc,L,H,N]
    ce = jnp.repeat(cr, hpg, axis=3)

    da = dtr * a.astype(f32)[None, None, None, :]          # log decay per step
    da_cum = jnp.cumsum(da, axis=2)                        # [B,nc,L,H]
    seg = segsum(jnp.moveaxis(da, -1, -2))                 # [B,nc,H,L,L]

    # 1. intra-chunk (diagonal) term: masked decay-weighted attention
    cb = jnp.einsum("bnlhs,bnmhs->bnhlm", ce, be)          # [B,nc,H,L,L]
    y_diag = jnp.einsum(
        "bnhlm,bnhlm,bnmh,bnmhp->bnlhp", cb, jnp.exp(seg), dtr, xr
    )

    # 2. chunk-final states
    decay_states = jnp.exp(da_cum[:, :, -1:, :] - da_cum)  # [B,nc,L,H]
    states = jnp.einsum("bnlhs,bnlh,bnlh,bnlhp->bnhps", be, decay_states, dtr, xr)

    # 3. inter-chunk recurrence over the nc chunk states
    chunk_decay = jnp.exp(da_cum[:, :, -1, :])             # [B,nc,H]
    init = (
        jnp.zeros((bsz, h, p, n), f32)
        if h0 is None else h0.astype(f32)
    )

    def step(carry, inp):
        st, dec = inp                                       # [B,H,P,N], [B,H]
        new = carry * dec[..., None, None] + st
        return new, carry                                   # emit state *entering* the chunk

    (final, prevs) = jax.lax.scan(
        step,
        init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prevs, 0, 1)                 # [B,nc,H,P,N]

    # 4. off-diagonal contribution from the carried state
    state_decay = jnp.exp(da_cum)                           # decay from chunk start
    y_off = jnp.einsum("bnlhs,bnhps,bnlh->bnlhp", ce, prev_states, state_decay)

    y = (y_diag + y_off).reshape(bsz, s, h, p)
    if return_final_state:
        return y, final
    return y


def ssd_recurrent_step(
    h_state: jax.Array,  # [B, H, P, N]
    x_t: jax.Array,      # [B, H, P]
    dt_t: jax.Array,     # [B, H]
    a: jax.Array,        # [H]
    b_t: jax.Array,      # [B, G, N]
    c_t: jax.Array,      # [B, G, N]
) -> Tuple[jax.Array, jax.Array]:
    """One decode step of the SSD recurrence; returns (y_t, new_state)."""
    f32 = jnp.float32
    h, g = x_t.shape[1], b_t.shape[1]
    hpg = h // g
    be = jnp.repeat(b_t.astype(f32), hpg, axis=1)           # [B,H,N]
    ce = jnp.repeat(c_t.astype(f32), hpg, axis=1)
    da = jnp.exp(dt_t.astype(f32) * a.astype(f32)[None, :])  # [B,H]
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dt_t.astype(f32), x_t.astype(f32), be)
    new = h_state.astype(f32) * da[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new, ce)
    return y.astype(x_t.dtype), new


# ---------------------------------------------------------------------------
# Causal depthwise conv (d_conv small, e.g. 4)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array,
                  state: Optional[jax.Array] = None) -> jax.Array:
    """x [B,S,C], w [K,C], b [C]; optional left-context state [B,K-1,C]."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    # depthwise: sum_k w[k,c] * x[t-K+1+k, c]
    out = sum(xp[:, i: i + x.shape[1], :] * w[i][None, None, :] for i in range(k))
    return out + b[None, None, :]


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------

def _split_in_proj(cfg: ModelConfig, proj: jax.Array):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    gn = s.n_groups * s.d_state
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [di + 2 * gn], axis=-1)
    return z, xbc, dt                                       # dt: [B,S,nh]


def gated_rms_norm(y: jax.Array, z: jax.Array, w: jax.Array, n_groups: int,
                   eps: float) -> jax.Array:
    """Mamba2's gated RMSNorm ``norm(y * silu(z)) * w``, the norm taken over
    each of ``n_groups`` equal groups of the width on its own."""
    g = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    lead, width = g.shape[:-1], g.shape[-1]
    g = rms_norm(g.reshape(*lead, n_groups, width // n_groups),
                 w.reshape(n_groups, width // n_groups), eps)
    return g.reshape(*lead, width)


def mamba2_block(
    p: Dict[str, jax.Array],
    x: jax.Array,                 # [B, S, d]
    cfg: ModelConfig,
    *,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_index: Optional[jax.Array] = None,
    return_cache: bool = False,
    use_kernel: str = "auto",
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Full Mamba2 mixer.  ``cache`` = {conv [B,K-1,C], ssm [B,H,P,N]}, or
    with ``layer`` a scanned body's layer-stacked [G, B, ...] states, of
    which this layer reads and rewrites its entry in place (the returned
    cache is then the stack).

    A decode step's ``cache_index`` (scalar or [B]) is each slot's position:
    a slot at 0 starts from zero states, and a slot at a negative position
    is not in the step and keeps its states exactly."""
    with jax.named_scope("repro.mamba"):
        if cache is None or layer is None:
            return _mamba2(p, x, cfg, cache=cache, cache_index=cache_index,
                           return_cache=return_cache, use_kernel=use_kernel)
        y, new = _mamba2(p, x, cfg, cache={k: layer_view(a, layer)
                                           for k, a in cache.items()},
                         cache_index=cache_index, return_cache=return_cache,
                         use_kernel=use_kernel)
        if new is not None:
            new = {k: a.at[layer].set(new[k].astype(a.dtype))
                   for k, a in cache.items()}
        return y, new


def _mamba2(p, x, cfg, *, cache, cache_index, return_cache, use_kernel):
    s = cfg.ssm
    bsz, seq, _ = x.shape
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    gn = s.n_groups * s.d_state
    a = -jnp.exp(p["A_log"].astype(jnp.float32))

    proj = x @ p["w_in"]
    z, xbc, dt_raw = _split_in_proj(cfg, proj)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    if seq == 1 and cache is not None:
        # --- decode: shift conv state, recurrent SSD step --------------------
        conv0, ssm0 = cache["conv"], cache["ssm"]
        if cache_index is not None:
            pos = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (bsz,))
            fresh = (pos == 0)[:, None, None]
            conv0 = jnp.where(fresh, jnp.zeros((), conv0.dtype), conv0)
            ssm0 = jnp.where(fresh[..., None], jnp.zeros((), ssm0.dtype), ssm0)
        conv_state = jnp.concatenate(
            [conv0, xbc.astype(conv0.dtype)], axis=1)              # [B,K,C]
        xbc_t = jnp.einsum("bkc,kc->bc", conv_state.astype(jnp.float32),
                           p["conv_w"].astype(jnp.float32)) + p["conv_b"]
        xbc_t = jax.nn.silu(xbc_t).astype(x.dtype)[:, None, :]
        xs, b_mat, c_mat = jnp.split(xbc_t, [di, di + gn], axis=-1)
        y_t, new_ssm = ssd_recurrent_step(
            ssm0,
            xs.reshape(bsz, nh, s.head_dim),
            dt[:, 0],
            a,
            b_mat.reshape(bsz, s.n_groups, s.d_state),
            c_mat.reshape(bsz, s.n_groups, s.d_state),
        )
        y = y_t.reshape(bsz, 1, di)
        y = y + xs * p["D"].astype(x.dtype).repeat(s.head_dim)[None, None, :]
        new_cache = None
        if return_cache:
            new_cache = {"conv": conv_state[:, 1:, :], "ssm": new_ssm}
            if cache_index is not None:
                idle = pos < 0
                new_cache = jax.tree.map(
                    lambda old, new: jnp.where(
                        idle.reshape((bsz,) + (1,) * (new.ndim - 1)),
                        old.astype(new.dtype), new),
                    dict(cache), new_cache)
    else:
        # --- train / prefill: chunked scan -----------------------------------
        conv_in_state = cache["conv"] if cache is not None else None
        xbc_c = jax.nn.silu(causal_conv1d(xbc, p["conv_w"], p["conv_b"], conv_in_state))
        xs, b_mat, c_mat = jnp.split(xbc_c, [di, di + gn], axis=-1)
        xh = xs.reshape(bsz, seq, nh, s.head_dim)
        bm = b_mat.reshape(bsz, seq, s.n_groups, s.d_state)
        cm = c_mat.reshape(bsz, seq, s.n_groups, s.d_state)
        h0 = cache["ssm"] if cache is not None else None
        from repro.kernels import kernel_mode

        interpret = kernel_mode(use_kernel)
        if interpret is not None:
            from repro.kernels import ssd_scan as ssd_k

            y_h, final = ssd_k.ssd_scan(xh, dt, a, bm, cm, chunk=s.chunk,
                                        h0=h0, interpret=interpret)
        else:
            pad = (-seq) % s.chunk
            if pad:
                xh_p = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
                dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
                bm_p = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
                cm_p = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
            else:
                xh_p, dt_p, bm_p, cm_p = xh, dt, bm, cm
            y_h, final = ssd_chunked(
                xh_p, dt_p, a, bm_p, cm_p, s.chunk, h0=h0, return_final_state=True
            )
            y_h = y_h[:, :seq]
        y = y_h.reshape(bsz, seq, di).astype(x.dtype)
        y = y + xs * p["D"].astype(x.dtype).repeat(s.head_dim)[None, None, :]
        new_cache = None
        if return_cache:
            k = s.d_conv
            tail = xbc[:, -(k - 1):, :]
            if cache is not None:
                tail = jnp.concatenate([cache["conv"], xbc], axis=1)[:, -(k - 1):, :]
            elif seq < k - 1:
                tail = jnp.pad(xbc, ((0, 0), (k - 1 - seq, 0), (0, 0)))
            new_cache = {"conv": tail, "ssm": final}

    y = gated_rms_norm(y, z, p["gate_norm"], s.n_groups, cfg.norm_eps)
    return y @ p["w_out"], new_cache


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype) -> Dict[str, jax.Array]:
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
    }
