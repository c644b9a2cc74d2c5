"""Jit'd public wrappers over the Pallas kernels: the one dispatch point.

``backend="auto"`` uses the compiled Pallas kernel on TPU and the pure-jnp
oracle elsewhere; ``backend="pallas"`` forces the kernel, compiled on TPU
and interpreted on other backends (the kernels themselves take
``interpret`` as given and never probe the backend).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import host_span

from . import kernel_mode, ref
from .flash_attention import flash_attention as _flash
from .lease_validate import lease_validate as _lease_validate
from .ssd_scan import ssd_scan as _ssd


def attention(q, k, v, *, q_positions, kv_positions, causal=True,
              sliding_window=None, logit_softcap=0.0, scale=None,
              backend: str = "auto"):
    interpret = kernel_mode(backend)
    if interpret is not None:
        return _flash(q, k, v, q_positions=q_positions,
                      kv_positions=kv_positions, causal=causal,
                      sliding_window=sliding_window,
                      logit_softcap=logit_softcap, scale=scale,
                      interpret=interpret)
    return ref.sdpa_ref(q, k, v, q_positions=q_positions,
                        kv_positions=kv_positions, causal=causal,
                        sliding_window=sliding_window,
                        logit_softcap=logit_softcap, scale=scale)


def ssd(x, dt, a, b_mat, c_mat, *, chunk=256, h0=None, backend: str = "auto"):
    interpret = kernel_mode(backend)
    if interpret is not None and b_mat.shape[2] == 1:
        return _ssd(x, dt, a, b_mat, c_mat, chunk=chunk, h0=h0,
                    interpret=interpret)
    return ref.ssd_ref(x, dt, a, b_mat, c_mat, chunk=chunk, h0=h0)


@jax.jit
def _lease_settle_jit(head_req, head_proc, head_active, qlen, fresh_blocked,
                      wait_req, wait_cc, proc):
    return ref.lease_settle_ref(head_req, head_proc, head_active, qlen,
                                fresh_blocked, wait_req, wait_cc, proc)


def settle_lease_batch(head_req, head_proc, head_active, qlen, fresh_blocked,
                       wait_req, wait_cc, proc, *, backend: str = "auto"):
    """One jit'd lease settle per delivery instant — the dispatch point of
    the sharded lease control plane (``repro.core.lease_batched``).

    Returns ``(owner[C], free[C], enabled[B])``: head ownership,
    blocked-and-drained frees, and ``isEnabled`` verdicts for the packed
    waiting groups.  All inputs are pow2-bucketed by the caller so
    recurring instant shapes reuse the compiled kernel; there is no
    hand-written Pallas variant yet — the jit'd jnp path is the dispatch
    on every backend (same structure as ``validate_transactions``'s ref
    path, and the hook point for a TPU kernel later).
    """
    del backend  # single jit'd path for now; kept for API symmetry
    with host_span("repro.ops.settle", h2d_bytes=lambda: _h2d_bytes(
            head_req, head_proc, head_active, qlen, fresh_blocked,
            wait_req, wait_cc)):
        return _lease_settle_jit(
            jnp.asarray(head_req, jnp.int32),
            jnp.asarray(head_proc, jnp.int32),
            jnp.asarray(head_active, jnp.int32), jnp.asarray(qlen, jnp.int32),
            jnp.asarray(fresh_blocked, bool), jnp.asarray(wait_req, jnp.int32),
            jnp.asarray(wait_cc, jnp.int32), jnp.int32(proc))


def moe_combine(back, tok_slot, gate_slot, *, tp: int, capacity: int,
                t_out: int, backend: str = "auto"):
    """Partial-activation psum + gated scatter closing the MoE a2a combine
    leg (``repro.models.moe._moe_local_a2a``): sums the ``tp`` f-slice
    partials per expert-group slot, then scatters gated rows to tokens.
    Runs inside ``shard_map``, so it must stay traceable — no jit wrapper
    of its own; the jnp oracle is the dispatch on every backend (hook
    point for a fused Pallas scatter later).
    """
    del backend  # single path for now; kept for API symmetry
    return ref.moe_combine_ref(back, tok_slot, gate_slot, tp=tp,
                               capacity=capacity, t_out=t_out)


@jax.jit
def _lease_validate_ref_jit(store_versions, read_items, read_versions,
                            write_locks, write_items):
    return ref.lease_validate_ref(store_versions, read_items, read_versions,
                                  write_locks > 0, write_items)


def validate_transactions(
    store_versions, read_items, read_versions,
    write_locks=None, write_items=None, *, backend: str = "auto",
):
    """Batched TL2 certification — the single dispatch point both the
    simulator (``repro.core.stm.validate_batch``) and the serving certifier
    (``repro.serve.certifier``) go through.  ``write_items`` index
    ``write_locks`` (any length: one value an item, or one a write entry);
    without locks every write passes, on both backends alike.
    """
    with host_span("repro.ops.validate", h2d_bytes=lambda: _h2d_bytes(
            store_versions, read_items, read_versions, write_locks,
            write_items), lock_lanes=lambda: (
                1 if write_locks is None else len(write_locks))):
        b = read_items.shape[0]
        store_versions = jnp.asarray(store_versions, jnp.int32)
        if write_locks is None:
            # one unlocked lane: nothing to sweep per item
            write_locks = jnp.zeros((1,), jnp.int32)
        else:
            write_locks = jnp.asarray(write_locks, jnp.int32)
        if write_items is None:
            write_items = jnp.full((b, 1), -1, jnp.int32)
        interpret = kernel_mode(backend)
        if interpret is not None:
            return _lease_validate(store_versions, read_items, read_versions,
                                   write_locks, write_items,
                                   interpret=interpret)
        return _lease_validate_ref_jit(store_versions, read_items,
                                       read_versions, write_locks,
                                       write_items)


def _h2d_bytes(*arrays) -> int:
    """Bytes of the host (numpy) arrays among ``arrays``: what a dispatch
    uploads to the device."""
    return sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))
