"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# --- flash attention oracle --------------------------------------------------

def sdpa_ref(
    q, k, v, *, q_positions, kv_positions, causal=True, sliding_window=None,
    logit_softcap=0.0, scale=None,
):
    from repro.models.attention import _sdpa_ref, attn_mask

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    mask = attn_mask(q_positions, kv_positions, causal, sliding_window)
    return _sdpa_ref(q, k, v, mask, scale, logit_softcap)


# --- SSD oracle ---------------------------------------------------------------

def ssd_ref(
    x, dt, a, b_mat, c_mat, *, chunk=256, h0=None,
) -> Tuple[jax.Array, jax.Array]:
    from repro.models.ssm import ssd_chunked

    return ssd_chunked(x, dt, a, b_mat, c_mat, chunk, h0=h0,
                       return_final_state=True)


# --- lease-settle oracle -------------------------------------------------------

def lease_settle_ref(
    head_req: jax.Array,      # [C] int32, -1 when the queue is empty
    head_proc: jax.Array,     # [C] int32
    head_active: jax.Array,   # [C] int32
    qlen: jax.Array,          # [C] int32
    fresh_blocked: jax.Array,  # [C] bool: head newly blocked this instant
    wait_req: jax.Array,      # [B, K] int32, -1 padded (waiting groups)
    wait_cc: jax.Array,       # [B, K] int32, -1 padded
    proc,                     # scalar int32: the settling replica
):
    """One lease-settle over a replica's packed conflict-queue heads.

    Algorithm 1's three per-instant queries as gather/compare math:

    * ``owner[c]``   — head ownership L(i, x) (-1: unowned);
    * ``free[c]``    — the blocked-and-drained rule: a head that is ours,
      was *newly* blocked at this instant (``fresh_blocked``), and has no
      active transactions must be freed now (already-blocked dormant heads
      were freed when they first blocked — re-freeing them would dequeue
      twice);
    * ``enabled[b]`` — ``isEnabled``: every LOR of waiting group ``b``
      heads its queue (matched by req_id, which is unique per queue).
    """
    c = head_req.shape[0]
    occupied = qlen > 0
    owner = jnp.where(occupied, head_proc, -1).astype(jnp.int32)
    free = occupied & fresh_blocked & (head_proc == proc) & (head_active == 0)
    valid = wait_cc >= 0
    cc = jnp.clip(wait_cc, 0, c - 1)
    at_head = occupied[cc] & (head_req[cc] == wait_req)
    enabled = jnp.all(jnp.where(valid, at_head, True), axis=1)
    return owner, free, enabled


# --- MoE combine oracle --------------------------------------------------------

def moe_combine_ref(
    back: jax.Array,          # [ep * tp * capacity, d] returned partials
    tok_slot: jax.Array,      # [ep * capacity] int32, t_out when empty
    gate_slot: jax.Array,     # [ep * capacity] f32, 0 when empty
    *,
    tp: int,
    capacity: int,
    t_out: int,
) -> jax.Array:
    """Combine leg of the tp-aware MoE a2a: the partial-activation psum.

    Each expert-group slot came back as ``tp`` f-slice partials (one per
    chunk rank, contiguous blocks of ``capacity`` rows per rank); gate each
    partial, sum over the tp blocks, and scatter the rows to their owning
    token rows.  Gating *before* the sum mirrors the replicated path's
    ``(h @ wd) * gate`` → psum association (``repro.models.moe._moe_local``)
    so the two paths agree to the same float-order; at ``tp == 1`` this
    degenerates to the plain gated scatter of the whole-expert path.
    """
    d = back.shape[-1]
    gate = gate_slot.reshape(-1, 1, capacity, 1).astype(back.dtype)
    gated = (back.reshape(-1, tp, capacity, d) * gate).sum(axis=1)
    return jnp.zeros((t_out, d), back.dtype).at[tok_slot].add(
        gated.reshape(-1, d), mode="drop")


# --- lease-validate oracle -----------------------------------------------------

def lease_validate_ref(
    store_versions: jax.Array,   # [n_items] int32
    read_items: jax.Array,       # [B, R] int32, -1 padded
    read_versions: jax.Array,    # [B, R] int32
    write_locks: Optional[jax.Array] = None,   # [L] bool
    write_items: Optional[jax.Array] = None,   # [B, W] int32, -1 padded
) -> jax.Array:
    """TL2 certification: read versions unchanged AND write set unlocked.

    ``write_items`` index ``write_locks``, which need not be as long as the
    store: the certifier hands one lock bit per write entry."""
    n = store_versions.shape[0]
    valid = read_items >= 0
    cur = store_versions[jnp.clip(read_items, 0, n - 1)]
    ok = jnp.all(jnp.where(valid, cur == read_versions, True), axis=1)
    if write_locks is not None and write_items is not None:
        wvalid = write_items >= 0
        nl = write_locks.shape[0]
        locked = write_locks[jnp.clip(write_items, 0, nl - 1)]
        ok &= jnp.all(jnp.where(wvalid, ~locked, True), axis=1)
    return ok
