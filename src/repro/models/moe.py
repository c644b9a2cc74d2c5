"""Mixture-of-Experts FFN: reference routing + sharded EP/TP execution.

Execution paths with identical math:

* :func:`moe_ref` — per-expert dense masking, exact top-k, no capacity drops.
  The oracle.
* :func:`moe_share` — one device: the router scores every expert, and only
  the (token, expert) pairs routed to the experts this device holds are
  computed, sorted by expert through grouped matmuls; no capacity drops.
* :func:`moe_sharded` — `shard_map` over the ``model`` mesh axis.  Expert
  weights are laid out in *chunks*: the model axis is split into
  ``ep × tp`` (ep = expert parallelism, tp = tensor parallelism inside an
  expert) so any expert count works on any axis size (mixtral: 8 experts ×
  f/2 halves on 16 devices; deepseek-v2: 10 experts/device).  Tokens are
  replicated across the model axis (as in TP dense FFN), so *dispatch is a
  local gather* on each expert owner and *combine is the single
  psum(model)* that TP needs anyway — no all_to_all, no cross-device
  dispatch tensor.  Capacity-factor token dropping bounds the gather size.

This dispatch-free formulation is the "migrate work to the state owner"
choice of the paper's cost model applied inside one step: tokens (work)
visit the expert shard (state owner) by *being already there* (replication
over the model axis), while the alternative — all_gathering expert weights
to the tokens — is the "migrate state" branch.  `repro.dist.locality`
prices both with the paper's SC cost formula.

A third path, :func:`moe_sharded_a2a`, shards the tokens over the model
axis too and moves only the *routed* activations with a pair of
``all_to_all`` collectives — the literal token-dispatch plan the pricing
model calls ``dispatch_s``.  :func:`moe_apply` consults
:func:`repro.dist.locality.price_moe_dispatch` per
``(tokens_per_device, ep_degree)`` cell (verdicts cached) and picks a2a
vs. the replicated-token path instead of always replicating.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.obs import trace as obs_trace
from .common import ModelConfig, chunk_plan, mlp_apply


# ---------------------------------------------------------------------------
# Routing
# ---------------------------------------------------------------------------

def router_topk(
    logits: jax.Array,            # [T, E] float32
    top_k: int,
    norm_topk: bool,
    router_scale: float,
    n_group: int = 1,
    topk_group: int = 1,
    scoring: str = "softmax",
    bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Return (gate values [T, K] float32, expert ids [T, K] int32).

    ``scoring`` "softmax" takes the top-k of the softmax and gates by it;
    "sigmoid" takes the top-k of ``sigmoid(logits) + bias`` (the
    correction bias steers the choice only) and gates by the chosen
    experts' ``sigmoid(logits)``.

    With ``n_group > 1`` the routing is group-limited (DeepSeek-V2's
    ``group_limited_greedy``): the ``E`` scores form ``n_group`` contiguous
    groups, each group scores its best expert, the ``topk_group`` best
    groups are kept (lower index first on ties) and every other score is
    zeroed before the top-k.
    """
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        probs = scores if bias is None else scores + bias.astype(jnp.float32)
    elif scoring == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    if n_group > 1:
        t, e = probs.shape
        by_group = probs.reshape(t, n_group, e // n_group)
        _, keep = jax.lax.top_k(by_group.max(axis=-1), topk_group)
        kept = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        probs = jnp.where(kept[:, :, None], by_group, 0.0).reshape(t, e)
    vals, ids = jax.lax.top_k(probs, top_k)
    if scoring == "sigmoid":
        vals = jnp.take_along_axis(scores, ids, axis=-1)
    if norm_topk:
        vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    return vals * router_scale, ids.astype(jnp.int32)


def route(x: jax.Array, router: jax.Array, m,
          bias: Optional[jax.Array] = None) -> Tuple[jax.Array, jax.Array]:
    """The layer's routing of tokens ``x`` [T, d] over all ``n_experts``;
    ``bias`` the sigmoid router's ``e_score_correction_bias``."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    return router_topk(logits, m.top_k, norm_topk=m.renormalise,
                       router_scale=m.router_scale, n_group=m.n_group,
                       topk_group=m.topk_group, scoring=m.scoring, bias=bias)


def _route_layer(p: Dict[str, Any], xt: jax.Array, m):
    """:func:`route` with the layer's router (and its correction bias)."""
    if "e_score_correction_bias" in p:
        return route(xt, p["router"], m, p["e_score_correction_bias"])
    return route(xt, p["router"], m)


def _expert_ffn(xt, w, act: str, dot=jnp.matmul):
    """An expert's FFN on rows ``xt``: gated SwiGLU or non-gated relu²,
    each weight applied by ``dot(rows, weight)``."""
    if act == "swiglu":
        return dot(jax.nn.silu(dot(xt, w["w_gate"])) * dot(xt, w["w_up"]),
                   w["w_down"])
    if act == "relu2":
        return dot(jnp.square(jax.nn.relu(dot(xt, w["w_up"]))), w["w_down"])
    raise ValueError(f"unknown expert activation {act!r}")


def aux_load_balance_loss(logits: jax.Array, ids: jax.Array, n_experts: int) -> jax.Array:
    """Switch-style load-balance auxiliary loss (mean prob × token fraction)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs, axis=0)                                    # [E]
    onehot = jax.nn.one_hot(ids[..., 0], n_experts, dtype=jnp.float32)
    ce = jnp.mean(onehot, axis=0)
    return n_experts * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Reference path (oracle; exact, no drops)
# ---------------------------------------------------------------------------

def moe_ref(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """[B, S, d] -> [B, S, d]; loops over the held experts with dense masks.

    Expert weights are in the chunked layout with n_chunks=1:
    ``experts.w_gate [1, E_held, d, f]`` etc.
    """
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    gates, ids = _route_layer(p, xt, m)
    out = jnp.zeros_like(xt, dtype=jnp.float32)
    we = p["experts"]
    first, n_held = m.held
    for e in range(n_held):
        w = jnp.sum(jnp.where(ids == first + e, gates, 0.0), axis=-1)  # [T]
        o = _expert_ffn(xt, {k: v[0, e] for k, v in we.items()}, m.expert_act)
        out = out + o.astype(jnp.float32) * w[:, None]
    y = out.astype(x.dtype)
    if m.n_shared:
        y = y + mlp_apply(p["shared"], xt, m.expert_act)
    return y.reshape(b, s, d)


# ---------------------------------------------------------------------------
# One device's expert share: only the routed (token, held expert) pairs
# ---------------------------------------------------------------------------

# grouped-matmul tiles (rows, contraction, output) of the TPU kernel: each
# group visit computes whole row tiles, so small row tiles keep the work
# near the routed pairs when each expert sees a few tokens; on a v5e, one
# DeepSeek-V2 expert share (256 tokens, 20 experts) took 1.74 ms against
# 2.04 with (128, 512, 512) tiles and 3.37 through lax.ragged_dot
GMM_TILING = (128, 2560, 768)


def _grouped(lhs, rhs, sizes, interpret: Optional[bool]):
    """``lhs`` rows grouped by expert (``sizes`` rows each, in order) times
    each group's ``rhs``; rows past ``sum(sizes)`` are left undefined.
    ``rhs`` is one layer's experts ``[E, k, n]``, or a scanned body's stack
    of them ``[G*E, k, n]`` with ``sizes`` of length ``G*E`` that are zero
    outside the layer's block: empty groups cost no work and the kernel
    reads the layer's experts where they lie in the stack.
    ``interpret`` None takes ``lax.ragged_dot``; else the megablox grouped
    matmul kernel, compiled (False) or interpreted (True)."""
    if interpret is None:
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    tm = min(GMM_TILING[0], -(-m // 8) * 8)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tiling = (tm, min(GMM_TILING[1], k), min(GMM_TILING[2], n))
    # the chip lays a weight whose last dim is not lane-aligned (128) but
    # whose contraction dim is with the contraction dim minor: the kernel
    # reads it through that layout, transposed, or XLA copies it whole
    flip = n % 128 != 0 and k % 128 == 0
    out = gmm(lhs, jnp.swapaxes(rhs, 1, 2) if flip else rhs, sizes,
              preferred_element_type=lhs.dtype, tiling=tiling,
              transpose_rhs=flip, interpret=interpret)
    return out[:m]


def moe_share(p: Dict[str, Any], x: jax.Array, cfg: ModelConfig,
              interpret: Optional[bool] = None,
              layer: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """[B, S, d] -> ([B, S, d], stats): the routed experts this device
    holds (``cfg.moe.held``) and the shared experts, with no capacity.

    The router scores all ``n_experts``; the (token, choice) pairs routed
    to a held expert are sorted by expert and run through one grouped
    matmul per weight, so the work grows with those pairs; the other
    pairs contribute nothing.  ``stats``: ``moe_pairs`` (pairs served) and
    ``moe_experts`` (held experts with at least one pair), int32.

    ``p["experts"]`` holds this layer's experts ``[1, E_held, ...]``; with
    ``layer``, a scanned body's stack of every layer's ``[G, 1, E_held,
    ...]``, of which the grouped matmuls read block ``layer`` in place
    (the group sizes are those of ``G*E_held`` experts, zero outside it).
    """
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    first, n_held = m.held
    with jax.named_scope("repro.moe.route"):
        gates, ids = _route_layer(p, xt, m)
        local = ids.reshape(-1) - first                     # [T*K]
        held = (local >= 0) & (local < n_held)
        key = jnp.where(held, local, n_held)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
        served = jnp.sum(sizes)
        xs = jnp.take(xt, order // m.top_k, axis=0)         # [T*K, d]
    with jax.named_scope("repro.moe.experts"):
        we = jax.tree.map(lambda w: w.reshape(-1, *w.shape[-2:]),
                          p["experts"])
        gs = sizes
        if layer is not None:
            gs = jax.lax.dynamic_update_slice(
                jnp.zeros(we["w_down"].shape[:1], jnp.int32), sizes,
                (layer * n_held,))
        o = _expert_ffn(xs, we, m.expert_act,
                        lambda a, w: _grouped(a, w, gs, interpret))
        live = jnp.arange(o.shape[0]) < served
        g = jnp.where(held, gates.reshape(-1), 0.0)[order]
        o = jnp.where(live[:, None], o.astype(jnp.float32) * g[:, None], 0.0)
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        y = o[back].reshape(-1, m.top_k, d).sum(axis=1).astype(x.dtype)
    if m.n_shared:
        with jax.named_scope("repro.moe.shared"):
            y = y + mlp_apply(p["shared"], xt, m.expert_act)
    stats = {"moe_pairs": served,
             "moe_experts": jnp.sum(sizes > 0).astype(jnp.int32)}
    return y.reshape(b, s, d), stats


# ---------------------------------------------------------------------------
# Chunked expert weight layout (EP × TP over the model axis)
# ---------------------------------------------------------------------------

def to_chunked(w_gate, w_up, w_down, model_size: int):
    """[E, d, f] expert weights -> chunked [n_chunks, n_e, d, f_c] layout.

    Chunk m holds experts ``(m // tp) * n_e + [0, n_e)`` restricted to
    f-slice ``m % tp``.
    """
    e, d, f = w_gate.shape
    ep, tp, n_e, nc = chunk_plan(e, model_size)
    f_c = f // tp

    def slice_chunks(w, transpose=False):
        # w [E, d, f] -> [ep, n_e, d, tp, f_c] -> [ep, tp, n_e, d, f_c] -> [nc, ...]
        wr = w.reshape(ep, n_e, d, tp, f_c) if not transpose else None
        if transpose:  # w_down [E, f, d] -> slice along f
            wr = w.reshape(ep, n_e, tp, f_c, d)
            wr = jnp.moveaxis(wr, 2, 1)                     # [ep, tp, n_e, f_c, d]
            return wr.reshape(nc, n_e, f_c, d)
        wr = jnp.moveaxis(wr, 3, 1)                         # [ep, tp, n_e, d, f_c]
        return wr.reshape(nc, n_e, d, f_c)

    return slice_chunks(w_gate), slice_chunks(w_up), slice_chunks(w_down, transpose=True)


def chunked_shapes(cfg: ModelConfig, model_size: int) -> Dict[str, Tuple[int, ...]]:
    m = cfg.moe
    ep, tp, n_e, nc = chunk_plan(m.n_experts, model_size)
    f_c = m.d_expert // tp
    return {
        "w_gate": (nc, n_e, cfg.d_model, f_c),
        "w_up": (nc, n_e, cfg.d_model, f_c),
        "w_down": (nc, n_e, f_c, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# Sharded path
# ---------------------------------------------------------------------------

def _moe_local(
    x_loc: jax.Array,             # [T_loc, d]  (this device's tokens)
    router: jax.Array,            # [d, E]
    wg: jax.Array, wu: jax.Array, wd: jax.Array,   # [n_e, d, f_c] / [n_e, f_c, d]
    *,
    cfg: ModelConfig,
    model_axis: str,
    model_size: int,
    capacity: int,
) -> jax.Array:
    """Per-device body: route, gather my experts' tokens, FFN, scatter, psum."""
    m = cfg.moe
    ep, tp, n_e, _ = chunk_plan(m.n_experts, model_size)
    midx = jax.lax.axis_index(model_axis)
    ep_rank = midx // tp

    t_loc, d = x_loc.shape
    acc_dt = x_loc.dtype   # accumulate in compute dtype: keeps the backward
    # cotangent chain (and its psum over the model axis) out of fp32
    gates, ids = route(x_loc, router, m)

    # slot assignment: for each (token, k) choice, its position among all
    # choices of the same expert (arrival order), for capacity dropping
    flat_ids = ids.reshape(-1)                               # [T*K]
    flat_gates = gates.reshape(-1)
    onehot_pos = jax.nn.one_hot(flat_ids, m.n_experts, dtype=jnp.int32)
    slot = jnp.cumsum(onehot_pos, axis=0) - onehot_pos       # [T*K, E] slot per expert
    my_first = ep_rank * n_e

    y = jnp.zeros((t_loc, d), acc_dt)
    token_of = jnp.arange(t_loc * m.top_k, dtype=jnp.int32) // m.top_k
    for le in range(n_e):
        gid = my_first + le
        sel = flat_ids == gid
        slot_e = slot[:, gid]
        keep = sel & (slot_e < capacity)
        # scatter (token, gate) into the capacity buffer
        dest = jnp.where(keep, slot_e, capacity)             # drops -> overflow row
        tok_idx = jnp.full((capacity + 1,), t_loc, jnp.int32).at[dest].set(
            jnp.where(keep, token_of, t_loc), mode="drop")[:capacity]
        gate_buf = jnp.zeros((capacity + 1,), jnp.float32).at[dest].set(
            jnp.where(keep, flat_gates, 0.0), mode="drop")[:capacity]
        xg = jnp.where(
            (tok_idx < t_loc)[:, None],
            jnp.take(x_loc, jnp.minimum(tok_idx, t_loc - 1), axis=0),
            0.0,
        )                                                     # [C, d]
        h = jax.nn.silu(xg @ wg[le]) * (xg @ wu[le])          # [C, f_c]
        o = (h @ wd[le]) * gate_buf[:, None].astype(acc_dt)
        y = y.at[jnp.minimum(tok_idx, t_loc - 1)].add(
            jnp.where((tok_idx < t_loc)[:, None], o, jnp.zeros((), acc_dt)))
    # one reduction: sums (a) expert contributions across ep ranks and
    # (b) partial f-slices across tp ranks.  Reduce in compute dtype — a
    # fp32 psum here doubles the layer's wire bytes for no accuracy gain
    # (each token sums at most top_k + tp partials).
    return jax.lax.psum(y, model_axis)


def moe_sharded(
    p: Dict[str, Any],
    x: jax.Array,                 # [B, S, d]
    cfg: ModelConfig,
    mesh: jax.sharding.Mesh,
    *,
    batch_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
    capacity_factor: float = 1.25,
) -> jax.Array:
    """EP/TP MoE over ``mesh``; expert weights must be in chunked layout."""
    from jax.experimental.shard_map import shard_map

    m = cfg.moe
    b, s, d = x.shape
    # shard the batch dim over as many batch axes as divide it; batch==1
    # (long-context decode) degrades to replication over the batch axes
    # (each data row computes identical routing; experts stay model-sharded)
    baxes: Tuple[str, ...] = tuple(batch_axes)
    while baxes:
        n = 1
        for a in baxes:
            n *= int(mesh.shape[a])
        if b % n == 0:
            break
        baxes = baxes[1:]
    n_batch_shards = 1
    for a in baxes:
        n_batch_shards *= int(mesh.shape[a])
    t_loc = (b // n_batch_shards) * s
    model_size = mesh.shape[model_axis]
    capacity = int(max(1, t_loc * m.top_k * capacity_factor) // m.n_experts)
    capacity = max(capacity, 8)

    def body(x_blk, router, wg, wu, wd):
        bl, sl, dl = x_blk.shape
        y = _moe_local(
            x_blk.reshape(-1, dl), router, wg[0], wu[0], wd[0],
            cfg=cfg, model_axis=model_axis, model_size=int(model_size),
            capacity=capacity,
        )
        return y.reshape(bl, sl, dl).astype(x_blk.dtype)

    bspec = P(baxes if baxes else None, None, None)
    out = shard_map(
        body, mesh=mesh,
        in_specs=(
            bspec,
            P(None, None),
            P(model_axis, None, None, None),
            P(model_axis, None, None, None),
            P(model_axis, None, None, None),
        ),
        out_specs=bspec,
        check_rep=False,
    )(x, p["router"], p["experts"]["w_gate"], p["experts"]["w_up"],
      p["experts"]["w_down"])
    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, "swiglu")
    return out


# ---------------------------------------------------------------------------
# Token all-to-all path (the priced "dispatch" plan)
# ---------------------------------------------------------------------------

def _moe_local_a2a(
    x_loc: jax.Array,             # [T_loc, d] (this device's token shard)
    router: jax.Array,            # [d, E]
    wg: jax.Array, wu: jax.Array, wd: jax.Array,   # [n_e, d, f_c] / [n_e, f_c, d]
    *,
    cfg: ModelConfig,
    axes: Tuple[str, ...],        # token-shard axes, major to minor
    axis_sizes: Tuple[int, ...],
    model_axis: str,
    model_size: int,
    capacity: int,
    t_valid: int,                 # global tokens that are real (rest is pad)
) -> jax.Array:
    """Per-device body: route my tokens, a2a them to their expert *chunks*,
    partial FFN there, a2a the partial activations back, psum-combine.

    tp-aware: model rank ``m`` owns chunk ``m`` of the EP×TP layout —
    experts ``(m // tp) * n_e + [0, n_e)`` restricted to f-slice ``m % tp``.
    A routed token is dispatched to all ``tp`` ranks of its expert's chunk
    group; each computes the f-slice partial ``(silu(x·wg)·(x·wu))·wd``
    (full d, partial sum over f), and the return a2a lands the ``tp``
    partials back in the sender's per-group slot where
    :func:`repro.kernels.ops.moe_combine` sums them — the partial-
    activation psum of the combine leg, materialized as a block-sum so the
    two ``all_to_all`` legs stay the layer's entire wire traffic (priced
    by ``price_moe_dispatch``'s ``tp_degree`` term).

    Each destination block is laid out ``[n_e, cap_e]`` — sub-blocked by
    the chunk's local expert — so the receiver selects each expert's rows
    with a reshape instead of a masked pass over the whole buffer, and no
    expert-id metadata crosses the wire.  ``capacity = n_e * cap_e`` bounds
    the routed rows per (source, expert) pair at ``cap_e``; token rows at
    global index ≥ ``t_valid`` are ragged-batch padding and are never
    dispatched.
    """
    from repro.kernels import ops as kops

    m = cfg.moe
    ep, tp, n_e, _ = chunk_plan(m.n_experts, model_size)
    cap_e = capacity // n_e                               # per (src, expert)
    t_loc, d = x_loc.shape
    acc_dt = x_loc.dtype
    gates, ids = route(x_loc, router, m)

    flat_ids = ids.reshape(-1)                            # [T*K]
    flat_gates = gates.reshape(-1)
    grp = flat_ids // n_e                                 # owning ep group
    le = flat_ids % n_e                                   # its local expert
    token_of = jnp.arange(t_loc * m.top_k, dtype=jnp.int32) // m.top_k
    # ragged batches pad the flattened token axis up to the shard multiple;
    # the pad rows live at the tail of the global order — mask them out of
    # dispatch so they neither consume capacity nor pollute the psum
    shard = jnp.zeros((), jnp.int32)
    for a, n in zip(axes, axis_sizes):
        shard = shard * n + jax.lax.axis_index(a)
    valid = (shard * t_loc + token_of) < t_valid
    # per-expert arrival slot (for capacity bounding), exactly the
    # replicated path's slots; all tp copies of a token share one slot
    onehot = jax.nn.one_hot(flat_ids, m.n_experts, dtype=jnp.int32) \
        * valid[:, None]
    slot = jnp.cumsum(onehot, axis=0) - onehot            # [T*K, E]
    slot_d = jnp.sum(slot * onehot, axis=1)
    keep = (slot_d < cap_e) & valid

    nbuf = model_size * capacity                          # = ep * tp * capacity
    send_x = jnp.zeros((nbuf + 1, d), x_loc.dtype)
    x_routed = jnp.take(x_loc, token_of, axis=0)
    sub = le * cap_e + slot_d                             # expert sub-block
    for j in range(tp):                                   # tp dest copies
        row = jnp.where(keep, (grp * tp + j) * capacity + sub, nbuf)
        send_x = send_x.at[row].set(x_routed, mode="drop")
    send_x = send_x[:nbuf]
    # sender-side combine metadata, per (group, expert-slot) — never
    # crosses the wire
    crow = jnp.where(keep, grp * capacity + sub, ep * capacity)
    tok_slot = jnp.full((ep * capacity + 1,), t_loc, jnp.int32).at[crow].set(
        jnp.where(keep, token_of, t_loc), mode="drop")[:ep * capacity]
    gate_slot = jnp.zeros((ep * capacity + 1,), jnp.float32).at[crow].set(
        jnp.where(keep, flat_gates, 0.0), mode="drop")[:ep * capacity]

    recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=True)
    # each source block arrives sub-blocked [n_e, cap_e]: slicing an
    # expert's rows is a transpose of the reshape, not a masked pass —
    # every recv row runs exactly one expert's FFN, like the dense path
    recv_e = recv_x.reshape(model_size, n_e, cap_e, d)
    outs = []
    for e in range(n_e):
        xe = recv_e[:, e].reshape(model_size * cap_e, d)
        h = jax.nn.silu(xe @ wg[e]) * (xe @ wu[e])           # [.., f_c]
        outs.append((h @ wd[e]).astype(acc_dt)
                    .reshape(model_size, cap_e, d))
    out = jnp.stack(outs, axis=1).reshape(nbuf, d)
    # the return a2a lands each chunk's partial output back in its sender's
    # (group, tp, expert-slot) cell; moe_combine sums the tp partials per
    # slot (the f-slice psum) and scatters the gated rows to their tokens
    back = jax.lax.all_to_all(out, model_axis, 0, 0, tiled=True)
    return kops.moe_combine(back, tok_slot, gate_slot, tp=tp,
                            capacity=capacity, t_out=t_loc)


def _a2a_plan(cfg: ModelConfig, t_total: int, mesh, batch_axes, model_axis):
    """(token_shards, ep, tp, t_pad) for the a2a layout.

    Any ``(n_experts, model_size)`` pair the chunk layout accepts is
    feasible: tp > 1 dispatches to chunks with a partial psum on the
    combine leg, and ragged token counts pad the flattened token axis up
    to ``t_pad`` (the next shard multiple) with masked rows rather than
    forfeiting the a2a plan to the dense fallback.
    """
    model_size = int(mesh.shape[model_axis])
    ep, tp, _, _ = chunk_plan(cfg.moe.n_experts, model_size)
    shards = model_size
    for a in batch_axes:
        shards *= int(mesh.shape[a])
    t_pad = -(-t_total // shards) * shards
    return shards, ep, tp, t_pad


def moe_sharded_a2a(
    p: Dict[str, Any],
    x: jax.Array,                 # [B, S, d]
    cfg: ModelConfig,
    mesh: jax.sharding.Mesh,
    *,
    batch_axes: Tuple[str, ...] = ("data",),
    model_axis: str = "model",
    capacity_factor: float = 1.25,
) -> jax.Array:
    """Token-dispatch MoE: tokens sharded over (batch × model) axes, routed
    activations moved by a2a pairs; expert chunks stay put (EP × TP)."""
    from jax.experimental.shard_map import shard_map

    m = cfg.moe
    b, s, d = x.shape
    shards, ep, tp, t_pad = _a2a_plan(cfg, b * s, mesh, batch_axes,
                                      model_axis)
    t_loc = t_pad // shards
    # per-(source, expert) slots, sub-blocked n_e per destination rank
    _, _, n_e, _ = chunk_plan(m.n_experts, int(mesh.shape[model_axis]))
    cap_e = max(8, -(-int(t_loc * m.top_k * capacity_factor) // m.n_experts))
    capacity = n_e * cap_e
    model_size = int(mesh.shape[model_axis])
    axes = (*tuple(batch_axes), model_axis)
    axis_sizes = tuple(int(mesh.shape[a]) for a in axes)

    def body(xt, router, wg, wu, wd):
        y = _moe_local_a2a(
            xt, router, wg[0], wu[0], wd[0], cfg=cfg, axes=axes,
            axis_sizes=axis_sizes, model_axis=model_axis,
            model_size=model_size, capacity=capacity, t_valid=b * s)
        return y.astype(xt.dtype)

    xt = x.reshape(b * s, d)
    if t_pad != b * s:
        xt = jnp.pad(xt, ((0, t_pad - b * s), (0, 0)))
    spec = P(axes, None)
    out = shard_map(
        body, mesh=mesh,
        in_specs=(
            spec,
            P(None, None),
            P(model_axis, None, None, None),
            P(model_axis, None, None, None),
            P(model_axis, None, None, None),
        ),
        out_specs=spec,
        check_rep=False,
    )(xt, p["router"], p["experts"]["w_gate"],
      p["experts"]["w_up"], p["experts"]["w_down"])
    y = out[:b * s].reshape(b, s, d)
    if m.n_shared:
        y = y + mlp_apply(p["shared"], x, "swiglu")
    return y


# ---------------------------------------------------------------------------
# Dispatch autotuning: the DTD verdict, cached per cell
# ---------------------------------------------------------------------------

# (tokens_per_device, ep_degree, tp_degree, layer dims) -> prefer token
# a2a.  One pricing call per cell ever: decode/prefill shapes recur, so the
# verdict lookup is a dict hit on the trace path.
_DISPATCH_CACHE: Dict[Tuple[int, ...], bool] = {}


def dispatch_verdict(cfg: ModelConfig, tokens_per_device: int,
                     ep_degree: int, tp_degree: int = 1) -> bool:
    """Cached ``price_moe_dispatch`` verdict for one (T/device, ep, tp)
    cell — tp > 1 prices the chunked layout's partial-activation psum."""
    m = cfg.moe
    key = (tokens_per_device, ep_degree, tp_degree, cfg.d_model, m.top_k,
           m.n_experts, m.d_expert)
    v = _DISPATCH_CACHE.get(key)
    if v is None:
        from repro.dist.locality import price_moe_dispatch

        v = price_moe_dispatch(
            tokens_per_device, cfg.d_model, m.top_k, m.n_experts,
            m.d_expert, ep_degree, tp_degree=tp_degree).prefer_dispatch
        _DISPATCH_CACHE[key] = v
    return v


def no_stats() -> Dict[str, jax.Array]:
    """The stats of a step that served no routed pair."""
    return {"moe_pairs": jnp.zeros((), jnp.int32),
            "moe_experts": jnp.zeros((), jnp.int32)}


def moe_apply(
    p: Dict[str, Any],
    x: jax.Array,
    cfg: ModelConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    *,
    dispatch: str = "auto",
    interpret: Optional[bool] = None,
    stats: bool = False,
    layer: Optional[jax.Array] = None,
    **kw,
):
    """MoE layer entry point with autotuned dispatch.

    One device (no mesh, or a unit model axis) runs :func:`moe_share`, with
    ``interpret`` its grouped-matmul choice.  On a model axis,
    ``dispatch``: ``"auto"`` consults the cached
    :func:`repro.dist.locality.price_moe_dispatch` verdict for this
    (tokens_per_device, ep_degree, tp_degree) cell — token a2a when the
    routed activations are lighter on the wire than replication, the
    replicated-token path otherwise; ``"a2a"`` / ``"replicate"`` force a
    path.  The a2a path covers every chunk layout (tp > 1 dispatches to
    expert chunks with a partial psum combine) and every token count
    (ragged batches are padded and masked), so the forced path is taken
    verbatim.  With ``stats`` returns ``(y, stats)``, ``stats`` as
    :func:`moe_share` counts them (zero on a model axis, which counts
    nothing).  With ``layer``, ``p["experts"]`` is a scanned body's stack
    (see :func:`moe_share`); a model axis takes the layer's slice of it.
    """
    # the dispatch-verdict span fires at jit-trace time — one event per
    # compiled (shape, path) cell, stamped at the recorder's last set_time;
    # this is exactly when the verdict is decided, so the trace records
    # which path each compilation cell took (the module-level recorder is
    # used because the layer has no engine/cluster to thread one through)
    tr = obs_trace.TRACE
    if mesh is None or mesh.shape.get("model", 1) == 1:
        if tr.enabled:
            tr.span("moe-dispatch", "moe", tr.time, 0.0, path="share",
                    tokens=int(x.shape[0] * x.shape[1]))
        y, st = moe_share(p, x, cfg, interpret, layer)
        return (y, st) if stats else y
    if layer is not None:
        p = dict(p, experts=jax.tree.map(lambda w: w[layer], p["experts"]))
    if dispatch not in ("auto", "a2a", "replicate"):
        raise ValueError(f"unknown moe dispatch {dispatch!r}")
    use_a2a = False
    ep = tp = 0
    if dispatch != "replicate":
        b, s, _ = x.shape
        batch_axes = tuple(kw.get("batch_axes", ("data",)))
        model_axis = kw.get("model_axis", "model")
        shards, ep, tp, t_pad = _a2a_plan(cfg, b * s, mesh, batch_axes,
                                          model_axis)
        use_a2a = (
            dispatch == "a2a"
            or dispatch_verdict(cfg, t_pad // shards, ep, tp))
    if tr.enabled:
        tr.span("moe-dispatch", "moe", tr.time, 0.0,
                path="a2a" if use_a2a else "replicate",
                tokens=int(x.shape[0] * x.shape[1]), ep=ep, tp=tp)
    if use_a2a:
        y = moe_sharded_a2a(p, x, cfg, mesh, **kw)
    else:
        y = moe_sharded(p, x, cfg, mesh, **kw)
    return (y, no_stats()) if stats else y
