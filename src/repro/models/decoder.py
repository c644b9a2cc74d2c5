"""Composable decoder/encoder stack: train forward, prefill and decode.

The stack is prefix (unrolled) + body (``lax.scan`` over stacked layer
groups) + suffix (unrolled), per :func:`repro.models.common.layer_plan`.
Every apply is a pure function of ``(params, batch)``; distribution comes
from a :class:`RunCtx` carrying the mesh and axis names (None = single
device, used by smoke tests).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .attention import gqa_attention, init_attn_cache, mla_attention
from .common import (LayerKind, LayerPlan, ModelConfig, layer_plan, mlp_apply,
                     param_shapes, rms_norm)
from .moe import moe_apply, no_stats
from .ssm import init_ssm_cache, mamba2_block


@dataclass(frozen=True)
class RunCtx:
    """Execution context: mesh, axis names, kernel/remat policy."""

    mesh: Optional[jax.sharding.Mesh] = None
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    use_kernel: str = "auto"          # "auto" | "pallas" | "ref"
    remat: str = "none"               # "none" | "full" | "dots"
    capacity_factor: float = 1.25
    seq_axis: Optional[str] = None    # shard long KV caches over this axis

    @property
    def model_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(self.mesh.shape[self.model_axis])

    @property
    def seq_size(self) -> int:
        """Devices along the seq axis (1 when the mesh exposes none)."""
        if self.mesh is None or self.seq_axis is None:
            return 1
        return int(dict(self.mesh.shape).get(self.seq_axis, 1))

    def seq_spec(self, seqlen: int) -> Optional[str]:
        """Seq-axis name if the mesh divides ``seqlen``, else None.

        The divisibility guard mirrors :mod:`repro.dist.sharding`: an
        indivisible (or unit) sequence dim is replicated, so decode steps
        (S=1) and smoke meshes share the sharded code path.
        """
        s = self.seq_size
        return self.seq_axis if s > 1 and seqlen % s == 0 else None

    def shard_act(self, x: jax.Array, *spec) -> jax.Array:
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh, P(*spec))
        )


# ---------------------------------------------------------------------------
# One block
# ---------------------------------------------------------------------------

def block_apply(
    cfg: ModelConfig,
    ctx: RunCtx,
    kind: LayerKind,
    p: Dict[str, Any],
    shared_attn_p: Optional[Dict[str, Any]],
    x: jax.Array,
    positions: jax.Array,
    *,
    cache: Optional[Dict[str, Any]] = None,
    cache_index: Optional[jax.Array] = None,
    return_cache: bool = False,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any], Dict[str, jax.Array]]:
    """One layer; returns ``(x, new_cache, stats)``, ``stats`` the MoE
    layer's counts (see :func:`repro.models.moe.moe_share`), else empty.

    With ``layer``, ``cache`` holds the scanned body's layer-stacked
    buffers and this layer writes its entry of them: its attention rows at
    (layer, slot, position), its whole mamba state at ``layer``; an MoE
    layer's ``p["moe"]["experts"]`` is then the body's whole stack of them
    (see :func:`repro.models.moe.moe_share`).
    """
    eps, gm = cfg.norm_eps, cfg.gemma_norm
    # params may be stored fp32 (training master copies); compute in cfg.dtype
    cdt = cfg.compute_dtype()
    cast = lambda t: jax.tree.map(
        lambda a: a.astype(cdt) if jnp.issubdtype(a.dtype, jnp.floating) else a, t
    )
    p = cast(p)
    if shared_attn_p is not None:
        shared_attn_p = cast(shared_attn_p)
    new_cache: Dict[str, Any] = {}
    attn_kw = dict(
        cache=None if cache is None else cache.get("attn"),
        cache_index=cache_index,
        return_cache=return_cache,
        use_kernel=ctx.use_kernel,
        ctx=ctx,
        layer=layer,
    )

    if kind.mixer in ("attn", "attn_local"):
        h = rms_norm(x, p["ln_attn"], eps, gemma=gm)
        fn = mla_attention if cfg.mla is not None else gqa_attention
        a, c = fn(p["attn"], h, cfg, positions,
                  is_global=(kind.mixer == "attn"), **attn_kw)
        if gm and "ln_post_attn" in p:
            a = rms_norm(a, p["ln_post_attn"], eps, gemma=gm)
        x = x + a
        if return_cache:
            new_cache["attn"] = c
    elif kind.mixer == "shared_attn":
        h = rms_norm(x, shared_attn_p["ln_attn"], eps, gemma=gm)
        a, c = gqa_attention(shared_attn_p["attn"], h, cfg, positions,
                             is_global=True, **attn_kw)
        x = x + a
        if return_cache:
            new_cache["attn"] = c
    elif kind.mixer == "mamba":
        h = rms_norm(x, p["ln_mix"], eps, gemma=gm)
        y, c = mamba2_block(
            p["mamba"], h, cfg,
            cache=None if cache is None else cache["mamba"],
            cache_index=cache_index,
            return_cache=return_cache,
            use_kernel=ctx.use_kernel,
            layer=layer,
        )
        x = x + y
        if return_cache:
            new_cache["mamba"] = c
    elif kind.mixer != "none":
        raise ValueError(kind.mixer)

    stats: Dict[str, jax.Array] = {}
    if kind.ffn == "dense":
        h = rms_norm(x, p["ln_mlp"], eps, gemma=gm)
        f = mlp_apply(p["mlp"], h, cfg.mlp_act)
        if gm and "ln_post_mlp" in p:
            f = rms_norm(f, p["ln_post_mlp"], eps, gemma=gm)
        x = x + f
    elif kind.ffn == "moe":
        from repro.kernels import kernel_mode

        h = rms_norm(x, p["ln_mlp"], eps, gemma=gm)
        f, stats = moe_apply(
            p["moe"], h, cfg, mesh=ctx.mesh,
            interpret=kernel_mode(ctx.use_kernel), stats=True, layer=layer,
            batch_axes=ctx.batch_axes, model_axis=ctx.model_axis,
            capacity_factor=ctx.capacity_factor,
        )
        x = x + f
    # residual boundary: batch over the data axes and, for multi-token
    # passes on a seq-bearing mesh, sequence over the seq axis (long-context
    # prefill work is then partitioned like its KV cache)
    x = ctx.shard_act(x, ctx.batch_axes, ctx.seq_spec(x.shape[1]), None)
    return x, new_cache, stats


def _zero_stats(cfg: ModelConfig) -> Dict[str, jax.Array]:
    """What the stack's layers count, before the first: the MoE counts for
    a model with experts, nothing otherwise."""
    return no_stats() if cfg.moe is not None else {}


def _add_stats(a: Dict[str, jax.Array], b: Dict[str, jax.Array]):
    return {k: a[k] + b[k] for k in b} if b else a


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------

def _remat_wrap(fn, remat: str):
    if remat == "none":
        return fn
    if remat == "full":
        return jax.checkpoint(fn)
    if remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(remat)


def _unrolled_names(params_sub: Dict[str, Any]) -> list:
    return sorted(params_sub, key=lambda s: int(s.removeprefix("layer")))


def _lift_experts(blocks: Dict[str, Any]):
    """``(blocks without their MoE experts, {position: stacked experts})``:
    a slice of the stack cannot feed the grouped-matmul kernel in place, so
    the body takes the experts whole and the scan slices the rest."""
    rest, experts = {}, {}
    for name, bp in blocks.items():
        if "moe" in bp:
            experts[name] = bp["moe"]["experts"]
            bp = dict(bp, moe={k: v for k, v in bp["moe"].items()
                               if k != "experts"})
        rest[name] = bp
    return rest, experts


def stack_apply(
    cfg: ModelConfig,
    ctx: RunCtx,
    params: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    *,
    caches: Optional[Dict[str, Any]] = None,
    cache_index: Optional[jax.Array] = None,
    return_cache: bool = False,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], Dict[str, jax.Array]]:
    """The layers over ``x``: ``(x, new caches or None, stats)``, ``stats``
    the MoE layers' counts summed (empty for a model without experts).

    Given ``caches`` (the decode step), the body's layer-stacked caches
    ride the scan's carry and each layer writes only its new rows into
    them, so a donated cache is updated in place, and its MoE layers'
    grouped matmuls read their experts in place in the stacked weights,
    which stay out of the scanned slices; without, the scan stacks each
    layer's cache as its output when ``return_cache``.
    """
    plan = layer_plan(cfg)
    kinds = plan.kinds
    shared_p = params.get("shared_attn")
    new_caches: Dict[str, Any] = {"prefix": [], "body": None, "suffix": []}
    stats = _zero_stats(cfg)
    return_cache = return_cache or caches is not None

    def one(kind, p, xx, cc, layer=None):
        return block_apply(
            cfg, ctx, kind, p, shared_p, xx, positions,
            cache=cc, cache_index=cache_index, return_cache=return_cache,
            layer=layer,
        )

    # --- prefix (unrolled) ---------------------------------------------------
    if plan.prefix:
        for i, name in enumerate(_unrolled_names(params["prefix"])):
            cc = caches["prefix"][i] if caches is not None else None
            x, nc, st = one(kinds[i], params["prefix"][name], x, cc)
            stats = _add_stats(stats, st)
            new_caches["prefix"].append(nc)

    # --- body (scanned over groups) -------------------------------------------
    if plan.n_groups:
        blocks, experts = params["blocks"], {}
        if caches is not None:
            blocks, experts = _lift_experts(blocks)

        def group_body(carry, scanned):
            xx, acc, gc = carry
            gp, layer = scanned
            ncs = []
            for j in range(plan.period):
                cc = None if gc is None else gc[j]
                pj = gp[f"pos{j}"]
                if f"pos{j}" in experts:
                    pj = dict(pj, moe=dict(pj["moe"],
                                           experts=experts[f"pos{j}"]))
                xx, nc, st = one(kinds[plan.prefix + j], pj, xx, cc,
                                 None if gc is None else layer)
                acc = _add_stats(acc, st)
                ncs.append(nc)
            if gc is not None:
                return (xx, acc, ncs), None
            return (xx, acc, None), ncs if return_cache else None

        body = caches["body"] if caches is not None else None
        (x, stats, body), ys = jax.lax.scan(
            _remat_wrap(group_body, ctx.remat), (x, stats, body),
            (blocks, jnp.arange(plan.n_groups, dtype=jnp.int32)))
        new_caches["body"] = ys if body is None else body

    # --- suffix (unrolled) ------------------------------------------------------
    if plan.suffix:
        for i, name in enumerate(_unrolled_names(params["suffix"])):
            li = plan.suffix_start + i
            cc = caches["suffix"][i] if caches is not None else None
            x, nc, st = one(kinds[li], params["suffix"][name], x, cc)
            stats = _add_stats(stats, st)
            new_caches["suffix"].append(nc)

    return x, (new_caches if return_cache else None), stats


# ---------------------------------------------------------------------------
# Model-level entry points
# ---------------------------------------------------------------------------

def embed_in(cfg: ModelConfig, params, batch: Dict[str, jax.Array]) -> jax.Array:
    """Token ids -> embeddings, or pass through stub-frontend features."""
    if "embeds" in batch:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(jnp.dtype(cfg.dtype))
    if cfg.gemma_norm:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def lm_logits(cfg: ModelConfig, ctx: RunCtx, params, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"].astype(x.dtype), cfg.norm_eps,
                 gemma=cfg.gemma_norm)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    if cfg.logit_softcap > 0:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    return logits


def forward(
    cfg: ModelConfig,
    ctx: RunCtx,
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
) -> jax.Array:
    """Full-sequence forward -> logits [B, S, V]."""
    x = embed_in(cfg, params, batch)
    x = ctx.shard_act(x, ctx.batch_axes, ctx.seq_spec(x.shape[1]), None)
    positions = batch.get("positions")
    if positions is None:
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, _, _ = stack_apply(cfg, ctx, params, x, positions)
    return lm_logits(cfg, ctx, params, x)


def loss_fn(
    cfg: ModelConfig,
    ctx: RunCtx,
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Mean next-token (or masked-frame) cross entropy; labels < 0 ignored."""
    logits = forward(cfg, ctx, params, batch).astype(jnp.float32)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    nll = (lse - picked) * mask
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll) / denom
    return loss, {"loss": loss, "ntokens": jnp.sum(mask)}


# ---------------------------------------------------------------------------
# Caches: allocation + prefill + decode
# ---------------------------------------------------------------------------

def _layer_cache(cfg: ModelConfig, kind: LayerKind, batch: int, max_len: int, dtype):
    """One layer's cache: every attention layer's K/V at full length (a
    windowed layer's too), a mamba layer's states, nothing for a block
    without a mixer."""
    if kind.mixer in ("attn", "attn_local", "shared_attn"):
        return {"attn": init_attn_cache(cfg, batch, max_len, dtype)}
    if kind.mixer == "mamba":
        return {"mamba": init_ssm_cache(cfg, batch, dtype)}
    if kind.mixer == "none":
        return {}
    raise ValueError(kind.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    plan = layer_plan(cfg)
    kinds = plan.kinds
    out: Dict[str, Any] = {"prefix": [], "body": None, "suffix": []}
    for i in range(plan.prefix):
        out["prefix"].append(_layer_cache(cfg, kinds[i], batch, max_len, dtype))
    if plan.n_groups:
        body = []
        for j in range(plan.period):
            one = _layer_cache(cfg, kinds[plan.prefix + j], batch, max_len, dtype)
            body.append(jax.tree.map(
                lambda a: jnp.zeros((plan.n_groups,) + a.shape, a.dtype), one))
        out["body"] = body
    for i in range(plan.suffix):
        out["suffix"].append(
            _layer_cache(cfg, kinds[plan.suffix_start + i], batch, max_len, dtype)
        )
    return out


def prefill(
    cfg: ModelConfig,
    ctx: RunCtx,
    params: Dict[str, Any],
    batch: Dict[str, jax.Array],
    max_len: Optional[int] = None,
) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the prompt, return (last-position logits [B, V], cache).

    The returned cache holds exactly the prompt (length S); the serving
    engine pads/relocates it into its ring buffers.
    """
    x = embed_in(cfg, params, batch)
    x = ctx.shard_act(x, ctx.batch_axes, ctx.seq_spec(x.shape[1]), None)
    positions = batch.get("positions")
    if positions is None:
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x, caches, _ = stack_apply(
        cfg, ctx, params, x, positions, return_cache=True
    )
    logits = lm_logits(cfg, ctx, params, x[:, -1:, :])
    return logits[:, 0, :], caches


def decode_step(
    cfg: ModelConfig,
    ctx: RunCtx,
    params: Dict[str, Any],
    caches: Dict[str, Any],
    tokens: jax.Array,           # [B] int32 (or embeds [B, 1, d])
    pos: jax.Array,              # () or [B] int32 — write position(s)
    *,
    return_stats: bool = False,
):
    """One autoregressive step over a pre-allocated cache; returns
    ``(logits [B, V], caches)``, with ``return_stats`` also the layers'
    stats (the MoE counts of :func:`repro.models.moe.moe_share`, summed
    over the layers; empty for a model without experts).

    A scalar ``pos`` steps all sequences in lockstep; a ``[B]`` vector is
    the continuous-batching path (each session at its own depth).  A slot
    at position 0 starts a session: its recurrent state starts from zero.
    A slot at a negative position is not in the step: no layer writes its
    cache or state (its logits are meaningless).
    """
    if tokens.ndim == 1:
        x = jnp.take(params["embed"], tokens[:, None], axis=0).astype(jnp.dtype(cfg.dtype))
        if cfg.gemma_norm:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    else:
        x = tokens.astype(jnp.dtype(cfg.dtype))
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos[None, None], (b, 1))
    else:
        positions = pos[:, None]
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(positions[None], (3, b, 1))
    x, new_caches, stats = stack_apply(
        cfg, ctx, params, x, positions,
        caches=caches, cache_index=pos, return_cache=True,
    )
    logits = lm_logits(cfg, ctx, params, x)
    if return_stats:
        return logits[:, 0, :], new_caches, stats
    return logits[:, 0, :], new_caches
