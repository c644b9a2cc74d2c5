"""Shared model-definition substrate for the 10 assigned architectures.

Everything is functional pure-JAX: a model is (init_fn, apply fns, sharding
rules).  Parameters are plain nested dicts of jnp arrays; layer stacks are
``lax.scan``-compatible (params stacked over a leading "group" axis), which
keeps HLO size independent of depth and makes per-layer sharding rules
uniform.

The configuration dataclasses below span every architectural feature the
assignment requires: GQA, partial/M-RoPE rotary, sliding-window + periodic
global attention (gemma3), MLA latent attention (deepseek-v2), mixture of
experts (mixtral / deepseek-v2), Mamba2 SSD blocks (mamba2), hybrid shared
attention (zamba2), bidirectional encoders (hubert) and vision/audio frontend
stubs (qwen2-vl / hubert).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek-V2 Multi-head Latent Attention dimensions."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8             # routed experts the router scores
    top_k: int = 2
    d_expert: int = 0              # expert FFN hidden dim
    n_shared: int = 0              # always-on shared experts (deepseek-v2)
    d_shared: int = 0              # hidden dim of the fused shared expert
    first_dense_layers: int = 0    # leading layers that use a dense FFN
    d_first_dense: int = 0
    router_scale: float = 1.0      # routed-expert weight scale
    # group-limited routing (deepseek-v2 ``group_limited_greedy``): the
    # experts form ``n_group`` contiguous groups, a token keeps the
    # ``topk_group`` groups with the highest best score and picks its
    # ``top_k`` among their experts; 1 group is plain top-k
    n_group: int = 1
    topk_group: int = 1
    # the expert share this layer holds: experts ``held_first ..
    # held_first + n_held - 1`` (0 = all of them); the router keeps its
    # ``n_experts`` outputs and absent experts contribute nothing
    held_first: int = 0
    n_held: int = 0

    @property
    def held(self) -> Tuple[int, int]:
        """``(first, count)`` of the experts this layer holds."""
        return self.held_first, self.n_held or self.n_experts


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling as DeepSeek-V2's ``rope_scaling`` gives it."""

    factor: float = 40.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block dimensions."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | encoder | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # activations / norms
    mlp_act: str = "swiglu"        # swiglu | geglu | relu2 | gelu
    norm_eps: float = 1e-5
    use_qk_norm: bool = False
    gemma_norm: bool = False       # (1+w) RMSNorm + sqrt(d) embedding scale
    tie_embeddings: bool = False
    logit_softcap: float = 0.0
    # rotary
    rope_theta: float = 1e4
    rope_theta_global: Optional[float] = None    # gemma3 global layers
    partial_rotary: float = 1.0
    rope_scaling: Optional[YarnScaling] = None   # deepseek-v2 (MLA only)
    mrope_sections: Optional[Tuple[int, ...]] = None    # qwen2-vl
    # attention pattern
    causal: bool = True            # False => bidirectional encoder
    sliding_window: Optional[int] = None
    global_every: Optional[int] = None   # 1 global layer per this many layers
    # specials
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    hybrid_attn_every: Optional[int] = None   # zamba2 shared-attn period
    max_seq_len: int = 131072
    dtype: str = "bfloat16"

    # -- derived -------------------------------------------------------------
    @property
    def q_per_kv(self) -> int:
        return max(1, self.n_heads // max(1, self.n_kv_heads))

    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        """Analytic parameter count (used by roofline MODEL_FLOPS)."""
        return _param_count_slow(self)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: shared + top-k experts only)."""
        total = _param_count_slow(self)
        if self.moe is None:
            return total
        m = self.moe
        n_moe_layers = self.n_layers - m.first_dense_layers
        per_expert = 3 * self.d_model * m.d_expert
        inactive = n_moe_layers * (m.n_experts - m.top_k) * per_expert
        return total - inactive


def _param_count_slow(cfg: ModelConfig) -> int:
    shapes = param_shapes(cfg)
    leaves = jax.tree.leaves(shapes, is_leaf=lambda s: isinstance(s, tuple))
    return int(sum(int(np.prod(s)) for s in leaves))


# ---------------------------------------------------------------------------
# Layer pattern
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerKind:
    mixer: str                     # "attn" | "attn_local" | "mamba" | "shared_attn"
    ffn: str                       # "dense" | "moe" | "none"


@dataclass(frozen=True)
class LayerPlan:
    """How the n_layers stack maps onto prefix + scanned body + suffix.

    ``kinds`` covers all layers; ``prefix`` leading layers and ``suffix``
    trailing layers are unrolled (own params), the middle
    ``n_groups × period`` layers are ``lax.scan``-stacked (params stacked on
    a leading group axis), keeping HLO size depth-independent.
    """

    kinds: Tuple[LayerKind, ...]
    prefix: int
    period: int
    n_groups: int

    @property
    def suffix(self) -> int:
        return len(self.kinds) - self.prefix - self.period * self.n_groups

    @property
    def suffix_start(self) -> int:
        return self.prefix + self.period * self.n_groups


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    kinds: List[LayerKind] = []
    for i in range(cfg.n_layers):
        if cfg.ssm is not None and cfg.hybrid_attn_every:
            # zamba2: shared attention block every `hybrid_attn_every` layers
            if (i + 1) % cfg.hybrid_attn_every == 0:
                kinds.append(LayerKind("shared_attn", "dense"))
            else:
                kinds.append(LayerKind("mamba", "none"))
        elif cfg.ssm is not None:
            kinds.append(LayerKind("mamba", "none"))
        elif cfg.global_every:
            # gemma3: 1 global layer per `global_every`, rest sliding-window
            if (i + 1) % cfg.global_every == 0:
                kinds.append(LayerKind("attn", "dense"))
            else:
                kinds.append(LayerKind("attn_local", "dense"))
        else:
            ffn = "dense"
            if cfg.moe is not None and i >= cfg.moe.first_dense_layers:
                ffn = "moe"
            local = cfg.sliding_window is not None and cfg.global_every is None
            kinds.append(LayerKind("attn_local" if local else "attn", ffn))
    prefix = 0
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        prefix = cfg.moe.first_dense_layers
    body = kinds[prefix:]
    # smallest period p whose repetition covers a maximal prefix of the body;
    # the remainder becomes the unrolled suffix
    period, n_groups = len(body), 1 if body else 0
    for p in range(1, len(body) + 1):
        k = len(body) // p
        if k >= 1 and all(body[j] == body[j % p] for j in range(k * p)):
            period, n_groups = p, k
            break
    return LayerPlan(tuple(kinds), prefix, period, n_groups)


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float, gemma: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if gemma else w.astype(jnp.float32)
    return (x * scale).astype(dt)


def mlp_apply(p: Dict[str, jax.Array], x: jax.Array, act: str) -> jax.Array:
    """Feed-forward: gated (swiglu/geglu) or plain (relu2/gelu)."""
    if act in ("swiglu", "geglu"):
        g = x @ p["w_gate"]
        u = x @ p["w_up"]
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    elif act == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_up"]))
    elif act == "gelu":
        h = jax.nn.gelu(x @ p["w_up"])
    else:
        raise ValueError(act)
    return h @ p["w_down"]


def mlp_shapes(d_model: int, d_ff: int, act: str) -> Dict[str, Tuple[int, ...]]:
    if act in ("swiglu", "geglu"):
        return {
            "w_gate": (d_model, d_ff),
            "w_up": (d_model, d_ff),
            "w_down": (d_ff, d_model),
        }
    return {"w_up": (d_model, d_ff), "w_down": (d_ff, d_model)}


# ---------------------------------------------------------------------------
# Rotary embeddings (standard / partial / M-RoPE / dual-theta)
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 m ln(factor) + 1`` (1 unscaled)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(dim: int, theta: float,
               scaling: Optional[YarnScaling] = None) -> jax.Array:
    """Inverse frequencies of the ``dim // 2`` rotated pairs.  With YaRN
    scaling, each frequency is blended between the interpolated one (÷
    ``factor``) and the original by a linear ramp over the correction
    range that ``beta_fast`` / ``beta_slow`` rotations give at the
    original context length."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    if scaling is None:
        return inv

    def correction_dim(rotations: float) -> float:
        return (dim * math.log(scaling.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    return inv / scaling.factor * ramp + inv * extrapolate


def apply_rope(
    x: jax.Array,                 # [B, S, H, D]
    positions: jax.Array,         # [B, S] or [3, B, S] for M-RoPE
    theta: float,
    partial: float = 1.0,
    mrope_sections: Optional[Tuple[int, ...]] = None,
    scaling: Optional[YarnScaling] = None,
) -> jax.Array:
    d = x.shape[-1]
    rot = int(d * partial)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    inv = rope_freqs(rot, theta, scaling)                # [rot/2]
    if mrope_sections is not None:
        # M-RoPE: frequency bands are split into sections, each rotated by a
        # different positional stream (temporal / height / width).  Text-only
        # inputs pass identical streams, which reduces to standard RoPE.
        assert positions.ndim == 3, "M-RoPE expects positions [n_sections, B, S]"
        assert sum(mrope_sections) == rot // 2
        pos_parts = []
        start = 0
        for sec_i, sec in enumerate(mrope_sections):
            pos_parts.append(
                positions[sec_i][..., None] * inv[start:start + sec][None, None, :]
            )
            start += sec
        ang = jnp.concatenate(pos_parts, axis=-1)        # [B, S, rot/2]
    else:
        if positions.ndim == 3:
            positions = positions[0]
        ang = positions[..., None].astype(jnp.float32) * inv[None, None, :]
    amp = 1.0 if scaling is None else (
        yarn_mscale(scaling.factor, scaling.mscale)
        / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    scale = (lambda t: t) if amp == 1.0 else (lambda t: t * amp)
    cos = scale(jnp.cos(ang))[:, :, None, :].astype(x.dtype)  # [B, S, 1, rot/2]
    sin = scale(jnp.sin(ang))[:, :, None, :].astype(x.dtype)
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, xp], axis=-1) if rot < d else out


# ---------------------------------------------------------------------------
# Parameter shapes & init
# ---------------------------------------------------------------------------

def attn_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.mla is not None:
        m = cfg.mla
        qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
        return {
            "wq_a": (d, m.q_lora_rank),
            "q_norm": (m.q_lora_rank,),
            "wq_b": (m.q_lora_rank, hq * qk_dim),
            "wkv_a": (d, m.kv_lora_rank + m.qk_rope_head_dim),
            "kv_norm": (m.kv_lora_rank,),
            "wkv_b": (m.kv_lora_rank, hq * (m.qk_nope_head_dim + m.v_head_dim)),
            "wo": (hq * m.v_head_dim, d),
        }
    sh: Dict[str, Any] = {
        "wq": (d, hq * hd),
        "wk": (d, hkv * hd),
        "wv": (d, hkv * hd),
        "wo": (hq * hd, d),
    }
    if cfg.use_qk_norm:
        sh["q_norm"] = (hd,)
        sh["k_norm"] = (hd,)
    return sh


def mamba_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_dim = di + 2 * s.n_groups * s.d_state
    return {
        # in_proj -> [z (gate), x, B, C, dt]
        "w_in": (d, 2 * di + 2 * s.n_groups * s.d_state + nh),
        "conv_w": (s.d_conv, conv_dim),
        "conv_b": (conv_dim,),
        "A_log": (nh,),
        "D": (nh,),
        "dt_bias": (nh,),
        "gate_norm": (di,),
        "w_out": (di, d),
    }


def chunk_plan(n_experts: int, model_size: int) -> Tuple[int, int, int, int]:
    """Expert layout plan: (ep, tp, experts_per_chunk, n_chunks=model_size).

    The model mesh axis is split into ``ep`` expert groups × ``tp``-way
    tensor parallelism inside each expert, so any expert count divides any
    axis size (one of the two must divide the other).
    """
    if model_size <= 1:
        return 1, 1, n_experts, 1
    if n_experts >= model_size:
        assert n_experts % model_size == 0, (n_experts, model_size)
        return model_size, 1, n_experts // model_size, model_size
    assert model_size % n_experts == 0, (n_experts, model_size)
    tp = model_size // n_experts
    return n_experts, tp, 1, model_size


def moe_shapes(cfg: ModelConfig, model_size: int = 1) -> Dict[str, Any]:
    """Expert weights in chunked [n_chunks, n_e, d, f_c] layout (EP × TP),
    of the experts the layer holds; the router scores all of them."""
    m = cfg.moe
    d = cfg.d_model
    ep, tp, n_e, nc = chunk_plan(m.held[1], model_size)
    f_c = m.d_expert // tp
    sh: Dict[str, Any] = {
        "router": (d, m.n_experts),
        "experts": {
            "w_gate": (nc, n_e, d, f_c),
            "w_up": (nc, n_e, d, f_c),
            "w_down": (nc, n_e, f_c, d),
        },
    }
    if m.n_shared:
        sh["shared"] = mlp_shapes(d, m.d_shared * m.n_shared, "swiglu")
    return sh


def _layer_shapes(cfg: ModelConfig, kind: LayerKind, model_size: int = 1) -> Dict[str, Any]:
    sh: Dict[str, Any] = {}
    if kind.mixer in ("attn", "attn_local"):
        sh["attn"] = attn_shapes(cfg)
        sh["ln_attn"] = (cfg.d_model,)
        if cfg.gemma_norm:
            sh["ln_post_attn"] = (cfg.d_model,)
    elif kind.mixer == "mamba":
        sh["mamba"] = mamba_shapes(cfg)
        sh["ln_mix"] = (cfg.d_model,)
    # shared_attn params live outside the stacked tree (they are shared)
    if kind.ffn == "dense":
        sh["mlp"] = mlp_shapes(cfg.d_model, cfg.d_ff, cfg.mlp_act)
        sh["ln_mlp"] = (cfg.d_model,)
        if cfg.gemma_norm:
            sh["ln_post_mlp"] = (cfg.d_model,)
    elif kind.ffn == "moe":
        sh["moe"] = moe_shapes(cfg, model_size)
        sh["ln_mlp"] = (cfg.d_model,)
    return sh


def param_shapes(cfg: ModelConfig, model_size: int = 1) -> Dict[str, Any]:
    """The full parameter tree, with per-pattern-group stacking.

    Layout::

        embed:   [vocab, d]
        prefix:  {layer0: {...}, ...}     unrolled leading layers (MoE dense prefix)
        blocks:  {pos0: [n_groups, ...]}  one stacked entry per pattern position
        suffix:  {layerK: {...}, ...}     unrolled trailing remainder layers
        shared_attn: {...}                zamba2 only (shared across groups)
        final_norm: [d]
        lm_head: [d, vocab]               (absent if tied)

    ``model_size`` fixes the MoE chunked-expert layout (EP × TP grid over the
    model mesh axis); 1 = single-device reference layout.
    """
    plan = layer_plan(cfg)
    kinds, prefix = plan.kinds, plan.prefix
    tree: Dict[str, Any] = {}
    tree["embed"] = (cfg.vocab_size, cfg.d_model)
    if prefix:
        dense_cfg = dataclasses.replace(
            cfg, moe=None, d_ff=cfg.moe.d_first_dense or cfg.d_ff
        )
        tree["prefix"] = {
            f"layer{i}": _layer_shapes(dense_cfg, LayerKind("attn", "dense"), model_size)
            for i in range(prefix)
        }
    body: Dict[str, Any] = {}
    for j in range(plan.period):
        kind = kinds[prefix + j]
        ls = _layer_shapes(cfg, kind, model_size)
        body[f"pos{j}"] = jax.tree.map(
            lambda s: (plan.n_groups,) + tuple(s),
            ls,
            is_leaf=lambda s: isinstance(s, tuple),
        )
    tree["blocks"] = body
    if plan.suffix:
        tree["suffix"] = {
            f"layer{plan.suffix_start + i}": _layer_shapes(
                cfg, kinds[plan.suffix_start + i], model_size
            )
            for i in range(plan.suffix)
        }
    if any(k.mixer == "shared_attn" for k in kinds):
        tree["shared_attn"] = {
            "attn": attn_shapes(cfg),
            "ln_attn": (cfg.d_model,),
        }
    tree["final_norm"] = (cfg.d_model,)
    if not cfg.tie_embeddings:
        tree["lm_head"] = (cfg.d_model, cfg.vocab_size)
    return tree


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _truncated_normal(key, shape, std, dtype):
    # one fused program per leaf: the float32 draw never materializes, so a
    # bf16 tree initializes within the device memory it will occupy
    return (jax.random.truncated_normal(key, -3, 3, shape, jnp.float32)
            * std).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32,
                model_size: int = 1) -> Dict[str, Any]:
    shapes = param_shapes(cfg, model_size)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda s: isinstance(s, tuple)
    )
    keys = jax.random.split(key, len(leaves))

    def init_one(shape, k):
        if len(shape) == 1 or (len(shape) == 2 and shape[-1] in (1,)):
            return jnp.zeros(shape, dtype)
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return _truncated_normal(k, tuple(shape), 1.0 / math.sqrt(fan_in),
                                 jnp.dtype(dtype))

    params = treedef.unflatten([init_one(s, k) for s, k in zip(leaves, keys)])
    # special inits
    def fix(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "A_log":
            return jnp.log(jnp.linspace(1.0, 16.0, x.shape[-1], dtype=jnp.float32)
                           * jnp.ones(x.shape, jnp.float32)).astype(x.dtype)
        if name == "D":
            return jnp.ones_like(x)
        if name == "dt_bias":
            # softplus^-1 of dt in [1e-3, 1e-1]
            return jnp.log(jnp.expm1(jnp.full(x.shape, 0.01, jnp.float32))).astype(x.dtype)
        if name in ("gate_norm", "q_norm", "k_norm", "kv_norm", "final_norm",
                    "ln_attn", "ln_mlp", "ln_mix", "ln_post_attn", "ln_post_mlp"):
            return jnp.zeros_like(x) if False else jnp.ones_like(x)
        return x

    params = jax.tree_util.tree_map_with_path(fix, params)
    if cfg.gemma_norm:
        # gemma RMSNorm computes (1 + w): init scales to zero
        def zero_norms(path, x):
            name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
            if name.startswith("ln_") or name == "final_norm":
                return jnp.zeros_like(x)
            return x
        params = jax.tree_util.tree_map_with_path(zero_norms, params)
    return params
