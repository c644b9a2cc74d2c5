"""Plain reference for DeepSeek-V2 as the MLA / MoE serving cell runs it.

Float32 throughout, matmuls at ``Precision.HIGHEST``, no cache: one causal
forward over whole sequences, a layer at a time, each layer's weights
regenerated from the seed by ``bench/weights.py`` (nothing the program made
is read).  Sequences of one length bucket go through together as the rows
of one batch; a row never reads another.  The equations (DeepSeek-V2,
arXiv:2405.04434, and its published ``config.json``):

* ``x = embed[tokens]``;
* per layer, attention (MLA, un-absorbed): ``h = rms(x)``;
  ``q = rms(h Wq_a) Wq_b`` split into 128 heads of ``qk_nope + qk_rope``
  (128 + 64); ``kv = h Wkv_a``, ``c = rms(kv[:512])`` the latent,
  ``k_pe = rope(kv[512:])`` one rotary key shared by every head;
  ``[k_nope, v] = c Wkv_b`` per head (128 + 128); keys ``[k_nope, k_pe]``,
  queries ``[q_nope, rope(q_pe)]``; causal softmax attention with scale
  ``(qk_nope + qk_rope) ** -0.5 * mscale(40, 0.707) ** 2``;
  ``x += attn Wo``;
* rotary (YaRN): pair ``i`` of the 64 rotary dims turns at
  ``f_i = theta ** (-2i / 64)`` blended toward ``f_i / 40`` by the ramp
  ``clip((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(c(32))``,
  ``hi = ceil(c(1))``, ``c(r) = 64 ln(4096 / (2 pi r)) / (2 ln theta)``;
  cos and sin scaled by ``mscale(40, 0.707) / mscale(40, 0.707) = 1``,
  ``mscale(f, m) = 0.1 m ln f + 1``;
* the first layer's FFN is dense: ``(silu(h Wg) * (h Wu)) Wd``, 12,288 wide;
* every later layer's FFN (MoE): ``s = softmax(h W_router)`` over all 160
  routed experts; each of the 8 groups of 20 scores its best expert, the 3
  best groups are kept (lower index first on ties) and the other scores
  zeroed; the 6 best remaining experts are the token's, with gate
  ``16 s_e`` (no renormalisation); the FFN is the gated sum of the chosen
  experts' SwiGLUs (1,536 wide) plus the shared experts' SwiGLU (two of
  1,536, fused, 3,072 wide);
* ``logits = rms(x) W_lm``.

Departures from the published model, as the program has them:

* the rotary dims are rotated as two split halves; the published code
  pairs interleaved dims (with random weights this changes which dims
  pair, not the work);
* the expert share: of the 160 routed experts only the held ones (the
  configuration's ``held_first`` and ``n_held``: group 0, experts 0-19)
  are computed; a chosen expert that is not held adds nothing.  Routing is
  still over all 160;
* RMSNorm gains, ``q_norm`` and ``kv_norm`` among them, are all ones in the
  seeded checkpoint, so ``rms(x)`` is ``x / sqrt(mean(x^2) + eps)``.

``gaps`` reads, for each served token, how far its reference logit lies
below the reference's best at that position.  With ``control=True`` it
reads instead the control: the forward recomputed with every matmul's
operands rounded to float8 (e4m3, per-tensor scale for weights, per-row for
activations), the precision below the configuration's bfloat16, and the
reference gap of the token that the float8 forward puts first.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 256         # sequence length bucket and logit block
QBLOCK = 128         # query block of the attention scores
ROWS = 8             # rows of a batch, at most
ATTN = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
SWIGLU = ("w_gate", "w_up", "w_down")


# ---------------------------------------------------------------------------
# Shapes and weights
# ---------------------------------------------------------------------------

def _attn_shapes(m: Dict) -> Dict[str, tuple]:
    a, d, h = m["mla"], m["d_model"], m["n_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    return {"wq_a": (d, a["q_lora_rank"]),
            "wq_b": (a["q_lora_rank"], h * qk),
            "wkv_a": (d, a["kv_lora_rank"] + a["qk_rope_head_dim"]),
            "wkv_b": (a["kv_lora_rank"],
                      h * (a["qk_nope_head_dim"] + a["v_head_dim"])),
            "wo": (h * a["v_head_dim"], d)}


def _swiglu_shapes(d: int, f: int) -> Dict[str, tuple]:
    return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}


def _draw(key, offset, shape):
    """A 2-D block of a leaf (fan-in its first dim), as served, in float32."""
    return weights.draw(key, shape, shape[0], jnp.bfloat16, offset
                        ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# The equations
# ---------------------------------------------------------------------------

def _q8(t, axis=None):
    """Round to float8 e4m3 with a scale that maps the largest |t| to 448."""
    amax = jnp.max(jnp.abs(t), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (t / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, q8: bool):
    if q8:
        a, w = _q8(a, axis=-1), _q8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(m: Dict):
    """(inverse frequencies of the rotary pairs, cos/sin amplitude)."""
    dim, theta = m["mla"]["qk_rope_head_dim"], m["rope_theta"]
    y = m["rope_scaling"]
    inv = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def c(rot):
        return (dim * math.log(y["original_max_position"] / (2 * math.pi * rot))
                / (2 * math.log(theta)))

    lo = max(math.floor(c(y["beta_fast"])), 0)
    hi = min(math.ceil(c(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    inv = inv / y["factor"] * ramp + inv * (1.0 - ramp)
    amp = (_mscale(y["factor"], y["mscale"])
           / _mscale(y["factor"], y["mscale_all_dim"]))
    return inv.astype(np.float32), amp


def _rope(x, pos, m: Dict):
    """``x``: [B, S, H, 64] rotated as two halves; ``pos``: [S]."""
    inv, amp = _yarn(m)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)[None, :]
    cos = (jnp.cos(ang) * amp)[:, None, :]
    sin = (jnp.sin(ang) * amp)[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(m: Dict) -> float:
    a, y = m["mla"], m["rope_scaling"]
    return ((a["qk_nope_head_dim"] + a["qk_rope_head_dim"]) ** -0.5
            * _mscale(y["factor"], y["mscale_all_dim"]) ** 2)


def _mla(x, w, m: Dict, q8: bool):
    """Causal MLA over ``x`` [B, S, d], per-head keys and values expanded
    from the latent."""
    a, h = m["mla"], m["n_heads"]
    nope, rope, dv = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                      a["v_head_dim"])
    b, s = x.shape[:2]
    pos = jnp.arange(s, dtype=jnp.int32)
    eps = m["norm_eps"]
    q = _mm(_rms(_mm(x, w["wq_a"], q8), eps), w["wq_b"], q8
            ).reshape(b, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], -1)
    kv = _mm(x, w["wkv_a"], q8)
    c = _rms(kv[..., :a["kv_lora_rank"]], eps)
    k_pe = _rope(kv[..., None, a["kv_lora_rank"]:], pos, m)
    kvb = _mm(c, w["wkv_b"], q8).reshape(b, s, h, nope + dv)
    k = jnp.concatenate([kvb[..., :nope],
                         jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    v = kvb[..., nope:]
    scale = softmax_scale(m)
    outs = []
    for q0 in range(0, s, QBLOCK):
        qb = q[:, q0:q0 + QBLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) * scale
        mask = pos[None, :] <= (q0 + jnp.arange(qb.shape[1]))[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST))
    attn = jnp.concatenate(outs, axis=1).reshape(b, s, h * dv)
    return _mm(attn, w["wo"], q8)


def _swiglu(x, w, q8: bool):
    g = _mm(x, w["w_gate"], q8)
    return _mm(jax.nn.silu(g) * _mm(x, w["w_up"], q8), w["w_down"], q8)


def _route(x, router, mo: Dict, q8: bool):
    """Gates [T, E] of the chosen experts (0 elsewhere), group-limited."""
    s = jax.nn.softmax(_mm(x, router, q8), axis=-1)
    t, e = s.shape
    groups = s.reshape(t, mo["n_group"], e // mo["n_group"])
    best = groups.max(axis=-1)                                  # [T, G]
    # rank of each group among the token's groups, lower index first on ties
    ahead = ((best[:, None, :] > best[:, :, None])
             | ((best[:, None, :] == best[:, :, None])
                & (jnp.arange(mo["n_group"])[None, None, :]
                   < jnp.arange(mo["n_group"])[None, :, None]))).sum(-1)
    kept = ahead < mo["topk_group"]                              # [T, G]
    s_kept = jnp.where(kept[:, :, None], groups, 0.0).reshape(t, e)
    _, ids = jax.lax.top_k(s_kept, mo["top_k"])
    chosen = jnp.zeros((t, e), bool).at[jnp.arange(t)[:, None], ids].set(True)
    return jnp.where(chosen, mo["router_scale"] * s, 0.0)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _moe(x, keys, offsets, *, mkey, q8):
    """The held experts' gated sum plus the shared experts: ``keys``,
    ``offsets`` for router, the three expert leaves, the three shared."""
    m = _unkey(mkey)
    mo, d = m["moe"], m["d_model"]
    b, s = x.shape[:2]
    xt = x.reshape(b * s, d)
    router = _draw(keys[0], offsets[0], (d, mo["n_experts"]))
    gates = _route(xt, router, mo, q8)
    f = mo["d_expert"]
    sizes = _swiglu_shapes(d, f)

    def expert(acc, e):
        w = {n: _draw(keys[1 + i], offsets[1 + i] + e * d * f, sizes[n])
             for i, n in enumerate(SWIGLU)}
        g = jax.lax.dynamic_index_in_dim(gates, mo["held_first"] + e, 1,
                                         keepdims=False)
        return acc + g[:, None] * _swiglu(xt, w, q8), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xt),
                        jnp.arange(mo["n_held"], dtype=jnp.uint32))
    shared = {n: _draw(keys[4 + i], offsets[4 + i], shape) for i, (n, shape)
              in enumerate(_swiglu_shapes(d, mo["d_shared"]
                                          * mo["n_shared"]).items())}
    return (y + _swiglu(xt, shared, q8)).reshape(b, s, d)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _attn_layer(x, keys, offsets, *, mkey, q8):
    m = _unkey(mkey)
    shapes = _attn_shapes(m)
    w = {n: _draw(keys[i], offsets[i], shapes[n]) for i, n in enumerate(ATTN)}
    return x + _mla(_rms(x, m["norm_eps"]), w, m, q8)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _dense_ffn(x, keys, *, mkey, q8):
    m = _unkey(mkey)
    shapes = _swiglu_shapes(m["d_model"], m["moe"]["d_first_dense"])
    w = {n: _draw(keys[i], 0, shapes[n]) for i, n in enumerate(SWIGLU)}
    return x + _swiglu(_rms(x, m["norm_eps"]), w, q8)


@partial(jax.jit, static_argnames=("mkey",))
def _embed(tokens, key, *, mkey):
    m = _unkey(mkey)
    table = weights.draw(key, (m["vocab_size"], m["d_model"]),
                         m["vocab_size"], jnp.bfloat16).astype(jnp.float32)
    return table[tokens]


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _head(x, key, served, *, mkey, q8):
    """Per row and position: (best logit, served token's logit, argmax)."""
    m = _unkey(mkey)
    w = weights.draw(key, (m["d_model"], m["vocab_size"]), m["d_model"],
                     jnp.bfloat16).astype(jnp.float32)
    x = _rms(x, m["norm_eps"])
    best, got, top = [], [], []
    for r0 in range(0, x.shape[1], BUCKET):
        lg = _mm(x[:, r0:r0 + BUCKET], w, q8)
        best.append(lg.max(axis=-1))
        got.append(jnp.take_along_axis(lg, served[:, r0:r0 + BUCKET, None],
                                       axis=-1)[..., 0])
        top.append(lg.argmax(axis=-1).astype(jnp.int32))
    return (jnp.concatenate(best, 1), jnp.concatenate(got, 1),
            jnp.concatenate(top, 1))


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------

def _keys(seed: int, names: Sequence[str]) -> np.ndarray:
    return np.asarray([weights.leaf_key(seed, n) for n in names], np.uint32)


def _hidden(m: Dict, seed: int, tokens, q8: bool):
    """The last layer's output for every row and position: the dense first
    layer, then the MoE layers (the stacked ``blocks/pos0`` leaves)."""
    mkey = _mkey(m)
    mo, d = m["moe"], m["d_model"]
    x = _embed(jnp.asarray(tokens), np.uint32(weights.leaf_key(seed, "embed")),
               mkey=mkey)
    shapes = _attn_shapes(m)
    zero = np.zeros(len(ATTN), np.uint32)
    x = _attn_layer(x, _keys(seed, [f"prefix/layer0/attn/{n}" for n in ATTN]),
                    zero, mkey=mkey, q8=q8)
    x = _dense_ffn(x, _keys(seed, [f"prefix/layer0/mlp/{n}" for n in SWIGLU]),
                   mkey=mkey, q8=q8)
    attn_keys = _keys(seed, [f"blocks/pos0/attn/{n}" for n in ATTN])
    moe_keys = _keys(seed, ["blocks/pos0/moe/router"]
                     + [f"blocks/pos0/moe/experts/{n}" for n in SWIGLU]
                     + [f"blocks/pos0/moe/shared/{n}" for n in SWIGLU])
    f, fs = mo["d_expert"], mo["d_shared"] * mo["n_shared"]
    per_layer = ([d * mo["n_experts"]] + [mo["n_held"] * d * f] * 3
                 + [d * fs] * 3)
    for layer in range(m["n_layers"] - mo["first_dense_layers"]):
        offs = np.asarray([layer * math.prod(shapes[n]) for n in ATTN],
                          np.uint32)
        x = _attn_layer(x, attn_keys, offs, mkey=mkey, q8=q8)
        offs = np.asarray([layer * n for n in per_layer], np.uint32)
        x = x + _moe(_rms_jit(x, m["norm_eps"]), moe_keys, offs, mkey=mkey,
                     q8=q8)
    return x


@partial(jax.jit, static_argnames=("eps",))
def _rms_jit(x, eps):
    return _rms(x, eps)


def _mkey(m: Dict):
    """A hashable key of the model block (nested blocks as tuples)."""
    return tuple(sorted((k, _mkey(v) if isinstance(v, dict) else v)
                        for k, v in m.items()
                        if isinstance(v, (int, float, str, dict))))


def _unkey(mkey) -> Dict:
    return {k: _unkey(v) if isinstance(v, tuple) else v for k, v in mkey}


def batches(lengths: Sequence[int]):
    """Sequences grouped by length bucket, at most :data:`ROWS` a batch:
    ``(padded length, indices)``."""
    by_len: Dict[int, List[int]] = {}
    for i, n in enumerate(lengths):
        by_len.setdefault(-(-n // BUCKET) * BUCKET, []).append(i)
    return [(s, idx[r:r + ROWS]) for s, idx in sorted(by_len.items())
            for r in range(0, len(idx), ROWS)]


def gaps(m: Dict, seed: int, sequences: Sequence[Sequence[int]],
         control: bool = False) -> List[np.ndarray]:
    """Reference logit gaps of each sequence's served tokens.

    A sequence is a session's first input token (its prompt) followed by
    the tokens it was served, in order.  Returns per sequence the gap of
    each served token or, with ``control``, the gap of the float8
    forward's first choice at each of those positions.
    """
    out: List[np.ndarray] = [np.zeros((0,))] * len(sequences)
    head_key = np.uint32(weights.leaf_key(seed, "lm_head"))
    mkey = _mkey(m)
    served_n = [len(q) - 1 for q in sequences]
    with jax.default_matmul_precision("highest"):
        for s, idx in batches(served_n):
            rows = 1 << (len(idx) - 1).bit_length()    # 1, 2, 4 or 8
            tokens = np.zeros((rows, s), np.int32)
            served = np.zeros((rows, s), np.int32)
            for r, i in enumerate(idx):
                seq = np.asarray(sequences[i], np.int32)
                tokens[r, :len(seq) - 1] = seq[:-1]
                served[r, :len(seq) - 1] = seq[1:]
            x = _hidden(m, seed, tokens, q8=False)
            best, got, _ = _head(x, head_key, served, mkey=mkey, q8=False)
            if control:
                x8 = _hidden(m, seed, tokens, q8=True)
                _, _, top = _head(x8, head_key, served, mkey=mkey, q8=True)
                del x8
                _, got, _ = _head(x, head_key, top, mkey=mkey, q8=False)
            gap = np.asarray(best) - np.asarray(got)
            for r, i in enumerate(idx):
                out[i] = gap[r, :served_n[i]]
    return out
