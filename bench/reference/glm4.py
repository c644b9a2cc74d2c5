"""Plain reference for the dense GQA decoder the serving cells run.

Float32 throughout, matmuls at ``Precision.HIGHEST``, no cache: one causal
forward over whole sequences, a layer at a time, each layer's weights
regenerated from the seed by ``bench/weights.py`` (nothing the program made
is read).  Sequences of one length bucket go through together as the rows
of one batch; a row never reads another.  The equations:

* ``x = embed[tokens]``;
* per layer: ``h = rms(x)``; ``q, k, v = h Wq, h Wk, h Wv`` split into
  ``n_heads`` / ``n_kv_heads`` heads of ``head_dim``; rotary position on
  the first ``partial_rotary * head_dim`` dims of q and k (the rotated part
  split in halves, ``theta = rope_theta``); causal softmax attention with
  scale ``head_dim ** -0.5``, query head ``h`` reading key/value head
  ``h // (n_heads / n_kv_heads)``; ``x += attn Wo``;
  ``h = rms(x)``; ``x += (silu(h Wg) * (h Wu)) Wd``;
* ``logits = rms(x) W_lm``.

RMSNorm gains are all ones in the seeded checkpoint, so ``rms(x)`` is
``x / sqrt(mean(x^2) + eps)``.

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
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST
BUCKET = 256         # sequence length bucket and query / logit block
ROWS = 8             # rows of a batch, at most
LAYER_LEAVES = ("attn/wq", "attn/wk", "attn/wv", "attn/wo",
                "mlp/w_gate", "mlp/w_up", "mlp/w_down")


def _layer_shapes(m: Dict) -> Dict[str, Tuple[int, int]]:
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"])
    return {"attn/wq": (d, hq * hd), "attn/wk": (d, hkv * hd),
            "attn/wv": (d, hkv * hd), "attn/wo": (hq * hd, d),
            "mlp/w_gate": (d, ff), "mlp/w_up": (d, ff), "mlp/w_down": (ff, d)}


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


def _rope(x, pos, theta: float, partial_rotary: float):
    """``x``: [B, S, H, D]; ``pos``: [S]."""
    d = x.shape[-1]
    rot = int(d * partial_rotary)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]       # [S, rot/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _draw(m, key, offset, shape):
    return weights.draw(key, shape, shape[0], jnp.bfloat16, offset
                        ).astype(jnp.float32)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _layer(x, keys, offsets, *, mkey, q8):
    """One layer over ``x``: [B, S, d_model]."""
    m = dict(mkey)
    shapes = _layer_shapes(m)
    w = {n: _draw(m, keys[i], offsets[i], shapes[n])
         for i, n in enumerate(LAYER_LEAVES)}
    b, s = x.shape[:2]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(s, dtype=jnp.int32)
    h = _rms(x, m["norm_eps"])
    q = _mm(h, w["attn/wq"], q8).reshape(b, s, hq, hd)
    k = _mm(h, w["attn/wk"], q8).reshape(b, s, hkv, hd)
    v = _mm(h, w["attn/wv"], q8).reshape(b, s, hkv, hd)
    q = _rope(q, pos, m["rope_theta"], m["partial_rotary"])
    k = _rope(k, pos, m["rope_theta"], m["partial_rotary"])
    g = hq // hkv
    k = jnp.repeat(k, g, axis=2)                       # head h reads h // g
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for q0 in range(0, s, BUCKET):
        qb = q[:, q0:q0 + BUCKET]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                        precision=HIGHEST) * hd ** -0.5
        mask = pos[None, :] <= (q0 + jnp.arange(qb.shape[1]))[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST))
    attn = jnp.concatenate(outs, axis=1).reshape(b, s, hq * hd)
    x = x + _mm(attn, w["attn/wo"], q8)
    h = _rms(x, m["norm_eps"])
    f = jax.nn.silu(_mm(h, w["mlp/w_gate"], q8)) * _mm(h, w["mlp/w_up"], q8)
    return x + _mm(f, w["mlp/w_down"], q8)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _embed(tokens, key, *, mkey, q8):
    m = dict(mkey)
    table = weights.draw(key, (m["vocab_size"], m["d_model"]),
                         m["vocab_size"], jnp.bfloat16).astype(jnp.float32)
    return table[tokens]


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _head(x, key, served, *, mkey, q8):
    """Per row and position: (best logit, served token's logit, argmax)."""
    m = dict(mkey)
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


def _hidden(m, seed, tokens, q8: bool):
    """The last layer's output for every row and position."""
    mkey = _mkey(m)
    x = _embed(jnp.asarray(tokens), np.uint32(weights.leaf_key(seed, "embed")),
               mkey=mkey, q8=q8)
    shapes = _layer_shapes(m)
    keys = np.asarray([weights.leaf_key(seed, f"blocks/pos0/{n}")
                       for n in LAYER_LEAVES], np.uint32)
    for layer in range(m["n_layers"]):
        offs = np.asarray([layer * math.prod(shapes[n])
                           for n in LAYER_LEAVES], np.uint32)
        x = _layer(x, keys, offs, mkey=mkey, q8=q8)
    return x


def _mkey(m: Dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str))))


def batches(lengths: Sequence[int]) -> List[Tuple[int, List[int]]]:
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
