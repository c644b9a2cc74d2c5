"""Plain reference for the hybrid Mamba-2 / MoE / GQA stack (``nemotron_h``,
NVIDIA-Nemotron-3-Nano-30B-A3B) as the hybrid serving cell runs it.

Float32 throughout, matmuls at ``Precision.HIGHEST``, no cache, no kernel:
one causal forward over whole sequences, a layer at a time, the Mamba-2
mixer as a sequential recurrence over time (not the chunked scan), each
layer's weights regenerated from the seed by :func:`leaf` (nothing the
program made is read).  Sequences of one length bucket go through together
as the rows of one batch; a row never reads another.  The equations
(``modeling_nemotron_h`` and the published ``config.json``), one block per
character of ``layer_pattern``, each ``x += mixer(rms(x))``:

* ``x = embed[tokens]``;
* ``M`` (Mamba-2, 64 heads of 64, state 128, 8 groups, conv 4):
  ``[z, xBC, dt] = h W_in``; ``xBC`` through the causal depthwise conv
  (4 taps, bias) and SiLU, split into ``x`` (4,096), ``B``, ``C`` (8 groups
  of 128); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  ``i`` (group ``i // 8``): ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
  ``y_t = h_t C_t + D x_t``; ``y silu(z)`` RMS-normalised over each group
  of 512 and times ``gate_norm``; ``W_out``;
* ``E`` (routed experts): ``s = sigmoid(h W_router)`` over the 128 experts;
  the 6 with the highest ``s + e_score_correction_bias`` are chosen, with
  gates their ``s`` renormalised to sum 1, times 2.5; the FFN is the gated
  sum of the chosen experts' ``relu(h W_up)^2 W_down`` (1,856 wide) plus
  the shared expert's (3,712 wide);
* ``*`` (GQA, no rotary): 32 query and 2 key/value heads of 128, causal
  softmax with scale ``128 ** -0.5``, query head ``i`` reading key/value
  head ``i // 16``; ``W_o``;
* ``logits = rms(x) W_lm``.

The checkpoint: layer ``i``'s leaves are ``blocks/pos{i}/...`` (the program
scans the pattern as one group); RMSNorm gains (``gate_norm`` too) and
``D`` are ones; :func:`leaf` draws the rest.  Departures from the published
model, as the program has them: the gated norm's ``y silu(z)`` is rounded
to the served type before the norm; the conv state is held in the served
type and the SSM state in float32.

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
BUCKET = 32          # sequence length bucket
TOKENS = 2048        # rows x padded length of a batch, at most
QBLOCK = 256         # query block of the scores, position block of logits
GAINS = ("final_norm", "gate_norm", "D")
MAMBA = ("w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias", "gate_norm",
         "w_out")
GQA = ("wq", "wk", "wv", "wo")
RELU2 = ("w_up", "w_down")


# ---------------------------------------------------------------------------
# The seeded checkpoint
# ---------------------------------------------------------------------------

def _uniform(key, shape, offset):
    """Uniform on [0, 1), by element, from the same hash as every weight."""
    return (weights.draw(key, shape, 3, jnp.float32, offset) + 1.0) / 2.0


def leaf(m: Dict, key, name: str, shape, dtype, offset=0):
    """Elements ``offset ..`` of the checkpoint's leaf ``name``, in
    ``shape``; traceable.

    * RMSNorm gains (``ln_*``, ``final_norm``, ``gate_norm``) and ``D``:
      ones;
    * ``A_log``: ``log A``, ``A`` uniform on [1, 16] (Mamba-2's init);
    * ``dt_bias``: the inverse softplus of ``dt``, log-uniform on
      [``time_step_min``, ``time_step_max``] = [0.001, 0.1], floored at
      ``time_step_floor`` 1e-4 (Mamba-2's init);
    * ``conv_b``: uniform, standard deviation ``1 / sqrt(d_conv)``;
    * ``e_score_correction_bias``: uniform, standard deviation
      ``1 / sqrt(n_experts)``, so that it changes some choices;
    * every other leaf ``[..., fan_in, fan_out]``: ``weights.draw``.
    """
    last = name.rsplit("/", 1)[-1]
    if last.startswith("ln_") or last in GAINS:
        return jnp.ones(shape, dtype)
    if last == "A_log":
        return jnp.log(1.0 + 15.0 * _uniform(key, shape, offset)).astype(dtype)
    if last == "dt_bias":
        lo, hi = math.log(1e-3), math.log(1e-1)
        dt = jnp.exp(lo + (hi - lo) * _uniform(key, shape, offset))
        dt = jnp.maximum(dt, 1e-4)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if last == "conv_b":
        return weights.draw(key, shape, m["ssm"]["d_conv"], dtype, offset)
    if last == "e_score_correction_bias":
        return weights.draw(key, shape, m["moe"]["n_experts"], dtype, offset)
    return weights.draw(key, shape, shape[-2], dtype, offset)


def _w(m, key, name, shape, offset=0):
    """A leaf as served (bfloat16), in float32."""
    return leaf(m, key, name, shape, jnp.bfloat16, offset).astype(jnp.float32)


def _mamba_shapes(m: Dict) -> Dict[str, Tuple[int, ...]]:
    s, d = m["ssm"], m["d_model"]
    di, gn, nh = s["heads"] * s["head_dim"], s["n_groups"] * s["d_state"], \
        s["heads"]
    return {"w_in": (d, 2 * di + 2 * gn + nh),
            "conv_w": (s["d_conv"], di + 2 * gn), "conv_b": (di + 2 * gn,),
            "A_log": (nh,), "D": (nh,), "dt_bias": (nh,), "gate_norm": (di,),
            "w_out": (di, d)}


def _gqa_shapes(m: Dict) -> Dict[str, Tuple[int, int]]:
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"wq": (d, hq * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
            "wo": (hq * hd, d)}


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


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _mamba(x, keys, *, mkey, q8):
    """``x + Mamba2(rms(x))`` over ``x`` [R, S, d], a sequential scan."""
    m = _unkey(mkey)
    s, eps = m["ssm"], m["norm_eps"]
    nh, hd, g, n, k = (s["heads"], s["head_dim"], s["n_groups"], s["d_state"],
                       s["d_conv"])
    di, gn = nh * hd, g * n
    w = {name: _w(m, keys[i], name, shape)
         for i, (name, shape) in enumerate(_mamba_shapes(m).items())}
    r, t = x.shape[:2]
    proj = _mm(_rms(x, eps), w["w_in"], q8)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * gn], \
        proj[..., 2 * di + 2 * gn:]
    pad = jnp.concatenate([jnp.zeros((r, k - 1, xbc.shape[-1])), xbc], 1)
    conv = jax.nn.silu(sum(pad[:, i:i + t] * w["conv_w"][i] for i in range(k))
                       + w["conv_b"])
    xs = conv[..., :di].reshape(r, t, nh, hd)
    grp = jnp.arange(nh) // (nh // g)
    bm = conv[..., di:di + gn].reshape(r, t, g, n)[:, :, grp]      # [R,S,H,N]
    cm = conv[..., di + gn:].reshape(r, t, g, n)[:, :, grp]
    dt = jax.nn.softplus(dt + w["dt_bias"])                        # [R,S,H]
    a = -jnp.exp(w["A_log"])

    def step(h, inp):
        xt, bt, ct, dtt = inp
        h = (jnp.exp(dtt * a)[..., None, None] * h
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        y = jnp.sum(h * ct[:, :, None, :], -1) + w["D"][:, None] * xt
        return h, y

    time_major = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    _, y = jax.lax.scan(step, jnp.zeros((r, nh, hd, n)),
                        tuple(map(time_major, (xs, bm, cm, dt))))
    y = jnp.moveaxis(y, 0, 1).reshape(r, t, g, di // g)
    gated = y * jax.nn.silu(z).reshape(r, t, g, di // g)
    gated = _rms(gated, eps).reshape(r, t, di) * w["gate_norm"]
    return x + _mm(gated, w["w_out"], q8)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _gqa(x, keys, *, mkey, q8):
    """``x + GQA(rms(x))`` over ``x`` [R, S, d], causal, no rotary."""
    m = _unkey(mkey)
    w = {name: _w(m, keys[i], name, shape)
         for i, (name, shape) in enumerate(_gqa_shapes(m).items())}
    r, s = x.shape[:2]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = _rms(x, m["norm_eps"])
    q = _mm(h, w["wq"], q8).reshape(r, s, hq, hd)
    k = jnp.repeat(_mm(h, w["wk"], q8).reshape(r, s, hkv, hd), hq // hkv, 2)
    v = jnp.repeat(_mm(h, w["wv"], q8).reshape(r, s, hkv, hd), hq // hkv, 2)
    pos = jnp.arange(s)
    outs = []
    for q0 in range(0, s, QBLOCK):
        qb = q[:, q0:q0 + QBLOCK]
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HIGHEST) \
            * hd ** -0.5
        mask = pos[None, :] <= (q0 + jnp.arange(qb.shape[1]))[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                               precision=HIGHEST))
    attn = jnp.concatenate(outs, axis=1).reshape(r, s, hq * hd)
    return x + _mm(attn, w["wo"], q8)


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _moe(x, keys, *, mkey, q8):
    """``x + MoE(rms(x))``: ``keys`` of the router, its correction bias,
    the experts' ``w_up`` and ``w_down``, the shared expert's."""
    m = _unkey(mkey)
    mo, d = m["moe"], m["d_model"]
    r, s = x.shape[:2]
    h = _rms(x, m["norm_eps"]).reshape(r * s, d)
    e, f, fs = mo["n_experts"], mo["d_expert"], mo["d_shared"] * mo["n_shared"]
    score = jax.nn.sigmoid(_mm(h, _w(m, keys[0], "router", (d, e)), q8))
    bias = _w(m, keys[1], "e_score_correction_bias", (e,))
    _, ids = jax.lax.top_k(score + bias, mo["top_k"])
    gate = jnp.take_along_axis(score, ids, -1)
    gate = mo["router_scale"] * gate / jnp.sum(gate, -1, keepdims=True)
    gates = jnp.zeros((r * s, e)).at[jnp.arange(r * s)[:, None], ids].set(gate)

    def relu2(w_up, w_down):
        return _mm(jnp.square(jax.nn.relu(_mm(h, w_up, q8))), w_down, q8)

    def expert(acc, i):
        up = _w(m, keys[2], "w_up", (d, f), i * d * f)
        down = _w(m, keys[3], "w_down", (f, d), i * f * d)
        g = jax.lax.dynamic_index_in_dim(gates, i, 1)
        return acc + g * relu2(up, down), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(e, dtype=jnp.uint32))
    y = y + relu2(_w(m, keys[4], "w_up", (d, fs)),
                  _w(m, keys[5], "w_down", (fs, d)))
    return x + y.reshape(r, s, d)


@partial(jax.jit, static_argnames=("mkey",))
def _embed(tokens, key, *, mkey):
    m = _unkey(mkey)
    return _w(m, key, "embed", (m["vocab_size"], m["d_model"]))[tokens]


@partial(jax.jit, static_argnames=("mkey", "q8"))
def _head(x, key, served, *, mkey, q8):
    """Per row and position: (best logit, served token's logit, argmax)."""
    m = _unkey(mkey)
    w = _w(m, key, "lm_head", (m["d_model"], m["vocab_size"]))
    x = _rms(x, m["norm_eps"])
    best, got, top = [], [], []
    for r0 in range(0, x.shape[1], QBLOCK):
        lg = _mm(x[:, r0:r0 + QBLOCK], w, q8)
        best.append(lg.max(axis=-1))
        got.append(jnp.take_along_axis(lg, served[:, r0:r0 + QBLOCK, None],
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
    """The last block's output for every row and position."""
    mkey = _mkey(m)
    x = _embed(jnp.asarray(tokens), np.uint32(weights.leaf_key(seed, "embed")),
               mkey=mkey)
    for i, kind in enumerate(m["layer_pattern"]):
        pre = f"blocks/pos{i}/"
        if kind == "M":
            x = _mamba(x, _keys(seed, [pre + "mamba/" + n for n in MAMBA]),
                       mkey=mkey, q8=q8)
        elif kind == "E":
            names = (["moe/router", "moe/e_score_correction_bias"]
                     + [f"moe/experts/{n}" for n in RELU2]
                     + [f"moe/shared/{n}" for n in RELU2])
            x = _moe(x, _keys(seed, [pre + n for n in names]), mkey=mkey,
                     q8=q8)
        elif kind == "*":
            x = _gqa(x, _keys(seed, [pre + "attn/" + n for n in GQA]),
                     mkey=mkey, q8=q8)
        else:
            raise ValueError(f"no block {kind!r} in this reference")
    return x


def _mkey(m: Dict):
    """A hashable key of the model block (nested blocks as tuples)."""
    return tuple(sorted((k, _mkey(v) if isinstance(v, dict) else v)
                        for k, v in m.items()
                        if isinstance(v, (int, float, str, dict))))


def _unkey(mkey) -> Dict:
    return {k: _unkey(v) if isinstance(v, tuple) else v for k, v in mkey}


def batches(lengths: Sequence[int]) -> List[Tuple[int, int, List[int]]]:
    """Sequences grouped by length bucket: ``(padded length, rows,
    indices)``, ``rows`` a power of two with ``rows x length`` at most
    :data:`TOKENS` (or one row), the same for every batch of a bucket."""
    by_len: Dict[int, List[int]] = {}
    for i, n in enumerate(lengths):
        by_len.setdefault(-(-n // BUCKET) * BUCKET, []).append(i)
    out = []
    for s, idx in sorted(by_len.items()):
        rows = 1 << max(0, (TOKENS // s).bit_length() - 1)
        rows = min(rows, 1 << (len(idx) - 1).bit_length())
        out += [(s, rows, idx[r:r + rows]) for r in range(0, len(idx), rows)]
    return out


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
        for s, rows, idx in batches(served_n):
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
