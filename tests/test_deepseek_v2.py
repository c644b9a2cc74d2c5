"""DeepSeek-V2's parts on the CPU at small sizes, seeded random weights:
group-limited routing, the one-device expert share, YaRN rotary, and the
smoke model's prefill and cached decode against a plain float64 forward."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import decoder, moe
from repro.models.attention import mla_softmax_scale
from repro.models.common import (YarnScaling, apply_rope, init_params,
                                 rope_freqs)

CTX = decoder.RunCtx(mesh=None, use_kernel="ref")


def _smoke(**moe_kw):
    cfg = dataclasses.replace(get_smoke_config("deepseek-v2-236b"),
                              dtype="float32")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                            **moe_kw))


# ---------------------------------------------------------------------------
# Group-limited routing
# ---------------------------------------------------------------------------

def _np_route(probs, top_k, n_group, topk_group):
    """Plain group-limited greedy routing: per token, the groups ordered by
    their best score (lower index first on ties), the best ``topk_group``
    kept, then the ``top_k`` best experts among theirs."""
    t, e = probs.shape
    size = e // n_group
    ids = np.zeros((t, top_k), np.int64)
    for r in range(t):
        best = [max(probs[r, g * size:(g + 1) * size]) for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: (-best[g], g))
        kept = set(groups[:topk_group])
        cand = sorted((-probs[r, i], i) for i in range(e) if i // size in kept)
        ids[r] = [i for _, i in cand[:top_k]]
    return ids


def _ties_at_group_edges():
    """Logits whose groups tie on their best score, and whose ties sit on
    the edges between groups."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(6, 16)).astype(np.float32)
    x[0, 3] = x[0, 4] = 5.0          # groups 0 and 1 tie at their shared edge
    x[1, 7] = x[1, 8] = x[1, 12] = 4.0   # groups 1, 2, 3 tie; 2 are kept
    x[2, :] = 0.0                    # every group ties
    x[3, 4:8] = 3.0                  # a whole group of equal scores
    x[4, 15] = x[4, 0] = 2.5         # the first and the last expert tie
    return x


@pytest.mark.parametrize("case", ["random", "ties"])
@pytest.mark.parametrize("n_group,topk_group,top_k",
                         [(4, 2, 3), (4, 1, 2), (8, 3, 2), (2, 1, 4)])
def test_group_routing_matches_numpy(case, n_group, topk_group, top_k):
    if case == "random":
        logits = np.random.default_rng(n_group * 7 + top_k).normal(
            size=(32, 16)).astype(np.float32)
    else:
        logits = _ties_at_group_edges()
    gates, ids = moe.router_topk(jnp.asarray(logits), top_k, norm_topk=False,
                                 router_scale=16.0, n_group=n_group,
                                 topk_group=topk_group)
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    want = _np_route(probs, top_k, n_group, topk_group)
    np.testing.assert_array_equal(np.asarray(ids), want)
    np.testing.assert_allclose(np.asarray(gates),
                               16.0 * np.take_along_axis(probs, want, 1),
                               rtol=1e-6)


def test_one_group_is_plain_topk_bit_for_bit():
    logits = jnp.asarray(np.random.default_rng(1).normal(size=(64, 8)),
                         jnp.float32)
    for norm in (True, False):
        probs = jax.nn.softmax(logits, axis=-1)
        vals, ids = jax.lax.top_k(probs, 2)
        if norm:
            vals = vals / jnp.maximum(jnp.sum(vals, -1, keepdims=True), 1e-9)
        gates, got = moe.router_topk(logits, 2, norm, 1.5)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ids))
        np.testing.assert_array_equal(np.asarray(gates),
                                      np.asarray(vals * 1.5))


# ---------------------------------------------------------------------------
# The expert share
# ---------------------------------------------------------------------------

def _moe_params(cfg, seed=0):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a[0], params["blocks"]["pos0"]["moe"])


def _tokens(cfg, n=24, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, n // 2, cfg.d_model),
                             jnp.float32)


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "mixtral-8x7b"])
def test_share_of_every_expert_is_moe_ref(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    p, x = _moe_params(cfg), _tokens(cfg)
    y, stats = moe.moe_share(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(moe.moe_ref(p, x, cfg)),
                               rtol=1e-5, atol=1e-5)
    assert int(stats["moe_pairs"]) == x.shape[0] * x.shape[1] * cfg.moe.top_k


def test_shares_of_all_groups_add_up_to_the_uncut_layer():
    """Each group's share computed alone, with the shared expert that every
    device computes alike counted once, sums to the whole layer."""
    full = _smoke()
    p, x = _moe_params(full), _tokens(full)
    m = full.moe
    size = m.n_experts // m.n_group
    shared = moe.mlp_apply(p["shared"], x.reshape(-1, full.d_model), "swiglu")
    total, pairs = jnp.zeros_like(x), 0
    for g in range(m.n_group):
        cfg = _smoke(held_first=g * size, n_held=size)
        pg = dict(p, experts=jax.tree.map(
            lambda w: w[:, g * size:(g + 1) * size], p["experts"]))
        y, stats = moe.moe_share(pg, x, cfg)
        total = total + y - shared.reshape(x.shape)
        pairs += int(stats["moe_pairs"])
        assert int(stats["moe_experts"]) <= size
    total = total + shared.reshape(x.shape)
    np.testing.assert_allclose(np.asarray(total),
                               np.asarray(moe.moe_ref(p, x, full)),
                               rtol=1e-5, atol=1e-5)
    assert pairs == x.shape[0] * x.shape[1] * m.top_k


def test_absent_experts_contribute_nothing():
    """A share whose experts no token picks returns the shared expert alone."""
    cfg = _smoke(held_first=6, n_held=2)
    p = _moe_params(_smoke())
    p = dict(p, experts=jax.tree.map(lambda w: w[:, 6:8], p["experts"]),
             router=p["router"].at[:, 6:].set(-1e4))
    x = jnp.abs(_tokens(cfg))       # every column of -1e4 scores far below
    y, stats = moe.moe_share(p, x, cfg)
    shared = moe.mlp_apply(p["shared"], x.reshape(-1, cfg.d_model), "swiglu")
    np.testing.assert_allclose(np.asarray(y).reshape(shared.shape),
                               np.asarray(shared), rtol=1e-6, atol=1e-6)
    assert int(stats["moe_pairs"]) == 0 and int(stats["moe_experts"]) == 0


def test_grouped_kernel_interpreted_matches_ragged_dot():
    """The TPU grouped-matmul kernel (interpreted here) and ``ragged_dot``
    give the share the same result."""
    cfg = _smoke(held_first=2, n_held=4)
    p = _moe_params(_smoke())
    p = dict(p, experts=jax.tree.map(lambda w: w[:, 2:6], p["experts"]))
    x = _tokens(cfg, n=40)
    y0, s0 = moe.moe_share(p, x, cfg, interpret=None)
    y1, s1 = moe.moe_share(p, x, cfg, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    assert int(s0["moe_pairs"]) == int(s1["moe_pairs"]) > 0


# ---------------------------------------------------------------------------
# YaRN rotary
# ---------------------------------------------------------------------------

def _rope_before(x, positions, theta, partial=1.0):
    """``apply_rope`` as it was before YaRN scaling existed."""
    d = x.shape[-1]
    rot = int(d * partial)
    rot -= rot % 2
    xr, xp = x[..., :rot], x[..., rot:]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, xp], axis=-1) if rot < d else out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("partial", [1.0, 0.5])
def test_rope_without_scaling_is_unchanged_bit_for_bit(dtype, partial):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 64), dtype)
    pos = jnp.broadcast_to(jnp.arange(9, dtype=jnp.int32) * 37, (2, 9))
    np.testing.assert_array_equal(
        np.asarray(apply_rope(x, pos, 1e4, partial).astype(jnp.float32)),
        np.asarray(_rope_before(x, pos, 1e4, partial).astype(jnp.float32)))


def test_yarn_frequencies_and_softmax_scale():
    """DeepSeek-V2's published YaRN: correction range from beta 32 / 1 at
    4096 positions, the ramp between interpolated and original frequencies,
    and the softmax scale ``192 ** -0.5 * mscale(40, 0.707) ** 2``."""
    y = get_config("deepseek-v2-236b").rope_scaling
    dim, base = 64, 1e4
    got = np.asarray(rope_freqs(dim, base, y), np.float64)
    orig = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = orig / 40 * ramp + orig * (1 - ramp)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(orig[0]) and got[-1] == pytest.approx(orig[-1] / 40)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
    cfg = get_config("deepseek-v2-236b")
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2)
    # mscale / mscale_all_dim = 1: cos and sin keep their unit amplitude
    x = jnp.ones((1, 5, 1, dim), jnp.float32)
    pos = jnp.arange(5, dtype=jnp.int32)[None]
    r = np.asarray(apply_rope(x, pos, base, scaling=y))
    np.testing.assert_allclose(np.linalg.norm(r, axis=-1),
                               np.sqrt(dim) * np.ones((1, 5, 1)), rtol=1e-5)
    half = np.asarray(apply_rope(x, pos, base, scaling=YarnScaling(
        factor=40.0, mscale=1.0, mscale_all_dim=0.5)))
    amp = (0.1 * math.log(40) + 1) / (0.05 * math.log(40) + 1)
    np.testing.assert_allclose(half[0, 0], amp * np.ones((1, dim)), rtol=1e-5)


def test_glm4_smoke_decode_logits_unchanged():
    """The dense GQA decode path, which the glm4 cell runs, gives the
    logits it gave before group routing, the expert share and YaRN."""
    cfg = dataclasses.replace(get_smoke_config("glm4-9b"), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    caches = decoder.init_cache(cfg, 2, 16, jnp.float32)
    tok = jnp.asarray([3, 5], jnp.int32)
    for i in range(3):
        logits, caches = decoder.decode_step(
            cfg, CTX, params, caches, tok, jnp.asarray([i, i + 2], jnp.int32))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    before = [[-0.762416660785675, -0.3624013662338257, 0.32605645060539246,
               -0.8168065547943115, 0.11997827887535095, 1.4441919326782227],
              [-0.41374948620796204, 0.9699469208717346, -1.0850765705108643,
               -0.6201607584953308, -1.4458528757095337, -0.6577637195587158]]
    np.testing.assert_allclose(np.asarray(logits)[:, :6], before, rtol=1e-6,
                               atol=1e-6)
    assert float(np.abs(np.asarray(logits)).sum()) == pytest.approx(
        381.1627197265625, rel=1e-6)
    assert tok.tolist() == [33, 165]


# ---------------------------------------------------------------------------
# The smoke model through prefill and the cache, against a plain forward
# ---------------------------------------------------------------------------

def _np_rope(x, pos, cfg):
    """x [S, H, D]; YaRN frequencies, halves rotated, float64."""
    d = x.shape[-1]
    inv = np.asarray(rope_freqs(d, cfg.rope_theta, cfg.rope_scaling), np.float64)
    ang = pos[:, None] * inv[None]
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _np_rms(x, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)


def _np_mla(p, h, cfg):
    """Un-absorbed MLA over a whole sequence ``h`` [S, d], causal."""
    m, s, nh = cfg.mla, h.shape[0], cfg.n_heads
    pos = np.arange(s, dtype=np.float64)
    q = (_np_rms(h @ p["wq_a"], cfg.norm_eps) @ p["wq_b"]).reshape(s, nh, -1)
    q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    kv = h @ p["wkv_a"]
    c = _np_rms(kv[:, :m.kv_lora_rank], cfg.norm_eps)
    k_pe = _np_rope(kv[:, None, m.kv_lora_rank:], pos, cfg)
    kvb = (c @ p["wkv_b"]).reshape(s, nh, -1)
    k = np.concatenate([kvb[..., :m.qk_nope_head_dim],
                        np.broadcast_to(k_pe, (s, nh, k_pe.shape[-1]))], -1)
    v = kvb[..., m.qk_nope_head_dim:]
    q = np.concatenate([q_nope, _np_rope(q_pe, pos, cfg)], -1)
    sc = np.einsum("qhd,khd->hqk", q, k) * mla_softmax_scale(cfg)
    sc = np.where(np.tril(np.ones((s, s), bool))[None], sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", pr, v).reshape(s, -1) @ p["wo"]


def _np_swiglu(p, h):
    g = h @ p["w_gate"]
    return (g / (1 + np.exp(-g)) * (h @ p["w_up"])) @ p["w_down"]


def _np_moe(p, h, cfg):
    """Group-limited routing over every expert; the held ones computed."""
    m = cfg.moe
    lg = h @ p["router"]
    probs = np.exp(lg - lg.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    ids = _np_route(probs, m.top_k, m.n_group, m.topk_group)
    first, n_held = m.held
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for e in ids[t]:
            if first <= e < first + n_held:
                w = {k: v[0, e - first] for k, v in p["experts"].items()}
                out[t] += m.router_scale * probs[t, e] * _np_swiglu(w, h[t])
    return out + _np_swiglu(p["shared"], h)


def _np_forward(params, cfg, tokens):
    """Logits of one sequence [S] through the whole stack, float64."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = p["embed"][tokens]
    layers = [p["prefix"]["layer0"]] + [
        jax.tree.map(lambda a: a[g], p["blocks"]["pos0"])
        for g in range(cfg.n_layers - 1)]
    for i, lp in enumerate(layers):
        x = x + _np_mla(lp["attn"], _np_rms(x, cfg.norm_eps), cfg)
        h = _np_rms(x, cfg.norm_eps)
        x = x + (_np_swiglu(lp["mlp"], h) if i == 0 else _np_moe(lp["moe"], h, cfg))
    return _np_rms(x, cfg.norm_eps) @ p["lm_head"]


def test_smoke_prefill_and_decode_match_plain_forward():
    """Group routing, YaRN, the shared expert, the dense first layer and a
    held share (group 1 of 4): prefill, then two decode steps through the
    cache, against the plain float64 forward of the whole sequence."""
    cfg = _smoke(held_first=2, n_held=2)
    params = init_params(cfg, jax.random.PRNGKey(5))
    b, s = 2, 12
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (b, s + 2),
                                           0, cfg.vocab_size))
    want = np.stack([_np_forward(params, cfg, tokens[r]) for r in range(b)])

    logits, caches = decoder.prefill(cfg, CTX, params,
                                     {"tokens": jnp.asarray(tokens[:, :s])})
    np.testing.assert_allclose(np.asarray(logits), want[:, s - 1],
                               rtol=2e-3, atol=2e-3)
    ring = decoder.init_cache(cfg, b, s + 4, jnp.float32)
    caches = jax.tree.map(
        lambda d, c: d if c is None else jax.lax.dynamic_update_slice(
            d, c.astype(d.dtype), (0,) * d.ndim), ring, caches)
    pairs = 0
    for i in range(2):
        logits, caches, stats = decoder.decode_step(
            cfg, CTX, params, caches, jnp.asarray(tokens[:, s + i]),
            jnp.full((b,), s + i, jnp.int32), return_stats=True)
        np.testing.assert_allclose(np.asarray(logits), want[:, s + i],
                                   rtol=2e-3, atol=2e-3)
        pairs += int(stats["moe_pairs"])
        assert 0 <= int(stats["moe_experts"]) <= 2 * (cfg.n_layers - 1)
    assert 0 <= pairs <= 2 * b * cfg.moe.top_k * (cfg.n_layers - 1)
