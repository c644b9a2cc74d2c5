"""NVIDIA-Nemotron-3-Nano (``nemotron_h``) on the CPU at the smoke size,
seeded random weights, against a plain float32 reference: Mamba-2 as a
sequential recurrence over time, GQA without rotary, and relu² experts
under sigmoid routing with a correction bias.  Also the serving path's two
kinds of per-slot state: a session started in a reused slot, and a slot
left out of a step."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import decoder, moe
from repro.models.common import init_params, rms_norm
from repro.models.ssm import gated_rms_norm

ARCH = "nemotron-3-nano-30b"
CTX = decoder.RunCtx(mesh=None, use_kernel="ref")


def _cfg(**ssm_kw):
    cfg = dataclasses.replace(get_smoke_config(ARCH), dtype="float32")
    return dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm, **ssm_kw))


def _params(cfg, seed=5):
    """``init_params`` with the leaves it leaves at zero or constant drawn
    too: the conv bias, the routers' correction bias (so that the biased
    choice differs from the unbiased one), A and dt spread over their
    Mamba-2 ranges."""
    params = init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def fix(path, a):
        name = path[-1].key
        if name == "e_score_correction_bias":
            return 0.2 * jax.random.normal(next(keys), a.shape, a.dtype)
        if name == "conv_b":
            return 0.3 * jax.random.normal(next(keys), a.shape, a.dtype)
        if name == "A_log":
            return jnp.log(jax.random.uniform(next(keys), a.shape, a.dtype,
                                              1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(next(keys), a.shape, a.dtype,
                                            np.log(1e-3), np.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        return a

    return jax.tree_util.tree_map_with_path(fix, params)


# ---------------------------------------------------------------------------
# The plain reference: float32, whole sequences, no cache or kernel
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _ref_mamba(p, h, cfg):
    """Mamba-2 over one sequence ``h`` [S, d]: causal depthwise conv with
    bias and SiLU; ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t``, ``y_t =
    C_t h_t + D x_t`` per head (head ``i`` reads group ``i // (H/G)``);
    ``norm(y silu(z))`` per group of ``d_inner / G``; ``out_proj``."""
    s = cfg.ssm
    di, nh, g, n, hd = (s.d_inner(cfg.d_model), s.n_heads(cfg.d_model),
                        s.n_groups, s.d_state, s.head_dim)
    proj = h @ p["w_in"]
    z, xbc, dt = (proj[:, :di], proj[:, di:2 * di + 2 * g * n],
                  proj[:, 2 * di + 2 * g * n:])
    pad = jnp.concatenate([jnp.zeros((s.d_conv - 1, xbc.shape[1])), xbc])
    conv = sum(pad[k:k + h.shape[0]] * p["conv_w"][k]
               for k in range(s.d_conv)) + p["conv_b"]
    conv = jax.nn.silu(conv)
    x, b, c = conv[:, :di], conv[:, di:di + g * n], conv[:, di + g * n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])                    # [S, H]
    a = -jnp.exp(p["A_log"])
    grp = jnp.arange(nh) // (nh // g)

    def step(state, t):
        xt = x[t].reshape(nh, hd)
        bt, ct = b[t].reshape(g, n)[grp], c[t].reshape(g, n)[grp]
        state = (jnp.exp(dt[t] * a)[:, None, None] * state
                 + dt[t][:, None, None] * xt[:, :, None] * bt[:, None, :])
        y = jnp.sum(state * ct[:, None, :], -1) + p["D"][:, None] * xt
        return state, y.reshape(di)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n)), jnp.arange(h.shape[0]))
    gated = (y * jax.nn.silu(z)).reshape(h.shape[0], g, di // g)
    gated = _rms(gated, p["gate_norm"].reshape(g, di // g), cfg.norm_eps)
    return gated.reshape(h.shape[0], di) @ p["w_out"]


def _ref_gqa(p, h, cfg):
    """Causal GQA over ``h`` [S, d], no rotary: query head ``i`` attends
    with KV head ``i // (Hq / Hkv)``."""
    s, hq, hkv, hd = h.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (h @ p["wq"]).reshape(s, hq, hd)
    k = jnp.repeat((h @ p["wk"]).reshape(s, hkv, hd), hq // hkv, axis=1)
    v = jnp.repeat((h @ p["wv"]).reshape(s, hkv, hd), hq // hkv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], sc, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
    return out.reshape(s, hq * hd) @ p["wo"]


def _relu2(w, h):
    return jnp.square(jax.nn.relu(h @ w["w_up"])) @ w["w_down"]


def _ref_moe(p, h, cfg):
    """Sigmoid scores; the top-k of ``score + bias`` chosen; gates the
    chosen scores renormalised, times ``router_scale``; the gated relu²
    experts plus the shared relu² expert."""
    m = cfg.moe
    score = jax.nn.sigmoid(h @ p["router"])
    _, ids = jax.lax.top_k(score + p["e_score_correction_bias"], m.top_k)
    gate = jnp.take_along_axis(score, ids, -1)
    gate = m.router_scale * gate / gate.sum(-1, keepdims=True)
    out = _relu2(p["shared"], h)
    for e in range(m.n_experts):
        w = jnp.sum(jnp.where(ids == e, gate, 0.0), -1)
        out = out + w[:, None] * _relu2(
            {k: v[0, e] for k, v in p["experts"].items()}, h)
    return out


def _ref_logits(params, cfg, tokens):
    """Logits [S, V] of one sequence through the whole pattern."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)]
        for i, kind in enumerate(cfg.layer_pattern):
            lp = jax.tree.map(lambda a: a[0], p["blocks"][f"pos{i}"])
            if kind == "M":
                x = x + _ref_mamba(lp["mamba"], _rms(x, lp["ln_mix"],
                                                     cfg.norm_eps), cfg)
            elif kind == "E":
                x = x + _ref_moe(lp["moe"], _rms(x, lp["ln_mlp"],
                                                 cfg.norm_eps), cfg)
            else:
                x = x + _ref_gqa(lp["attn"], _rms(x, lp["ln_attn"],
                                                  cfg.norm_eps), cfg)
        return np.asarray(_rms(x, p["final_norm"], cfg.norm_eps)
                          @ p["lm_head"])


def _tokens(cfg, b, s, seed=6):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (b, s), 0,
                                         cfg.vocab_size))


# ---------------------------------------------------------------------------
# Prefill, then decode through the cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernel,n_groups", [("ref", 2), ("pallas", 1)])
def test_prefill_then_decode_matches_reference(use_kernel, n_groups):
    """The smoke pattern ``MEMEM*E``: prefill 32 tokens, then three decode
    steps through ``decoder.decode_step``, against the reference's full
    forward.  ``ref`` takes the chunked SSD (``ssd_chunked``) with two
    groups; ``pallas`` interprets the ``ssd_scan`` kernel (one group, as it
    requires), the flash attention kernel and the grouped-matmul kernel."""
    cfg = _cfg(n_groups=n_groups)
    ctx = decoder.RunCtx(mesh=None, use_kernel=use_kernel)
    params = _params(cfg)
    b, s, steps = 2, 32, 3
    tokens = _tokens(cfg, b, s + steps)
    want = np.stack([_ref_logits(params, cfg, tokens[r]) for r in range(b)])

    logits, caches = decoder.prefill(cfg, ctx, params,
                                     {"tokens": jnp.asarray(tokens[:, :s])})
    np.testing.assert_allclose(np.asarray(logits), want[:, s - 1],
                               rtol=2e-3, atol=2e-3)
    ring = decoder.init_cache(cfg, b, s + 8, jnp.float32)
    caches = jax.tree.map(
        lambda d, c: jax.lax.dynamic_update_slice(
            d, c.astype(d.dtype), (0,) * d.ndim), ring, caches)
    for i in range(steps):
        logits, caches = decoder.decode_step(
            cfg, ctx, params, caches, jnp.asarray(tokens[:, s + i]),
            jnp.full((b,), s + i, jnp.int32))
        np.testing.assert_allclose(np.asarray(logits), want[:, s + i],
                                   rtol=2e-3, atol=2e-3, err_msg=str(i))


def test_decode_from_empty_cache_matches_reference():
    """The serving path's form: every position decoded through the cache,
    from a zeroed cache, slots at different depths."""
    cfg = _cfg()
    params = _params(cfg, seed=8)
    tokens = _tokens(cfg, 2, 12, seed=9)
    caches = decoder.init_cache(cfg, 3, 16, jnp.float32)
    got = [[], []]
    for t in range(12):
        # slot 0 runs sequence 0 from step 0; slot 2 runs sequence 1 from
        # step 2 (slot 1 sits every step out)
        pos = np.array([t, -1, t - 2], np.int32)
        tok = np.array([tokens[0, t], 0, tokens[1, max(t - 2, 0)]], np.int32)
        logits, caches = decoder.decode_step(cfg, CTX, params, caches,
                                             jnp.asarray(tok), jnp.asarray(pos))
        got[0].append(np.asarray(logits[0]))
        if t >= 2:
            got[1].append(np.asarray(logits[2]))
    for r, n in ((0, 12), (1, 10)):
        np.testing.assert_allclose(np.stack(got[r]),
                                   _ref_logits(params, cfg, tokens[r, :n]),
                                   rtol=2e-3, atol=2e-3, err_msg=str(r))


# ---------------------------------------------------------------------------
# Sigmoid routing with a correction bias
# ---------------------------------------------------------------------------

def _np_route(logits, bias, top_k, scale):
    score = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = score + bias
    ids = np.stack([sorted(range(len(c)), key=lambda e: (-c[e], e))[:top_k]
                    for c in choice])
    gate = np.take_along_axis(score, ids, 1)
    return ids, scale * gate / gate.sum(1, keepdims=True)


def test_sigmoid_bias_routing_matches_numpy():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64, 16)).astype(np.float32)
    bias = (0.1 * rng.normal(size=16)).astype(np.float32)
    gates, ids = moe.router_topk(jnp.asarray(logits), 6, True, 2.5,
                                 scoring="sigmoid", bias=jnp.asarray(bias))
    want_ids, want_gates = _np_route(logits, bias, 6, 2.5)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-6)
    unbiased = _np_route(logits, np.zeros(16), 6, 2.5)[0]
    assert (np.sort(want_ids, 1) != np.sort(unbiased, 1)).any()


def test_bias_changes_the_choice_but_not_the_gates():
    """Expert 3 scores lowest, but its bias puts it among the top 2: it is
    chosen, and its gate is its own unbiased score's share."""
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]], jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.9], jnp.float32)
    s = jax.nn.sigmoid(logits[0])
    gates, ids = moe.router_topk(logits, 2, True, 2.5, scoring="sigmoid",
                                 bias=bias)
    assert ids.tolist() == [[3, 0]]
    want = 2.5 * jnp.asarray([s[3], s[0]]) / (s[3] + s[0])
    np.testing.assert_allclose(np.asarray(gates[0]), np.asarray(want),
                               rtol=1e-6)
    _, plain = moe.router_topk(logits, 2, True, 2.5, scoring="sigmoid")
    assert plain.tolist() == [[0, 1]]


# ---------------------------------------------------------------------------
# The relu² expert share
# ---------------------------------------------------------------------------

def _moe_params(cfg, layer=1):
    return jax.tree.map(lambda a: a[0],
                        _params(cfg)["blocks"][f"pos{layer}"]["moe"])


@pytest.mark.parametrize("interpret", [None, True])
def test_relu2_share_matches_moe_ref(interpret):
    """Both grouped-matmul paths (``ragged_dot``, the interpreted kernel)
    give ``moe_ref``'s relu² layer, and ``moe_ref`` the reference's."""
    cfg = _cfg()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, cfg.d_model))
    y, stats = moe.moe_share(p, x, cfg, interpret=interpret)
    ref = moe.moe_ref(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert int(stats["moe_pairs"]) == 40 * cfg.moe.top_k
    assert set(p["experts"]) == {"w_up", "w_down"}
    with jax.default_matmul_precision("highest"):
        plain = _ref_moe(p, x.reshape(-1, cfg.d_model), cfg)
    np.testing.assert_allclose(np.asarray(ref).reshape(plain.shape),
                               np.asarray(plain), rtol=1e-4, atol=1e-4)


def test_relu2_share_reads_stacked_experts_in_place_like_its_slice():
    """A stack of two layers' relu² experts and a layer index give that
    layer's share, as its slice does (``ragged_dot`` and the kernel)."""
    cfg = _cfg()
    layers = [_moe_params(cfg, 1), _moe_params(cfg, 3)]
    stack = jax.tree.map(lambda *w: jnp.stack(w),
                         *[p["experts"] for p in layers])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 10, cfg.d_model))
    for interpret in (None, True):
        for layer, p in enumerate(layers):
            y0, s0 = moe.moe_share(p, x, cfg, interpret=interpret)
            y1, s1 = moe.moe_share(dict(p, experts=stack), x, cfg,
                                   interpret=interpret,
                                   layer=jnp.int32(layer))
            np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
            assert int(s1["moe_pairs"]) == int(s0["moe_pairs"])


def test_kernel_reads_a_misaligned_weight_transposed():
    """A weight whose contraction dim is lane-aligned and whose output dim
    is not (nemotron's ``w_up``, 2688 x 1856) goes to the grouped-matmul
    kernel transposed; interpreted, it gives ``ragged_dot``'s product."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    lhs = jax.random.normal(k1, (40, 256), jnp.float32)
    rhs = jax.random.normal(k2, (4, 256, 96), jnp.float32)
    sizes = jnp.asarray([7, 0, 20, 13], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(moe._grouped(lhs, rhs, sizes, True)),
        np.asarray(moe._grouped(lhs, rhs, sizes, None)), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# Per-slot state: a reused slot, a slot left out of a step
# ---------------------------------------------------------------------------

def _decode(cfg, params, caches, tokens, pos):
    return decoder.decode_step(cfg, CTX, params, caches,
                               jnp.asarray(tokens, jnp.int32),
                               jnp.asarray(pos, jnp.int32))


def test_reused_slot_at_position_0_decodes_as_a_fresh_cache():
    """Slot 1 serves one session for five steps, then a new one from
    position 0: the new session's logits are those of a fresh cache."""
    cfg = _cfg()
    params = _params(cfg)
    toks = _tokens(cfg, 2, 6, seed=11)
    caches = decoder.init_cache(cfg, 2, 16, jnp.float32)
    for t in range(5):
        _, caches = _decode(cfg, params, caches, [toks[0, t], toks[0, t]],
                            [t, t])
    fresh = decoder.init_cache(cfg, 2, 16, jnp.float32)
    for t in range(6):
        got, caches = _decode(cfg, params, caches, [toks[0, 5], toks[1, t]],
                              [5 + t, t])
        want, fresh = _decode(cfg, params, fresh, [0, toks[1, t]], [-1, t])
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   rtol=1e-5, atol=1e-5, err_msg=str(t))


@pytest.mark.parametrize("arch", [ARCH, "glm4-9b", "deepseek-v2-236b"])
def test_slot_left_out_of_a_step_keeps_its_state(arch):
    """A slot at a negative position: every one of its state leaves and K/V
    rows is unchanged, bit for bit, and its next step matches the
    reference (nemotron) or the step it would have made had it never sat
    out (every model)."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = _params(cfg) if arch == ARCH else init_params(
        cfg, jax.random.PRNGKey(5))
    toks = _tokens(cfg, 2, 6, seed=12)
    caches = decoder.init_cache(cfg, 2, 16, jnp.float32)
    twin = decoder.init_cache(cfg, 2, 16, jnp.float32)
    for t in range(4):
        _, caches = _decode(cfg, params, caches, toks[:, t], [t, t])
        _, twin = _decode(cfg, params, twin, toks[:, t], [t, t])
    before = jax.tree.map(lambda a: np.array(a, copy=True), caches)
    # slot 1 sits out two steps while slot 0 goes on
    for t in (4, 5):
        _, caches = _decode(cfg, params, caches, [toks[0, t], 7], [t, -1])
    for key, bdim in (("prefix", 0), ("body", 1), ("suffix", 0)):
        for a, b in zip(jax.tree.leaves(before[key]),
                        jax.tree.leaves(caches[key])):
            np.testing.assert_array_equal(np.take(a, 1, bdim),
                                          np.take(np.asarray(b), 1, bdim))
    got, _ = _decode(cfg, params, caches, [0, toks[1, 4]], [-1, 4])
    want, _ = _decode(cfg, params, twin, [0, toks[1, 4]], [-1, 4])
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    if arch == ARCH:
        ref = _ref_logits(params, cfg, toks[1, :5])[-1]
        np.testing.assert_allclose(np.asarray(got[1]), ref, rtol=2e-3,
                                   atol=2e-3)


# ---------------------------------------------------------------------------
# What stays as it was
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_group_norm_is_the_old_norm_bit_for_bit(dtype):
    ky, kz, kw = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(ky, (3, 5, 64), dtype)
    z = jax.random.normal(kz, (3, 5, 64), dtype)
    w = 1.0 + 0.1 * jax.random.normal(kw, (64,), dtype)
    old = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                   w, 1e-5)
    np.testing.assert_array_equal(
        np.asarray(gated_rms_norm(y, z, w, 1, 1e-5).astype(jnp.float32)),
        np.asarray(old.astype(jnp.float32)))


def test_grouped_norm_normalises_each_group():
    y = jnp.concatenate([jnp.ones((1, 4)), 10 * jnp.ones((1, 4))], -1)
    z = 50.0 * jnp.ones((1, 8))                      # silu(z) = z
    out = gated_rms_norm(y, z, jnp.ones((8,)), 2, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.ones((1, 8)), rtol=1e-6)


# sha256 of the float32 logits of four decode steps of three slots at
# different depths, as the code gave them before nemotron_h's changes
SMOKE_LOGITS = {
    "glm4-9b": "131e07684ca2a03cfa67e91322ae1bda2b8d2bc3c057f6522a00066d4caeca39",
    "deepseek-v2-236b":
        "6fe126f701b700f341bc735e58df120e586ead602b5248a4f7ae5f9279253b36",
}


@pytest.mark.parametrize("arch", sorted(SMOKE_LOGITS))
def test_smoke_decode_logits_unchanged_bit_for_bit(arch):
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(7))
    caches = decoder.init_cache(cfg, 3, 16, jnp.float32)
    tok = jnp.asarray([3, 5, 9], jnp.int32)
    out = []
    for i in range(4):
        logits, caches = _decode(cfg, params, caches, tok, [i, i + 2, 2 * i])
        out.append(np.asarray(logits))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    digest = hashlib.sha256(np.stack(out).tobytes()).hexdigest()
    assert digest == SMOKE_LOGITS[arch]
