"""Serving-layer tests: KV store migration, locality router, engine."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.dist.locality import price_moe_dispatch, price_session_dispatch
from repro.models import decoder
from repro.models.common import init_params
from repro.serve.engine import MultiPodEngine, RealBackend, Request, SimBackend
from repro.serve.kvcache import KVStore
from repro.serve.router import LocalityRouter

CFG = dataclasses.replace(get_smoke_config("glm4-9b"), dtype="float32")
CTX = decoder.RunCtx(mesh=None, use_kernel="ref")


def test_kvstore_export_import_roundtrip():
    """A migrated session decodes identically on the destination pod."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    src, dst = KVStore(CFG, 4, 64, jnp.float32), KVStore(CFG, 4, 64, jnp.float32)
    s = src.alloc(42)
    # run a few decode steps on src to fill its cache column
    tok = jnp.zeros((4,), jnp.int32)
    pos = jnp.zeros((4,), jnp.int32)
    for t in range(3):
        logits, src.caches = decoder.decode_step(
            CFG, CTX, params, src.caches, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1
    s.length = 3
    s.last_token = int(tok[s.slot])
    logits_src, _ = decoder.decode_step(CFG, CTX, params, src.caches, tok, pos)

    blob = src.export_session(42)
    s2 = dst.import_session(blob)
    tok2 = jnp.zeros((4,), jnp.int32).at[s2.slot].set(s.last_token)
    # position vector: only the imported slot matters
    logits_dst, _ = decoder.decode_step(
        CFG, CTX, params, dst.caches, tok2, jnp.full((4,), 3, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_dst[s2.slot]), np.asarray(logits_src[s.slot]),
        rtol=1e-4, atol=1e-4)


def test_router_lease_stickiness_and_reuse():
    r = LocalityRouter(4, policy="short")
    d1 = r.route(origin=1, sid=7, session_len=10)
    assert d1.action == "local" and d1.target == 1
    # repeated requests from the owner are local (lease reuse)
    for _ in range(5):
        assert r.route(1, 7, 10).action == "local"
    assert r.metrics.lease_reuse_rate > 0.8


def test_router_forwards_to_owner():
    r = LocalityRouter(4, policy="short")
    r.route(0, 9, 0)                      # pod 0 becomes owner
    d = r.route(2, 9, 50)                 # long session: work migrates
    assert d.action == "forward" and d.target == 0


def test_router_overload_redirects():
    r = LocalityRouter(4, policy="short")
    r.route(0, 9, 0)
    r.observe_cpu(np.array([1.0, 0.0, 0.0, 0.0]))   # owner overloaded
    d = r.route(2, 9, 4)
    assert d.target != 0                  # constraint (3) excluded the owner


def test_engine_locality_improves_throughput():
    from repro.configs import get_config
    big = get_config("mixtral-8x7b")
    out = {}
    for P in (0.1, 0.9):
        router = LocalityRouter(4, policy="short", kv_bytes_per_token=2048.0 * 32)
        eng = MultiPodEngine(4, SimBackend(big), router)
        rng = np.random.default_rng(0)
        for _ in range(40):
            for _ in range(8):
                sid = int(rng.integers(64))
                origin = sid % 4 if rng.random() < P else int(rng.integers(4))
                eng.submit(Request(sid=sid, origin=origin, n_tokens=4))
            eng.run_step()
        eng.drain()
        out[P] = eng.metrics.as_dict()["tokens_per_s"]
    assert out[0.9] > 1.1 * out[0.1]


def test_price_session_dispatch_prefers_forward_for_long_sessions():
    short = price_session_dispatch(4096, 1024, kv_state_bytes=2_000)
    long_ = price_session_dispatch(4096, 1024, kv_state_bytes=50_000_000)
    assert long_.prefer_migration          # ship the request, not 50MB of KV
    assert long_.migrate_state_s > long_.migrate_work_s


def test_price_moe_dispatch_prefers_token_a2a_at_scale():
    c = price_moe_dispatch(tokens_per_device=4096, d_model=4096, top_k=2,
                           n_experts=8, d_expert=14336, ep_degree=8)
    assert c.prefer_dispatch               # a2a of tokens beats expert a-g


def test_kvstore_roundtrip_after_slot_recycling():
    """Export → free → import still decodes right when slot indices differ
    between pods (slots are recycled on the source, pre-claimed on the dst)."""
    params = init_params(CFG, jax.random.PRNGKey(1))
    src, dst = KVStore(CFG, 4, 64, jnp.float32), KVStore(CFG, 4, 64, jnp.float32)
    # churn the source ledger so sid 42 lands on a recycled slot
    for sid in (1, 2, 3):
        src.alloc(sid)
    src.free(2)
    s = src.alloc(42)                      # reuses slot freed by sid 2
    # occupy low slots on the destination so the import gets a different one
    for sid in (7, 8):
        dst.alloc(sid)

    tok = jnp.zeros((4,), jnp.int32)
    pos = jnp.zeros((4,), jnp.int32)
    for _ in range(3):
        logits, src.caches = decoder.decode_step(
            CFG, CTX, params, src.caches, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1
    s.length, s.last_token = 3, int(tok[s.slot])
    logits_src, _ = decoder.decode_step(CFG, CTX, params, src.caches, tok, pos)

    blob = src.export_session(42)
    src.free(42)
    s2 = dst.import_session(blob)
    assert s2.slot != s.slot               # the indirection must absorb this
    assert (s2.length, s2.last_token) == (3, s.last_token)
    tok2 = jnp.zeros((4,), jnp.int32).at[s2.slot].set(s.last_token)
    logits_dst, _ = decoder.decode_step(
        CFG, CTX, params, dst.caches, tok2, jnp.full((4,), 3, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_dst[s2.slot]), np.asarray(logits_src[s.slot]),
        rtol=1e-4, atol=1e-4)


def test_kvstore_mesh_allocates_with_cache_pspecs():
    """With a mesh, the store's trees carry the dist.sharding placements."""
    from jax.sharding import Mesh, NamedSharding

    from repro.dist.sharding import cache_shardings

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    st = KVStore(CFG, 4, 32, jnp.float32, mesh=mesh)
    want = cache_shardings(CFG, mesh, st.caches, 4)
    for leaf, sh in zip(jax.tree.leaves(st.caches), jax.tree.leaves(want)):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)


def _crossover_len(r: LocalityRouter, handoff: float = 512.0) -> int:
    """session_len where forwarded work bytes == migrated state bytes."""
    work = r.request_bytes + r.response_bytes
    return int((work - handoff) / r.kv_bytes_per_token)


def test_router_priced_flips_at_byte_crossover():
    """The priced verdict alone picks the action: acquire below the byte
    crossover (KV lighter than the work description), forward above it."""
    for delta, want in ((0, "acquire"), (1, "forward")):
        r = LocalityRouter(4, policy="short", arbitration="priced",
                           kv_bytes_per_token=1.0)
        r.route(0, 5, 0)                   # pod 0 owns session 5
        d = r.route(2, 5, _crossover_len(r) + delta)
        assert d.action == want, (delta, d)
        assert d.target == (0 if want == "forward" else 2)
    # steps arbitration ignores the byte model: same inputs, always forward
    for delta in (0, 1):
        r = LocalityRouter(4, policy="short", arbitration="steps",
                           kv_bytes_per_token=1.0)
        r.route(0, 5, 0)
        assert r.route(2, 5, _crossover_len(r) + delta).action == "forward"


def test_router_hybrid_byte_model_breaks_disagreement():
    """SC step constants say forward; a featherweight KV says acquire —
    hybrid lets the byte model win and records the flip."""
    r = LocalityRouter(4, policy="short", arbitration="hybrid",
                       kv_bytes_per_token=1.0)
    r.route(0, 5, 0)
    d = r.route(2, 5, 1)                   # 1-byte KV state
    assert d.action == "acquire" and d.target == 2
    assert r.metrics.flips == 1


def test_route_decision_wire_s_set_on_every_branch():
    from repro.dist.locality import DCN_RTT_S

    r = LocalityRouter(4, policy="short", arbitration="priced",
                       kv_bytes_per_token=1.0)
    assert r.route(0, 5, 0).wire_s == 0.0              # local
    fwd = r.route(2, 5, 10**6)                         # forward to owner
    assert fwd.action == "forward" and fwd.wire_s > DCN_RTT_S
    acq = r.route(2, 6, 0)                             # new session, local
    assert acq.wire_s == 0.0
    acq = r.route(1, 5, 10)                            # tiny KV: acquire
    assert acq.action == "acquire" and acq.wire_s > DCN_RTT_S
    # both plans pay one RTT, so the gap between them is pure bytes
    assert fwd.wire_s != acq.wire_s


def test_engine_session_len_advances_once_per_sid_per_step():
    """Two queued requests on one sid must not double-advance session_len
    past the backend's cache length."""
    big = get_smoke_config("mixtral-8x7b")
    eng = MultiPodEngine(
        2, SimBackend(big), LocalityRouter(2, policy="short"))
    eng.submit(Request(sid=3, origin=0, n_tokens=2))
    eng.submit(Request(sid=3, origin=0, n_tokens=2))
    eng.run_step()
    assert eng.session_len[3] == 1
    assert eng.backend.lengths[(0, 3)] == 1
    eng.drain()
    assert eng.session_len[3] == eng.backend.lengths[(0, 3)] == 2


def test_engine_charges_priced_wire_time():
    """Wire time comes from price_session_dispatch (RTT included), not an
    ad-hoc bytes/bandwidth quotient."""
    from repro.dist.locality import DCN_RTT_S

    big = get_smoke_config("mixtral-8x7b")
    eng = MultiPodEngine(
        2, SimBackend(big),
        LocalityRouter(2, policy="short", kv_bytes_per_token=10_000.0))
    eng.submit(Request(sid=0, origin=0, n_tokens=1))   # pod 0 owns sid 0
    eng.run_step()
    base = eng.metrics.sim_time_s
    dec = eng.submit(Request(sid=0, origin=1, n_tokens=1))
    assert dec.action == "forward" and dec.wire_s >= DCN_RTT_S
    eng.run_step()
    assert eng.metrics.sim_time_s - base >= DCN_RTT_S


def test_engine_acquire_rehomes_queued_requests():
    """A lease move carries the session's pending work: requests queued on
    the old owner follow the KV cache to the acquiring pod."""
    big = get_smoke_config("mixtral-8x7b")
    eng = MultiPodEngine(
        2, SimBackend(big),
        LocalityRouter(2, policy="short", kv_bytes_per_token=1.0))
    eng.submit(Request(sid=4, origin=0, n_tokens=3))   # pod 0 owns, queues it
    dec = eng.submit(Request(sid=4, origin=1, n_tokens=3))
    assert dec.action == "acquire" and dec.target == 1  # tiny KV: state moves
    assert [r.sid for r in eng.queues[0]] == []
    assert [r.sid for r in eng.queues[1]] == [4, 4]
    eng.drain()                                        # both requests finish
    assert eng.metrics.tokens > 0 and not any(eng.queues)


def test_kvstore_roundtrip_when_n_groups_equals_n_slots():
    """The body caches' leading ``n_groups`` axis equals the slot count here
    (glm4-9b smoke has 2 scanned groups): the old shape-sniffing heuristic
    ``leaf.shape[0] != n_slots`` then picked the *group* axis as the batch
    axis and exported the wrong column.  The batch dim is now structural."""
    from repro.models.common import layer_plan

    n_slots = layer_plan(CFG).n_groups
    assert n_slots == 2                     # the collision this test needs
    params = init_params(CFG, jax.random.PRNGKey(2))
    src = KVStore(CFG, n_slots, 64, jnp.float32)
    dst = KVStore(CFG, n_slots, 64, jnp.float32)
    s = src.alloc(42)
    tok = jnp.zeros((n_slots,), jnp.int32)
    pos = jnp.zeros((n_slots,), jnp.int32)
    for _ in range(3):
        logits, src.caches = decoder.decode_step(
            CFG, CTX, params, src.caches, tok, pos)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        pos = pos + 1
    s.length, s.last_token = 3, int(tok[s.slot])
    logits_src, _ = decoder.decode_step(CFG, CTX, params, src.caches, tok, pos)

    blob = src.export_session(42)
    # the exported column must be one slot wide on the *batch* axis: body
    # leaves keep their full n_groups leading axis
    for leaf in jax.tree.leaves(blob["tree"]["body"]):
        assert leaf.shape[0] == n_slots and leaf.shape[1] == 1, leaf.shape
    # occupy a slot on dst so the imported session lands on a different one
    dst.alloc(7)
    s2 = dst.import_session(blob)
    tok2 = jnp.zeros((n_slots,), jnp.int32).at[s2.slot].set(s.last_token)
    logits_dst, _ = decoder.decode_step(
        CFG, CTX, params, dst.caches, tok2, jnp.full((n_slots,), 3, jnp.int32))
    np.testing.assert_allclose(
        np.asarray(logits_dst[s2.slot]), np.asarray(logits_src[s.slot]),
        rtol=1e-4, atol=1e-4)


def test_router_seq_shards_flips_near_crossover():
    """seq_shards feeds straight into the priced verdict: the same session
    length forwards on a whole-column router and acquires on a seq-sharded
    one (the state's per-hop bytes shrank 8x)."""
    for shards, want in ((1, "forward"), (8, "acquire")):
        r = LocalityRouter(4, policy="short", arbitration="priced",
                           kv_bytes_per_token=1.0, seq_shards=shards)
        r.route(0, 5, 0)                   # pod 0 owns session 5
        # 4x the work bytes: whole-column state clearly loses, 1/8-per-hop wins
        ln = 4 * int(r.request_bytes + r.response_bytes)
        d = r.route(2, 5, ln)
        assert d.action == want, (shards, d)


def test_engine_seq_shards_reprices_real_transfers():
    """RealBackend exposes its stores' seq_shards and the engine's re-pricing
    path uses it (sanity: attribute exists and is >= 1 without a mesh)."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    backend = RealBackend(CFG, CTX, params, n_pods=2, n_slots=4, max_len=32)
    assert backend.seq_shards == 1
    assert backend.stores[0].seq_shards == 1


def test_build_engine_pods_on_devices_decode_like_shared_device():
    """build_engine with ``devices`` (each pod a replica on its own device)
    serves a stream with an acquire and forwards to the same greedy tokens
    as the pods sharing the default device; imported columns land on the
    destination pod's device and bf16 params come out of init."""
    from repro.launch.serve import build_engine

    cfg = get_smoke_config("glm4-9b")
    dev = jax.devices()[0]

    def serve(devices):
        eng = build_engine(cfg, pods=2, sessions=8, max_len=32,
                           devices=devices)
        out = {}
        step = eng.backend.step

        def recording(pod, sids):
            toks = step(pod, sids)
            for sid, t in toks.items():
                out.setdefault(sid, []).append(t)
            return toks

        eng.backend.step = recording
        # 24 tokens of cache outweigh a request's bytes: later remote
        # requests forward instead of acquiring
        for sid, origin in [(0, 0), (1, 1), (1, 0), (2, 0)]:
            eng.submit(Request(sid=sid, origin=origin, n_tokens=24))
        eng.drain()
        for sid, origin in [(0, 1), (2, 1), (1, 1)]:
            eng.submit(Request(sid=sid, origin=origin, n_tokens=2))
        eng.drain()
        return eng, out

    shared, want = serve(None)
    placed, got = serve([dev, dev])
    assert got == want and sum(map(len, got.values())) > 0
    assert placed.router.metrics.acquires >= 1
    assert placed.metrics.forwards >= 1
    for st in placed.backend.stores:
        assert {d for x in jax.tree.leaves(st.caches)
                for d in x.devices()} == {dev}
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(shared.backend.params)
               if jnp.issubdtype(x.dtype, jnp.floating))


def test_router_freq_decays_with_clock():
    """Session-touch rates decay on the router clock (tick), so the LC
    attractor is rate-based: old bursts fade once time passes.  Rates live
    in ONE growable matrix (the planner-shared implementation), not a dict
    of per-sid trackers."""
    from repro.core.stats import DecayedFrequency

    r = LocalityRouter(2, policy="long", freq_tau_ms=100.0)
    assert isinstance(r.freq, DecayedFrequency) and r.freq.grow_cols
    for _ in range(8):
        r.route(0, 7, 4)
    hot = r.freq.rates(r._now)[0, 7]
    r.tick(1000.0)                          # 10 tau of idle time
    cold = r.freq.rates(r._now)[0, 7]
    assert cold < 1e-3 * hot
    r.evict(7)
    assert r.freq.rates(r._now)[0, 7] == 0.0


def test_engine_async_plan_epoch_kicks_then_harvests():
    """plan_async (the default): an epoch boundary KICKS scoring and the
    next step's start HARVESTS it, so the pending plan is observable
    between steps and the decode loop never stalls on the evaluation.
    Moves land one step later than synchronous planning, with live-
    ownership staleness re-checks at harvest — the steady-state outcome
    (the misplaced session re-homed to its hot pod) matches
    plan_async=False."""
    from repro.plan import PlacementPlanner

    big = get_smoke_config("mixtral-8x7b")

    def run(plan_async):
        router = LocalityRouter(2, policy="short",
                                kv_bytes_per_token=10_000.0)
        planner = PlacementPlanner.for_serving(2, 8)
        eng = MultiPodEngine(2, SimBackend(big), router, planner=planner,
                             plan_async=plan_async)
        eng.submit(Request(sid=5, origin=0, n_tokens=1))
        eng.run_step()                       # pod 0 takes first-touch ownership
        saw_pending = False
        for _ in range(40):                  # ...but pod 1 sends all traffic
            eng.submit(Request(sid=5, origin=1, n_tokens=1))
            eng.run_step()
            saw_pending |= eng._pending_plan is not None
        eng.drain()
        return eng, saw_pending

    eng_async, saw_pending = run(True)
    assert saw_pending                       # a kicked epoch outlived its step
    assert eng_async.metrics.plan_epochs > 0
    assert eng_async.planner.planned_moves >= 1
    assert eng_async.router.owner[5] == 1    # re-homed to the hot pod
    # the on-path accounting exists and is a sliver of simulated decode
    d = eng_async.metrics.as_dict()
    assert d["plan_block_s"] > 0.0

    eng_sync, saw_pending_sync = run(False)
    assert not saw_pending_sync              # sync epochs never leave a pending
    assert eng_sync.router.owner[5] == 1     # same steady state


def test_real_backend_decode_wall_time_feeds_token_latency():
    """Under RealBackend each step's measured decode time (the
    ``repro.serve.decode`` span through the backend's HostClock) lands in
    the token-latency histograms; the pod clocks and the router clock keep
    the priced time."""
    import itertools

    from repro.obs.trace import HostClock
    from repro.serve.engine import REAL_STEP_MS

    params = init_params(CFG, jax.random.PRNGKey(0))
    backend = RealBackend(CFG, CTX, params, n_pods=1, n_slots=4, max_len=32)
    ticks = itertools.count(0.0, 0.25)   # each read 0.25 s after the last
    backend.host_clock = HostClock(clock=lambda: next(ticks))
    router = LocalityRouter(1, policy="short")
    eng = MultiPodEngine(1, backend, router)
    for sid in range(3):
        eng.submit(Request(sid=sid, origin=0, n_tokens=2))
    now0 = router._now
    eng.run_step()
    eng.run_step()
    fleet = eng.metrics.token_latency()
    assert fleet.count == 6
    assert fleet.samples == pytest.approx([0.25] * 6)
    assert eng.metrics.token_latency(0).count == 6
    assert backend.decode_s.value == pytest.approx(0.5)
    assert eng.metrics.as_dict()["token_lat_p99_s"] == pytest.approx(0.25)
    assert eng.metrics.sim_time_s == 0.0
    assert router._now - now0 == pytest.approx(2 * REAL_STEP_MS)


# ---------------------------------------------------------------------------
# The donated decode step against the per-layer form it replaced
# ---------------------------------------------------------------------------

def _vmapped_write(buf, new, index, layer=None):
    """The per-slot cache write as the decode step made it before its
    caches were donated: each slot's row by its own
    ``dynamic_update_slice`` into one layer's cache, sliced out of the
    stack."""
    assert layer is None
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), new.shape[:1])
    return jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))(buf, new.astype(buf.dtype), index)


def _per_layer_step(cfg, params, caches, tokens, pos):
    """The oracle: one decode step run eagerly layer by layer, each layer's
    cache taken out of the stack, written by ``_vmapped_write`` and
    stacked back; returns ``(logits, caches)``."""
    from unittest import mock

    from repro.models import attention
    from repro.models.common import layer_plan

    plan = layer_plan(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    b = tokens.shape[0]
    positions = jnp.broadcast_to(pos, (b,))[:, None]
    x = decoder.embed_in(cfg, params, {"tokens": tokens[:, None]})

    def run(kind, p, cache):
        nonlocal x
        x, nc, _ = decoder.block_apply(
            cfg, CTX, kind, p, params.get("shared_attn"), x, positions,
            cache=jax.tree.map(jnp.asarray, cache), cache_index=pos,
            return_cache=True)
        return nc

    take = lambda t, g: jax.tree.map(lambda a: a[g], t)  # noqa: E731
    out = {"prefix": [], "body": None, "suffix": []}
    with mock.patch.object(attention, "write_rows", _vmapped_write):
        for i, name in enumerate(decoder._unrolled_names(params["prefix"])
                                 if plan.prefix else []):
            out["prefix"].append(run(plan.kinds[i], params["prefix"][name],
                                     caches["prefix"][i]))
        groups = []
        for g in range(plan.n_groups):
            groups.append([run(plan.kinds[plan.prefix + j],
                               take(params["blocks"][f"pos{j}"], g),
                               take(caches["body"][j], g))
                           for j in range(plan.period)])
        if groups:
            out["body"] = [jax.tree.map(lambda *ls: jnp.stack(ls),
                                        *[row[j] for row in groups])
                           for j in range(plan.period)]
        for i, name in enumerate(decoder._unrolled_names(params["suffix"])
                                 if plan.suffix else []):
            out["suffix"].append(run(plan.kinds[plan.suffix_start + i],
                                     params["suffix"][name],
                                     caches["suffix"][i]))
    return decoder.lm_logits(cfg, CTX, params, x)[:, 0], out


@pytest.mark.parametrize("arch", ["glm4-9b", "deepseek-v2-236b",
                                  "zamba2-1.2b"])
def test_donated_decode_step_matches_per_layer_writes(arch):
    """``RealBackend``'s jitted step, its caches donated and written in
    place, gives the logits and caches of the per-layer form it replaced:
    GQA (glm4), MLA with a dense unrolled first layer (DeepSeek-V2), and
    mamba layers in the scanned body and the unrolled suffix beside a
    shared attention block (Zamba2).  Four steps with slots at different
    depths and one slot idle (token 0 at position 0, as ``RealBackend``
    passes it), then one step of the scalar-position path."""
    cfg = dataclasses.replace(get_smoke_config(arch), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(3))
    be = RealBackend(cfg, CTX, params, n_pods=1, n_slots=4, max_len=16)
    st = be.stores[0]
    # float32 caches, so that a float rounding apart between the jitted
    # step and the eager oracle is not widened to a bfloat16 unit
    st.caches = decoder.init_cache(cfg, 4, 16, jnp.float32)
    # host copies: a zero-copy view would keep the buffers from being donated
    want_caches = jax.tree.map(lambda a: np.array(a, copy=True), st.caches)
    rng = np.random.default_rng(0)
    depth = np.array([0, 3, 7, 0])           # slot 3 sits every step out
    steps = [(t, depth + t) for t in range(4)] + [(4, np.int32(12))]
    for t, pos in steps:
        tokens = rng.integers(1, cfg.vocab_size, 4).astype(np.int32)
        if np.ndim(pos):
            tokens[3], pos = 0, np.where(np.arange(4) == 3, 0, pos)
        donated = jax.tree.leaves(st.caches)
        logits, st.caches, *_ = be._step(be.pod_params[0], st.caches,
                                         jnp.asarray(tokens),
                                         jnp.asarray(pos, jnp.int32))
        assert all(a.is_deleted() for a in donated), t
        want, want_caches = _per_layer_step(cfg, params, want_caches,
                                            tokens, pos)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                                   rtol=1e-5, atol=1e-5, err_msg=str(t))
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
            err_msg=str(t)), st.caches, want_caches)
