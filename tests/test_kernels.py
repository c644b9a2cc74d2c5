"""Kernel allclose sweeps (interpret=True) against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lease_validate import lease_validate
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


@pytest.mark.parametrize(
    "b,sq,skv,hq,hkv,dk,dv,causal,window,cap,dtype",
    [
        (2, 128, 128, 4, 2, 32, 32, True, None, 0.0, jnp.float32),
        (1, 100, 100, 4, 4, 16, 16, True, None, 0.0, jnp.float32),
        (2, 128, 128, 4, 2, 32, 32, True, 40, 0.0, jnp.float32),
        (2, 64, 192, 4, 2, 32, 32, True, None, 0.0, jnp.float32),   # cache
        (2, 128, 128, 4, 4, 32, 32, False, None, 0.0, jnp.float32),  # encoder
        (2, 128, 128, 8, 2, 64, 64, True, None, 30.0, jnp.bfloat16),
        (1, 256, 256, 2, 2, 192, 128, True, None, 0.0, jnp.float32),  # MLA dims
        (1, 72, 72, 2, 1, 24, 24, True, 16, 0.0, jnp.float32),  # odd sizes
    ],
)
def test_flash_attention_vs_ref(b, sq, skv, hq, hkv, dk, dv, causal, window,
                                cap, dtype):
    q = jnp.asarray(RNG.standard_normal((b, sq, hq, dk)), dtype)
    k = jnp.asarray(RNG.standard_normal((b, skv, hkv, dk)), dtype)
    v = jnp.asarray(RNG.standard_normal((b, skv, hkv, dv)), dtype)
    qp = jnp.broadcast_to(jnp.arange(skv - sq, skv, dtype=jnp.int32)[None], (b, sq))
    kp = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32)[None], (b, skv))
    out = flash_attention(q, k, v, q_positions=qp, kv_positions=kp,
                          causal=causal, sliding_window=window,
                          logit_softcap=cap, block_q=64, block_k=64,
                          interpret=True)
    want = ref.sdpa_ref(q, k, v, q_positions=qp, kv_positions=kp,
                        causal=causal, sliding_window=window, logit_softcap=cap)
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize(
    "b,s,h,p,n,chunk,hb",
    [
        (2, 256, 8, 16, 32, 64, 4),
        (1, 128, 16, 64, 128, 32, 8),
        (2, 512, 48, 64, 128, 256, 8),
        (1, 64, 4, 32, 16, 64, 4),       # single chunk
    ],
)
def test_ssd_scan_vs_ref(b, s, h, p, n, chunk, hb):
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(RNG.standard_normal((b, s, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(RNG.standard_normal((h,)) * 0.3, jnp.float32))
    bm = jnp.asarray(RNG.standard_normal((b, s, 1, n)) * 0.4, jnp.float32)
    cm = jnp.asarray(RNG.standard_normal((b, s, 1, n)) * 0.4, jnp.float32)
    h0 = jnp.asarray(RNG.standard_normal((b, h, p, n)) * 0.1, jnp.float32)
    y_k, f_k = ssd_scan(x, dt, a, bm, cm, chunk=chunk, h0=h0, block_heads=hb,
                        interpret=True)
    y_r, f_r = ref.ssd_ref(x, dt, a, bm, cm, chunk=chunk, h0=h0)
    scale = float(jnp.max(jnp.abs(y_r))) + 1e-9
    assert float(jnp.max(jnp.abs(y_k - y_r))) / scale < 2e-5
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                               atol=2e-3, rtol=1e-4)


def test_ssd_decode_recurrence_matches_scan():
    """Recurrent single steps replayed == chunked scan on the same stream."""
    from repro.models.ssm import ssd_recurrent_step
    b, s, h, p, n = 1, 32, 4, 8, 16
    x = jnp.asarray(RNG.standard_normal((b, s, h, p)) * 0.5, jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(RNG.standard_normal((b, s, h)), jnp.float32))
    a = -jnp.exp(jnp.asarray(RNG.standard_normal((h,)) * 0.3, jnp.float32))
    bm = jnp.asarray(RNG.standard_normal((b, s, 1, n)) * 0.4, jnp.float32)
    cm = jnp.asarray(RNG.standard_normal((b, s, 1, n)) * 0.4, jnp.float32)
    y_scan, _ = ref.ssd_ref(x, dt, a, bm, cm, chunk=16)
    hstate = jnp.zeros((b, h, p, n), jnp.float32)
    outs = []
    for t in range(s):
        y_t, hstate = ssd_recurrent_step(
            hstate, x[:, t], dt[:, t], a, bm[:, t], cm[:, t])
        outs.append(y_t)
    y_rec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_rec), np.asarray(y_scan),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,R,W,n_items,chunk,te", [
    (64, 8, 4, 1024, 256, 32),
    (200, 16, 8, 5000, 512, 64),
    (7, 3, 2, 100, 64, 8),
])
def test_lease_validate_vs_ref(B, R, W, n_items, chunk, te):
    store = jnp.asarray(RNG.integers(0, 50, n_items), jnp.int32)
    locks = jnp.asarray(RNG.random(n_items) < 0.05, jnp.int32)
    items = jnp.asarray(RNG.integers(-1, n_items, (B, R)), jnp.int32)
    vers = jnp.where(jnp.asarray(RNG.random((B, R)) < 0.8),
                     store[jnp.clip(items, 0, n_items - 1)],
                     jnp.asarray(RNG.integers(0, 50, (B, R)), jnp.int32))
    witems = jnp.asarray(RNG.integers(-1, n_items, (B, W)), jnp.int32)
    got = lease_validate(store, items, vers, locks, witems,
                         block_entries=te, chunk=chunk, interpret=True)
    want = ref.lease_validate_ref(store, items, vers, locks > 0, witems)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,R,W,n_items", [(32, 8, 4, 512), (8, 4, 2, 64)])
def test_validate_transactions_backends_agree(B, R, W, n_items):
    """ops.validate_transactions: the dispatch point's pallas(interpret)
    and jit'd-ref paths agree bitwise, locks honored on both."""
    from repro.kernels.ops import validate_transactions
    store = jnp.asarray(RNG.integers(0, 40, n_items), jnp.int32)
    locks = jnp.asarray(RNG.random(n_items) < 0.1, jnp.int32)
    items = jnp.asarray(RNG.integers(-1, n_items, (B, R)), jnp.int32)
    vers = jnp.where(jnp.asarray(RNG.random((B, R)) < 0.8),
                     store[jnp.clip(items, 0, n_items - 1)],
                     jnp.asarray(RNG.integers(0, 40, (B, R)), jnp.int32))
    witems = jnp.asarray(RNG.integers(-1, n_items, (B, W)), jnp.int32)
    kern = validate_transactions(store, items, vers, write_locks=locks,
                                 write_items=witems, backend="pallas")
    ref_out = validate_transactions(store, items, vers, write_locks=locks,
                                    write_items=witems, backend="jnp")
    want = ref.lease_validate_ref(store, items, vers, locks > 0, witems)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(ref_out), np.asarray(want))
    # lock-free default: all-zero locks
    base = validate_transactions(store, items, vers, backend="jnp")
    want_nolock = ref.lease_validate_ref(
        store, items, vers, jnp.zeros_like(store) > 0,
        jnp.full((B, 1), -1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(base), np.asarray(want_nolock))


def test_stm_batched_validation_matches_kernel():
    """The STM's jnp batched validation, the kernel, and the python loop agree."""
    from repro.core.stm import Transaction, VersionedStore, pack_read_sets, validate_batch
    store = VersionedStore(500)
    rng = np.random.default_rng(7)
    txns = []
    for i in range(40):
        t = Transaction(txid=i, origin=0)
        for item in rng.integers(0, 500, rng.integers(1, 6)):
            store.read(t, int(item))
        txns.append(t)
    # mutate some items
    store.apply({int(i): 1.0 for i in rng.integers(0, 500, 60)})
    batched = validate_batch(store, txns)
    loop = np.asarray([store.validate(t) for t in txns])
    np.testing.assert_array_equal(batched, loop)
    items, vers = pack_read_sets(txns)
    kern = lease_validate(
        jnp.asarray(store.versions, jnp.int32), jnp.asarray(items),
        jnp.asarray(vers), jnp.zeros((500,), jnp.int32),
        jnp.full((len(txns), 1), -1, jnp.int32), block_entries=16, chunk=128,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(kern), loop)


@pytest.mark.parametrize("ep,tp,capacity,t_out", [
    (4, 1, 8, 16),    # mixtral-style: whole experts, no psum
    (2, 2, 8, 16),    # deepseek-style: tp partials summed per slot
    (2, 4, 4, 8),
])
def test_moe_combine_vs_ref(ep, tp, capacity, t_out):
    """ops.moe_combine (the a2a combine leg's partial-activation psum)
    against an independent numpy oracle: gate each tp partial, sum the tp
    f-slice partials per (group, slot), scatter-add to the slot's token."""
    from repro.kernels import ops

    d = 12
    back = RNG.standard_normal((ep * tp * capacity, d)).astype(np.float32)
    # slot -> token map; index t_out marks an empty slot (dropped)
    tok_slot = RNG.integers(0, t_out + 1, ep * capacity).astype(np.int32)
    gate_slot = (RNG.random(ep * capacity).astype(np.float32)
                 * (tok_slot < t_out))
    got = np.asarray(ops.moe_combine(
        jnp.asarray(back), jnp.asarray(tok_slot), jnp.asarray(gate_slot),
        tp=tp, capacity=capacity, t_out=t_out))
    gated = (back.reshape(ep, tp, capacity, d)
             * gate_slot.reshape(ep, 1, capacity, 1)).sum(axis=1)
    want = np.zeros((t_out, d), np.float32)
    for i, t in enumerate(tok_slot):
        if t < t_out:
            want[t] += gated.reshape(-1, d)[i]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
