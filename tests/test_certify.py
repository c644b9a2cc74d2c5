"""Batched certification pipeline: equivalence, locks, packing, serving.

The contract under test (ISSUE 4): the batched commit phase is a pure
vectorization of the one-at-a-time path — byte-identical store state and
identical commit/abort/forward counts on seeded runs — with write locks
actually threaded through both kernels, and the serving certifier draining
each pod's forwarded batch in one dispatch per engine step.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import BankWorkload, SimConfig, make_cluster
from repro.core.stm import (Transaction, VersionedStore, pack_read_sets,
                            pack_write_sets, validate_batch)


def _run_mode(mode, *, algo="LILAC-TM-ST", locality=0.5, seed=3, **cfg_kw):
    cfg = SimConfig(duration_ms=300.0, warmup_ms=50.0, seed=seed,
                    certify_mode=mode, **cfg_kw)
    wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items,
                      locality=locality)
    c = make_cluster(algo, wl, cfg)
    m = c.run()
    return c, m


# ---------------------------------------------------------------------------
# Tentpole: batched drain == sequential oracle, byte for byte
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("algo,locality", [
    ("LILAC-TM-ST", 0.3), ("FGL", 0.9), ("ALC", 0.5)])
def test_batched_certification_byte_identical_to_sequential(algo, locality):
    """Seeded runs: batched drain (forced through the vectorized kernel,
    certify_jax_min=1) produces byte-identical per-replica values/versions
    arrays and identical commit/abort/forward counts.  The amortized slot
    cost is pinned off: with ``cert_slot_mode="per_txn"`` the batched drain
    is a *pure vectorization* of the one-at-a-time path."""
    seq_c, seq_m = _run_mode("sequential", algo=algo, locality=locality)
    bat_c, bat_m = _run_mode("batched", algo=algo, locality=locality,
                             certify_jax_min=1, cert_slot_mode="per_txn")
    assert (bat_m.commits, bat_m.aborts, bat_m.forwards) == \
        (seq_m.commits, seq_m.aborts, seq_m.forwards)
    assert bat_m.commit_times == seq_m.commit_times
    for rs, rb in zip(seq_c.replicas, bat_c.replicas):
        assert rs.store.values.tobytes() == rb.store.values.tobytes()
        assert rs.store.versions.tobytes() == rb.store.versions.tobytes()
    # the batched path actually ran: every certification went through it
    assert bat_m.cert_batches > 0
    assert bat_m.cert_batch_txns >= bat_m.rw_certified - bat_m.forwards


def test_amortized_slot_cost_keeps_invariants_and_lifts_throughput():
    """ROADMAP item: with the amortized slot model (the batched-mode
    default), the commit-phase group charges ONE slot fixed + per-txn
    increment, so *simulated* throughput reflects PR 4's batching — it must
    be at least the per-txn model's, and safety must be untouched."""
    assert SimConfig().cert_slot_mode == "amortized"
    thr = {}
    for mode in ("per_txn", "amortized"):
        c, m = _run_mode("batched", locality=0.3, cert_slot_mode=mode)
        assert m.commits > 100
        expect = c.cfg.n_items * c.cfg.init_value
        for r in c.replicas:
            assert r.store.total() == pytest.approx(expect, abs=1e-6)
        v0 = c.replicas[0].store.values
        for r in c.replicas[1:]:
            np.testing.assert_array_equal(v0, r.store.values)
        thr[mode] = c.throughput()
    assert thr["amortized"] >= thr["per_txn"]


def test_amortized_slot_charges_fixed_plus_increment_per_group():
    """Two transactions enabled together occupy one slot for
    fixed + 2*per_txn (not two slots for the full cost each)."""
    from repro.core.cluster import Cluster, Replica

    cfg = SimConfig(certify_mode="batched", cert_slot_mode="amortized",
                    cert_fixed_ms=1.0, cert_per_txn_ms=0.25)
    wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items)
    c = make_cluster("FGL", wl, cfg)

    class _Txn:
        def __init__(self):
            self.lors = []
    r = c.replicas[0]
    t1, t2 = _Txn(), _Txn()
    r.lm.is_enabled = lambda lors: True
    drained = []
    c._enqueue_certify = lambda t, node: drained.append(t)
    r.waiters = [(t1, []), (t2, [])]
    c._check_waiters(0)
    assert r.free_slots == cfg.threads_per_node - 1   # ONE slot for the group
    c.events.run(until=1.49)                          # fixed + 2*inc = 1.5
    assert drained == []
    c.events.run(until=2.0)
    assert drained == [t1, t2]
    assert r.free_slots == cfg.threads_per_node


def test_batched_is_the_default_and_window_keeps_invariants():
    """Batched is the default simulator path; a coalescing window > 0 still
    conserves money and converges replicas (safety under deferral)."""
    assert SimConfig().certify_mode == "batched"
    c, m = _run_mode("batched", certify_window_ms=2.0, seed=5)
    assert m.commits > 100
    expect = c.cfg.n_items * c.cfg.init_value
    for r in c.replicas:
        assert r.store.total() == pytest.approx(expect, abs=1e-6)
    v0 = c.replicas[0].store.values
    for r in c.replicas[1:]:
        np.testing.assert_array_equal(v0, r.store.values)


# ---------------------------------------------------------------------------
# Satellite: the write-lock path is live on both backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_lock_conflict_flips_verdict(backend):
    """Regression for the silent stub: the old pallas branch fabricated
    witems = -1 and zero locks, so a locked-write conflict could never be
    reported.  Now a lock on a written item flips the verdict, and only for
    the writer of that item, on both backends."""
    store = VersionedStore(64)
    t1 = Transaction(txid=1, origin=0)
    t1.log_read(3, 0)
    t1.write_set[7] = 1.0
    t2 = Transaction(txid=2, origin=0)
    t2.log_read(4, 0)
    t2.write_set[9] = 2.0
    no_locks = validate_batch(store, [t1, t2], backend=backend)
    np.testing.assert_array_equal(no_locks, [True, True])
    locks = np.zeros((64,), np.int32)
    locks[7] = 1
    with_locks = validate_batch(store, [t1, t2], locks=locks, backend=backend)
    np.testing.assert_array_equal(with_locks, [False, True])


def test_backends_agree_bitwise_with_locks_and_writes():
    """jnp <-> Pallas(interpret) <-> python loop, randomized, bitwise —
    including lock conflicts and stale reads."""
    rng = np.random.default_rng(11)
    store = VersionedStore(500)
    store.versions[:] = rng.integers(0, 30, 500)
    locks = (rng.random(500) < 0.15).astype(np.int32)
    txns = []
    for i in range(60):
        t = Transaction(txid=i + 1, origin=0)
        for it in rng.integers(0, 500, rng.integers(1, 9)):
            ver = int(store.versions[it])
            if rng.random() < 0.2:
                ver += 1                      # stale
            t.log_read(int(it), ver)
        for it in rng.integers(0, 500, rng.integers(0, 5)):
            t.write_set[int(it)] = float(it)
        txns.append(t)
    jnp_out = validate_batch(store, txns, locks=locks, backend="jnp")
    pls_out = validate_batch(store, txns, locks=locks, backend="pallas")
    loop = np.asarray([
        store.validate(t) and not any(locks[it] for it in t.write_set)
        for t in txns])
    np.testing.assert_array_equal(jnp_out, loop)
    np.testing.assert_array_equal(pls_out, loop)


def test_cluster_write_locks_reflect_lease_ownership():
    """_write_locks marks exactly the conflict classes leased to another
    replica; expanded through the item -> class map it is the per-item
    derivation, item for item."""
    c, _ = _run_mode("batched", locality=0.3, seed=7)
    for node in range(c.cfg.n_nodes):
        locks = c._write_locks(node)
        lm = c.replicas[node].lm
        assert locks.shape == (c.cfg.n_classes,) and locks.dtype == np.int32
        per_item = lm.owner_np()[c._item_cc]
        np.testing.assert_array_equal(
            locks[c._item_cc], (per_item >= 0) & (per_item != node))
        items = np.random.default_rng(0).integers(0, c.cfg.n_items, 200)
        for it in items:
            cc = c.ccmap.of_item(int(it))
            owner = lm.head_owner(cc)
            assert bool(locks[c._item_cc[it]]) == \
                (owner >= 0 and owner != node)


def _random_batch(rng, n_items, n_txns, max_reads=9, max_writes=9):
    """Transactions with stale reads, repeated items and some rows with no
    writes (or no reads) at all; the first reads fresh and writes nothing,
    so it passes."""
    store = VersionedStore(n_items)
    store.versions[:] = rng.integers(0, 30, n_items)
    txns = []
    for i in range(n_txns):
        t = Transaction(txid=i + 1, origin=0)
        for it in rng.integers(0, n_items, rng.integers(0, max_reads)):
            ver = int(store.versions[it])
            if i and rng.random() < 0.1:
                ver += 1                      # stale
            t.log_read(int(it), ver)
        for it in rng.integers(0, n_items,
                               rng.integers(0, max_writes) if i else 0):
            t.write_set[int(it)] = float(it)
        txns.append(t)
    return store, txns


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("n_items,n_classes,n_txns,seed", [
    (64, 5, 16, 1),       # 16 rows x 8 write lanes: more entries than items
    (64, 3, 11, 2),       # 11 rows padded to 16
    (500, 33, 60, 3),
    (40, 1, 3, 4),        # one class, padded rows, tiny store
])
def test_class_domain_locks_match_item_domain(backend, n_items, n_classes,
                                              n_txns, seed):
    """Class-domain locks looked up through ``lock_of_item`` certify the
    same verdicts as the item-domain ``locks_cls[lock_of_item]`` and as the
    python loop, bit for bit."""
    rng = np.random.default_rng(seed)
    store, txns = _random_batch(rng, n_items, n_txns)
    lock_of_item = rng.integers(0, n_classes, n_items).astype(np.int32)
    locks_cls = (rng.random(n_classes) < 0.3).astype(np.int32)
    locks_cls[0] = 1
    locks_item = locks_cls[lock_of_item]
    by_class = validate_batch(store, txns, locks=locks_cls,
                              lock_of_item=lock_of_item, backend=backend)
    by_item = validate_batch(store, txns, locks=locks_item, backend=backend)
    loop = np.asarray([
        store.validate(t) and not any(locks_item[it] for it in t.write_set)
        for t in txns])
    assert by_class.dtype == bool and by_class.shape == (n_txns,)
    np.testing.assert_array_equal(by_class, loop)
    np.testing.assert_array_equal(by_item, loop)
    # the draw reaches both verdicts
    assert loop.any() and not loop.all()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_lock_values_longer_than_the_store(backend):
    """``write_items`` index the lock values, not the store: on a store of
    64 items, entry 100 of a 128-entry lock vector decides the verdict."""
    from repro.kernels.ops import validate_transactions

    store = np.zeros((64,), np.int32)
    items = np.full((2, 8), -1, np.int32)
    vers = np.zeros((2, 8), np.int32)
    locks = np.zeros((128,), np.int32)
    locks[100] = 1
    witems = np.full((2, 8), -1, np.int32)
    witems[0, 0], witems[1, 0] = 100, 63
    out = validate_transactions(store, items, vers, write_locks=locks,
                                write_items=witems, backend=backend)
    np.testing.assert_array_equal(np.asarray(out), [False, True])


# the footprints the TPC-C mix certifies, as its warm-up compiles them:
# (reads, writes) for Payment, New-Order and its repeated-stock variants
_TPCC_FOOTPRINTS = ((3, 3), (11, 6), (17, 6), (17, 9))


def _footprint_batch(store, n, reads, writes):
    txns = []
    for i in range(n):
        t = Transaction(txid=i + 1, origin=0)
        for j in range(reads):
            store.read(t, j)
        for j in range(writes):
            store.write(t, j, 0.0)
        txns.append(t)
    return txns


def test_class_domain_locks_compile_no_new_shape():
    """Item-domain locks warm the certify shapes; class-domain locks on the
    same rows, reads and writes then hit them: the jit caches behind
    ``validate_transactions`` gain no entry."""
    from repro.kernels import ops

    n_items, n_classes = 4096, 33
    store = VersionedStore(n_items)
    item_locks = np.zeros((n_items,), np.int32)
    rows = (1, 5, 8, 9, 16)
    for n in rows:
        for reads, writes in _TPCC_FOOTPRINTS:
            validate_batch(store, _footprint_batch(store, n, reads, writes),
                           locks=item_locks)
    warmed = ops._lease_validate_ref_jit._cache_size()
    assert warmed > 0
    lock_of_item = (np.arange(n_items) % n_classes).astype(np.int32)
    cls_locks = np.zeros((n_classes,), np.int32)
    cls_locks[1] = 1
    for n in rows:
        for reads, writes in _TPCC_FOOTPRINTS:
            ok = validate_batch(store, _footprint_batch(store, n, reads,
                                                        writes),
                                locks=cls_locks, lock_of_item=lock_of_item)
            assert not ok.any()               # every batch writes item 1
    assert ops._lease_validate_ref_jit._cache_size() == warmed


def test_certify_uploads_entries_not_a_lock_array(monkeypatch):
    """A small cluster's certify calls upload the store's versions and the
    packed entries, and sweep one lock lane a write entry: no second
    ``[n_items]`` array."""
    from repro.kernels import ops
    from repro.obs.trace import NO_SPAN

    spans, calls = [], []

    def recording_span(name, **args):
        if name == "repro.ops.validate":
            spans.append({k: v() if callable(v) else v
                          for k, v in args.items()})
        return NO_SPAN

    real = ops.validate_transactions

    def shapes(store_versions, read_items, read_versions, write_locks=None,
               write_items=None, **kw):
        calls.append((read_items.shape, write_items.shape))
        return real(store_versions, read_items, read_versions,
                    write_locks=write_locks, write_items=write_items, **kw)

    monkeypatch.setattr(ops, "host_span", recording_span)
    monkeypatch.setattr(ops, "validate_transactions", shapes)
    c, m = _run_mode("batched", locality=0.3, seed=7, certify_jax_min=1)
    assert m.cert_batches > 0 and len(spans) == len(calls) > 0
    n_items = c.cfg.n_items
    for span, ((bp, r), (bw, w)) in zip(spans, calls):
        assert bw == bp
        entries = 4 * (2 * bp * r + 2 * bp * w)
        assert span["h2d_bytes"] <= 4 * n_items + entries
        assert span["h2d_bytes"] < 2 * 4 * n_items
        assert span["lock_lanes"] == bp * w


# ---------------------------------------------------------------------------
# Packing + batched apply
# ---------------------------------------------------------------------------

def test_pack_pow2_buckets_and_padding():
    txns = []
    for n in (3, 5, 2):
        t = Transaction(txid=1, origin=0)
        for k in range(n):
            t.log_read(k, k + 10)
        t.write_set = {k: float(k) for k in range(n)}
        txns.append(t)
    items, vers = pack_read_sets(txns)
    assert items.shape == (3, 8)             # 5 reads -> pow2 bucket 8
    witems = pack_write_sets(txns)
    assert witems.shape == (3, 8)
    # padded slots masked, real slots in order
    assert list(items[1, :5]) == [0, 1, 2, 3, 4]
    assert list(vers[1, :5]) == [10, 11, 12, 13, 14]
    assert (items[1, 5:] == -1).all() and (items[2, 2:] == -1).all()
    assert set(witems[0, :3]) == {0, 1, 2} and (witems[0, 3:] == -1).all()
    # pad_to widens, pow2 keeps buckets stable across nearby batch shapes
    assert pack_read_sets(txns, pad_to=11)[0].shape == (3, 16)
    assert pack_read_sets(txns[:2])[0].shape == (2, 8)


def test_apply_batch_matches_sequential_apply_versioned():
    """Vectorized scatter == ordered apply_versioned loop, including
    item overlap across write-sets (last writer wins)."""
    rng = np.random.default_rng(3)
    a, b = VersionedStore(200), VersionedStore(200)
    write_sets, versions = [], []
    for i in range(40):
        ws = {int(it): float(rng.random())
              for it in rng.integers(0, 200, rng.integers(0, 6))}
        write_sets.append(ws)
        versions.append(100 + i)
    for ws, v in zip(write_sets, versions):
        a.apply_versioned(ws, v)
    b.apply_batch(write_sets, versions)
    assert a.values.tobytes() == b.values.tobytes()
    assert a.versions.tobytes() == b.versions.tobytes()
    assert a.clock == b.clock


def test_read_log_record_view_roundtrip():
    """The compact read log and its ReadSetEntry view stay in sync."""
    store = VersionedStore(16)
    store.apply({3: 1.5})
    t = Transaction(txid=1, origin=0)
    assert store.read(t, 3) == 1.5
    store.read(t, 4)
    assert t.n_reads == 2
    assert [(e.item, e.version) for e in t.read_set] == [(3, 1), (4, 0)]
    assert list(t.read_items) == [3, 4]
    assert store.validate(t)
    store.apply({3: 2.0})
    assert not store.validate(t)


# ---------------------------------------------------------------------------
# Serving-layer certifier
# ---------------------------------------------------------------------------

def _engine(n_pods=2, **router_kw):
    from repro.configs import get_smoke_config
    from repro.serve.certifier import StepCertifier
    from repro.serve.engine import MultiPodEngine, SimBackend
    from repro.serve.router import LocalityRouter

    cfg = get_smoke_config("mixtral-8x7b")
    router = LocalityRouter(n_pods, policy="short",
                            kv_bytes_per_token=router_kw.pop("kvb", 1e9),
                            **router_kw)
    certifier = StepCertifier(n_pods, jax_min=1)   # pin the packed path
    return MultiPodEngine(n_pods, SimBackend(cfg), router, certifier)


def test_engine_certifies_forwarded_batch_in_one_dispatch():
    from repro.serve.engine import Request

    eng = _engine()
    eng.submit(Request(sid=1, origin=0, n_tokens=1))   # pod 0 owns sid 1
    eng.submit(Request(sid=2, origin=0, n_tokens=1))   # pod 0 owns sid 2
    eng.run_step()
    base_batches = eng.certifier.metrics.batches
    # two forwarded requests from pod 1 -> one batch at the owner
    d1 = eng.submit(Request(sid=1, origin=1, n_tokens=1))
    d2 = eng.submit(Request(sid=2, origin=1, n_tokens=1))
    assert d1.action == d2.action == "forward"
    cm = eng.certifier.metrics
    t0, clock0 = cm.time_s, float(eng._pod_clock[0])
    eng.run_step()
    assert cm.batches == base_batches + 1              # ONE dispatch
    assert cm.max_batch >= 2 and cm.aborts == 0
    assert cm.certified >= 2
    # the batch's validate time landed on the owner pod's busy clock
    assert cm.time_s > t0
    assert float(eng._pod_clock[0]) - clock0 >= eng.certifier.certify_time_s(2)
    # engine metrics expose the certifier's counters (single source)
    assert eng.metrics.as_dict()["certified"] == cm.certified


def test_certify_time_scales_with_batch_not_per_request():
    from repro.serve.certifier import StepCertifier

    c = StepCertifier(1)
    one, many = c.certify_time_s(1), c.certify_time_s(64)
    assert many < 64 * one                  # amortized, not a constant each
    assert many > one                       # but it does scale with rows


def test_stale_epoch_forward_aborts_and_reroutes():
    """A forward in flight when the session is acquired away fails
    certification (stale lease epoch) and is re-routed, then completes."""
    from repro.serve.engine import Request

    eng = _engine(kvb=1.0)                  # featherweight KV: acquires win
    eng.submit(Request(sid=5, origin=0, n_tokens=1))   # pod 0 owns sid 5
    eng.run_step()
    # force a forward to the owner, then move ownership before the step
    eng.router.owner[5] = 0
    d = eng.router.route(1, 5, 10**9)       # huge KV -> forward verdict
    assert d.action == "forward"
    req = Request(sid=5, origin=1, n_tokens=1)
    eng.certifier.enqueue(0, req, d.epoch)
    acq = eng.submit(Request(sid=5, origin=1, n_tokens=1))
    assert acq.action == "acquire"          # bumps the lease epoch
    aborts0 = eng.certifier.metrics.aborts
    eng.drain()
    assert eng.certifier.metrics.aborts == aborts0 + 1
    assert not eng.certifier.has_pending()
    assert req.n_tokens == 0                # re-routed and decoded


def test_router_epoch_bumps_on_every_ownership_move():
    from repro.serve.router import LocalityRouter

    r = LocalityRouter(2, policy="short", arbitration="priced",
                       kv_bytes_per_token=1.0)
    d0 = r.route(0, 9, 0)
    assert d0.epoch == 1                    # placement is a transition
    assert r.route(0, 9, 5).epoch == 1      # local reuse
    acq = r.route(1, 9, 5)                  # tiny KV: state moves
    assert acq.action == "acquire" and acq.epoch == 2
    fwd = r.route(0, 9, 10**9)              # heavy KV: work moves
    assert fwd.action == "forward" and fwd.epoch == 2


def test_evicted_session_replacement_invalidates_stale_forwards():
    """Regression: evict() keeps the epoch, and re-placement bumps it, so a
    forward snapshotted before the evict can never certify against the new
    placement (it used to pass and decode on the dropped cache's pod)."""
    from repro.serve.engine import Request

    eng = _engine()
    eng.submit(Request(sid=7, origin=0, n_tokens=1))   # pod 0 owns sid 7
    eng.run_step()
    d = eng.router.route(1, 7, 10**9)       # forward verdict, epoch 1
    assert d.action == "forward"
    stale = Request(sid=7, origin=1, n_tokens=1)
    eng.certifier.enqueue(0, stale, d.epoch)
    eng.router.evict(7)
    eng.backend.drop(0, 7)
    aborts0 = eng.certifier.metrics.aborts
    d2 = eng.submit(Request(sid=7, origin=1, n_tokens=1))  # re-placement
    assert d2.epoch > d.epoch
    eng.drain()
    assert eng.certifier.metrics.aborts == aborts0 + 1
