"""System-level tests of the discrete-event cluster (the paper's runtime)."""
import numpy as np
import pytest

from repro.core import (ALGORITHMS, BankWorkload, Cluster, SimConfig,
                        TpccConflictMap, TpccLayout, TpccWorkload, make_cluster)


def _bank(algo, locality=0.9, seed=0, duration=300.0, **kw):
    cfg = SimConfig(duration_ms=duration, warmup_ms=50.0, seed=seed, **kw)
    wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items, locality=locality)
    return make_cluster(algo, wl, cfg)


@pytest.mark.parametrize("algo", list(ALGORITHMS))
def test_conservation_and_convergence(algo):
    """Total money is conserved and replicas converge (after drain)."""
    c = _bank(algo)
    m = c.run()
    assert m.commits > 100
    totals = [r.store.total() for r in c.replicas]
    expect = c.cfg.n_items * c.cfg.init_value
    for t in totals:
        assert t == pytest.approx(expect, abs=1e-6)
    # replicated stores bytewise identical
    v0 = c.replicas[0].store.values
    for r in c.replicas[1:]:
        np.testing.assert_array_equal(v0, r.store.values)


def test_determinism():
    a = _bank("LILAC-TM-ST", seed=3).run()
    b = _bank("LILAC-TM-ST", seed=3).run()
    assert a.commits == b.commits
    assert a.commit_times == b.commit_times


def test_conflict_queue_state_replicated():
    c = _bank("FGL")
    c.run()
    owners0 = c.replicas[0].lm.owner_view()
    for r in c.replicas[1:]:
        assert r.lm.owner_view() == owners0


def test_fgl_beats_alc_at_high_locality():
    thr = {}
    for algo in ("ALC", "FGL"):
        cl = _bank(algo, locality=0.95, duration=500.0)
        cl.run()
        thr[algo] = cl.throughput()
    assert thr["FGL"] > 1.5 * thr["ALC"]


def test_migration_helps_at_low_locality():
    thr = {}
    for algo in ("ALC", "LILAC-TM-ST"):
        cl = _bank(algo, locality=0.3, duration=500.0)
        cl.run()
        thr[algo] = cl.throughput()
    assert thr["LILAC-TM-ST"] > 1.15 * thr["ALC"]


@pytest.mark.parametrize("locality", [0.0, 0.9])
def test_pipelined_handoff_no_slower_than_drain(locality):
    """The pipelined handoff (lease request overlapped with execution) never
    costs simulated throughput against the paper's drain ordering.  The
    cells tie within scheduler-ordering jitter, hence the 0.99 floor."""
    thr = {}
    for handoff in ("drain", "pipelined"):
        cfg = SimConfig(duration_ms=400.0, warmup_ms=60.0, threads_per_node=2,
                        seed=0, handoff=handoff)
        wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items,
                          locality=locality)
        c = make_cluster("FGL", wl, cfg)
        c.run()
        thr[handoff] = c.throughput()
    assert thr["pipelined"] >= 0.99 * thr["drain"]


def test_lease_reuse_rate_tracks_locality():
    lo = _bank("FGL", locality=0.1, duration=400.0)
    hi = _bank("FGL", locality=0.95, duration=400.0)
    lo.run(); hi.run()
    assert hi.metrics.lease_reuse_rate() > lo.metrics.lease_reuse_rate() + 0.3


def test_node_failure_recovery():
    """Crash a node mid-run: survivors keep committing, leases reclaimed."""
    c = _bank("LILAC-TM-ST", locality=0.5, duration=600.0)
    c.events.schedule(200.0, lambda: c.gcs.fail(3))
    m = c.run()
    # survivors continued past the failure
    late = [t for (t, n) in m.commit_times if t > 300.0]
    assert len(late) > 50
    assert all(n != 3 for (t, n) in m.commit_times if t > 250.0)
    # no dangling LORs of the failed node at survivors
    for r in c.replicas[:3]:
        for q in r.lm.cq:
            assert all(l.proc != 3 for l in q)


def test_overload_control_avoids_hot_node():
    """Fig 3(c): with ctrl, throughput under overload is much higher.

    Setup per the paper: every node accesses the hot partition with prob.
    0.2 except its home node, which accesses only it; the home node is then
    overloaded with external CPU jobs.  Conflict classes are coarse enough
    (4/partition) that the home node holds the hot partition's leases —
    the attractor premise of §4.
    """
    from dataclasses import replace
    thr = {}
    for ctrl in (True, False):
        cfg = SimConfig(duration_ms=800.0, warmup_ms=100.0, n_classes=16)
        cfg = replace(cfg, dtd=replace(cfg.dtd, policy="short",
                                       enable_overload_ctrl=ctrl))
        wl = BankWorkload(n_nodes=4, n_items=cfg.n_items, locality=1.0,
                          hot_partition=0, hot_fraction=0.2)
        c = Cluster(cfg, wl)
        c.events.schedule(
            150.0, lambda c=c: c.inject_load(0, extra_load=0.95,
                                             slowdown=50.0, seize_slots=1))
        c.run()
        thr[ctrl] = c.metrics.throughput(300.0, 800.0)
    assert thr[True] > 1.5 * thr[False]


def test_forward_in_flight_to_failed_node_restarts_thread():
    """Fail the target while a forwarded txn is on the wire: the p2p is
    dropped (fail-stop), and the originating thread must be restarted by the
    view change — it used to wedge forever because ``exec_node`` was only
    recorded when the *target* ran ``_certify``."""
    c = _bank("LILAC-TM-ST", locality=0.3, duration=600.0)
    orig_send = c.gcs.p2p_send
    hit = {}

    def send_and_fail(sender, dest, msg):
        orig_send(sender, dest, msg)
        if not hit and isinstance(msg, tuple) and msg[0] == "forward" \
                and c.events.now > 100.0:
            txn = msg[1]
            assert txn.exec_node == dest      # target recorded at send time
            hit.update(origin=txn.origin, txid=txn.txid, dest=dest)
            c.gcs.fail(dest)                  # dies with the forward in flight

    c.gcs.p2p_send = send_and_fail
    m = c.run()
    assert hit, "no forward happened — weaken the trigger"
    # the in-flight transaction was restarted, not wedged: it left _inflight,
    # and no *survivor's* txn still points at the dead node (the dead node's
    # own in-flight txns died with it — that's fail-stop, not a wedge)
    assert hit["txid"] not in c._inflight
    assert all(t.exec_node != hit["dest"] for t in c._inflight.values()
               if t.origin != hit["dest"])
    t_fail = [t for (t, n) in m.commit_times if n == hit["origin"]]
    assert any(t > 450.0 for t in t_fail), "originating thread wedged"
    # the dead node never executed the dropped forward: survivors converge
    expect = c.cfg.n_items * c.cfg.init_value
    for r in c.replicas:
        if r.node != hit["dest"]:
            assert r.store.total() == pytest.approx(expect, abs=1e-6)


def test_tpcc_runs_and_fgl_helps():
    lay = TpccLayout(n_nodes=4)
    ccmap = TpccConflictMap(lay)
    thr = {}
    for algo in ("ALC", "LILAC-TM-LT"):
        cfg = SimConfig(duration_ms=600.0, warmup_ms=100.0,
                        n_items=lay.n_items, n_classes=ccmap.n_classes)
        c = make_cluster(algo, TpccWorkload(lay), cfg, ccmap=ccmap)
        c.run()
        thr[algo] = c.throughput()
        assert c.metrics.commits > 100
    assert thr["LILAC-TM-LT"] > thr["ALC"]
