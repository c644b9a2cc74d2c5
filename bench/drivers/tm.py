"""Lilac-TM driver: the replicated cluster under a closed-loop TPC-C mix.

The system under test is ``repro.core.make_cluster(algorithm, workload,
SimConfig)``: replicas with full copies of the store, the lease layer, the
transaction dispatcher and forwarder, and the batched certification and
lease settle that the configuration sends to the device.  The benchmark
drives the cluster's own ``Cluster.run``: it runs the event queue a slice
of simulated time at a time, after a warm-up in simulated time, until the
window closes on the host clock; then the cluster stops its clients and
drains every transaction in flight.

Traffic (``bench/traffic/<mix>.json``, drawn by ``bench/tpcc.py``): the
paper's Payment / New-Order mix with its geographic injection: each node's clients
ask for their own region's warehouses, misrouted with ``lb_mistake``.  The
loop is closed: ``clients_per_node`` threads per node each start their next
transaction as soon as the last one finished.  All draws come from the
cluster's seeded generators (``SimConfig.seed = --seed``).

``tm_commits_per_s`` counts the commits acknowledged to clients in the
window over the window's wall seconds.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict

import numpy as np

import harness
import tpcc
from probes import DeviceCalls

SLICE_SIM_MS = 0.5


def build(config: Dict, traffic: Dict, seed: int):
    from repro.core import SimConfig, make_cluster
    from repro.core.workloads import TpccConflictMap

    lay = tpcc.layout(config)
    ccmap = TpccConflictMap(lay)
    wl = tpcc.make_workload(lay, traffic)
    cfg = SimConfig(n_nodes=config["nodes"],
                    threads_per_node=traffic["clients_per_node"],
                    n_items=lay.n_items, n_classes=ccmap.n_classes,
                    seed=int(seed), duration_ms=1e12, warmup_ms=0.0,
                    drain_ms=1e12, **config["sim"])
    return lay, ccmap, wl, cfg, make_cluster(config["algorithm"], wl, cfg,
                                             ccmap=ccmap)


class Recorder:
    """Keeps what the serial replay needs: every commit broadcast, in
    order, and the read log of each transaction's latest execution."""

    def __init__(self, cluster, workload) -> None:
        self.commits = []
        self.reads: Dict[int, np.ndarray] = {}
        self.started = 0                 # transactions begun, for the window
        bcast = cluster.gcs.ur_broadcast
        sample = workload.sample

        def recording_bcast(node, msg, *a, **k):
            if msg[0] == "commit":
                self.commits.append(msg[1])
            return bcast(node, msg, *a, **k)

        def recording_sample(node, rng):
            self.started += 1
            spec = sample(node, rng)
            execute = spec.execute

            def run(store, txn):
                out = execute(store, txn)
                self.reads[txn.txid] = np.frombuffer(txn.read_log, np.int32)
                return out

            return dataclasses.replace(spec, execute=run)

        cluster.gcs.ur_broadcast = recording_bcast
        workload.sample = recording_sample


def warm_shapes(n_items: int) -> None:
    """Compile the certify and settle shapes the mix reaches.

    Certify: batches of 1 to 16 transactions (padded to 8 or 16 rows) of
    Payment's footprint (3 reads, 3 writes: read/write widths 8/8) and of
    New-Order's (11 reads, 6 writes: 16/8; 17 reads, 6 writes when order
    lines repeat a stock row: 32/8; 17 reads, 9 writes: 32/16), each
    through ``validate_batch`` as the cluster calls it.  Settle: up to 16 classes, 8 waiting groups, 8 LORs a group.
    """
    import jax
    from repro.core.stm import Transaction, VersionedStore, validate_batch
    from repro.kernels import ops

    store = VersionedStore(n_items)
    locks = np.zeros((n_items,), np.int32)
    for n in range(1, 17):
        for reads, writes in ((3, 3), (11, 6), (17, 6), (17, 9)):
            txns = []
            for i in range(n):
                t = Transaction(txid=i + 1, origin=0)
                for j in range(reads):
                    store.read(t, j)
                for j in range(writes):
                    store.write(t, j, 0.0)
                txns.append(t)
            validate_batch(store, txns, locks=locks)
    outs = []
    for c in (1, 2, 4, 8, 16):
        for b in (1, 2, 4, 8):
            for k in (1, 2, 4, 8):
                outs.append(ops.settle_lease_batch(
                    np.full((c,), -1, np.int32), np.full((c,), -1, np.int32),
                    np.zeros((c,), np.int32), np.zeros((c,), np.int32),
                    np.zeros((c,), bool), np.full((b, k), -1, np.int32),
                    np.full((b, k), -1, np.int32), 0))
    jax.block_until_ready(outs)


def skip_version_check():
    """Install the control, certification that skips the read-version
    check (each read is compared with the store's own version), which
    breaks the serializability the configuration states; returns a
    function that removes it."""
    from repro.kernels import ops

    real = ops.validate_transactions

    def control(store_versions, read_items, read_versions, write_locks=None,
                write_items=None, **kw):
        items = np.asarray(read_items)
        seen = np.asarray(store_versions)[np.maximum(items, 0)]
        vers = np.where(items >= 0, seen, np.asarray(read_versions))
        return real(store_versions, read_items, vers.astype(np.int32),
                    write_locks=write_locks, write_items=write_items, **kw)

    ops.validate_transactions = control
    return lambda: setattr(ops, "validate_transactions", real)


def run(spec: "harness.Spec") -> "harness.Result":
    if not spec.control:
        return _run(spec)
    undo = skip_version_check()
    try:
        return _run(spec)
    finally:
        undo()


def _run(spec: "harness.Spec") -> "harness.Result":
    from reference import tpcc as ref

    cell, win = spec.cell, spec.window
    lay, _cc, wl, cfg, cluster = build(cell.config, cell.traffic, spec.seed)
    rec = Recorder(cluster, wl)
    harness.stamp("cluster")
    warm_shapes(lay.n_items)
    harness.stamp("warm_shapes")
    m = cluster.metrics
    events = cluster.events
    real_run = events.run
    state: Dict[str, float] = {}

    with DeviceCalls(win) as calls:
        def driven(until, max_events=None):
            if state:                       # Cluster.run's drain after stop
                return real_run(until, max_events)
            real_run(float(cell.traffic["warmup_sim_ms"]))
            harness.stamp("warm_up_sim")
            state["setup_s"] = time.perf_counter() - spec.t_start
            calls.clear()
            state["c0"], state["sim0"] = m.commits, events.now
            state["a0"], state["s0"] = m.aborts, rec.started
            t0 = win.open()
            t_end = t0 + spec.seconds
            while True:
                now = time.perf_counter()
                if win.poll(now):
                    state["ct"], state["simt"] = m.commits, events.now
                if now >= t_end:
                    break
                with win.span("bench.event_loop"):
                    real_run(events.now + SLICE_SIM_MS)
            state["t1"] = win.close()
            state["t0"] = t0
            state["c1"], state["sim1"] = m.commits, events.now
            state["a1"], state["s1"] = m.aborts, rec.started
            state["peaks"] = harness.memory_peaks(spec.devices)

        events.run = driven
        cluster.run()                         # window, then the drain
        events.run = real_run
        t0, t1 = state["t0"], state["t1"]
        m0, m1 = win.measured()
        window_calls = {
            "validate": calls.in_window(m0, m1, "validate"),
            "settle": calls.in_window(m0, m1, "settle")}
        bad = calls.mismatches()

    # -- the guarantee: serial replay against every replica ------------------
    values, versions, stale = ref.replay(
        lay.n_items, cfg.init_value, rec.commits, rec.reads)
    divergent = ref.divergent_items(
        values, versions,
        [(r.store.values, r.store.versions) for r in cluster.replicas])
    unlogged = abs(m.rw_commits - len(rec.commits))
    lim = cell.config["check"]
    checks = [
        harness.Check("verdict_mismatches", bad["validate"],
                      lim["verdict_mismatches"]),
        harness.Check("settle_mismatches", bad["settle"],
                      lim["settle_mismatches"]),
        harness.Check("stale_reads_in_replay", stale,
                      lim["stale_reads_in_replay"]),
        harness.Check("replica_items_off_replay", divergent,
                      lim["replica_items_off_replay"]),
        harness.Check("acked_commits_not_broadcast", unlogged,
                      lim["acked_commits_not_broadcast"]),
        harness.Check("device_calls_checked", len(calls.validate),
                      lim["device_calls_checked"], higher_fails=False),
    ]
    commits = state["c1"] - state["c0"]
    wall = t1 - t0
    sim_s = (state["sim1"] - state["sim0"]) / 1e3
    e2e = {"setup_s": state["setup_s"], "tm_commits_per_s": commits / wall}
    peaks = state["peaks"]
    lines = [
        f"tm: {cell.config['algorithm']} nodes={cfg.n_nodes} "
        f"items={lay.n_items} classes={cfg.n_classes} "
        f"clients_per_node={cfg.threads_per_node}",
        f"window: seconds={wall!r} commits={commits} "
        f"aborts={state['a1'] - state['a0']} sim_ms={1e3 * sim_s!r} "
        f"validate_calls={len(window_calls['validate'])} "
        f"settle_calls={len(window_calls['settle'])} "
        f"compiles_in_window={len(win.compiled)} {sorted(set(win.compiled))}",
        f"run: commits={m.commits} aborts={m.aborts} forwards={m.forwards} "
        f"cert_batches={m.cert_batches} replayed={len(rec.commits)}",
        "memory: peak_bytes_in_use per chip " + " ".join(map(str, peaks)),
        harness.setup_line(spec.t_start),
        f"probes: bookkeeping_s={calls.bookkeeping_s!r} over "
        f"{len(calls.validate) + len(calls.settle)} calls, outside their "
        f"timed spans",
    ]
    c_end, sim_end = ((state.get("ct", state["c1"]),
                       state.get("simt", state["sim1"])) if win.trace
                      else (state["c1"], state["sim1"]))
    records = {"window": win.measured(),
               "trace_window": (win.trace_t0, win.trace_t1),
               "calls": window_calls, "commits": c_end - state["c0"],
               "sim_s": (sim_end - state["sim0"]) / 1e3}
    # a transaction that fails certification re-executes under its leases;
    # none is refused to its client, so none counts as failed
    return harness.Result(end_to_end=e2e,
                          attempted=state["s1"] - state["s0"], failed=0,
                          checks=checks, records=records, lines=lines,
                          memory_peak_bytes=peaks)
