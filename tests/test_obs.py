"""repro.obs tests: quantile metrics, trace schema, determinism, zero-cost,
and the host-clock spans on the profiler's clock."""
import dataclasses
import glob
import json

import jax
import numpy as np
import pytest

from repro.core import BankWorkload, SimConfig, make_cluster
from repro.obs import trace as obs_trace
from repro.obs.metrics import Counter, Histogram, MetricSet, Registry
from repro.obs.trace import NO_SPAN, NULL, HostClock, TraceRecorder, host_span


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def test_histogram_quantiles_match_numpy():
    rng = np.random.default_rng(0)
    for samples in (rng.exponential(3.0, 257), rng.normal(10.0, 2.0, 64),
                    np.array([4.2]), np.arange(100.0)):
        h = Histogram("lat")
        for v in samples:
            h.observe(float(v))
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            want = float(np.percentile(samples, 100.0 * q))
            assert h.quantile(q) == pytest.approx(want, rel=1e-12, abs=1e-12)


def test_histogram_slo_attainment_and_summary():
    h = Histogram("lat")
    assert h.quantile(0.5) is None
    assert h.slo_attainment(1.0) is None
    for v in range(1, 11):
        h.observe(float(v))
    assert h.slo_attainment(5.0) == 0.5
    assert h.slo_attainment(10.0) == 1.0
    assert h.slo_attainment(0.5) == 0.0
    s = h.summary()
    assert s["count"] == 10
    assert s["p50"] == pytest.approx(np.percentile(range(1, 11), 50))
    assert set(s) == {"count", "p50", "p90", "p99"}


def test_registry_and_scalar_metrics():
    r = Registry()
    c = r.counter("steps")
    c.inc()
    c.inc(4)
    assert r.counter("steps").value == 5       # same object on re-access
    r.histogram("lat").observe(2.0, n=3)
    d = r.as_dict()
    assert d["steps"] == 5
    assert d["lat"]["count"] == 3
    assert "steps" in r and "missing" not in r
    assert isinstance(r.counter("steps"), Counter)


def test_metricset_facade_routes_to_registry():
    class M(MetricSet):
        FIELDS = {"forwards": 0, "wire_s": 0.0}

    m = M()
    m.forwards += 1
    m.forwards += 2
    m.wire_s += 0.25
    assert m.forwards == 3
    assert m.registry.counter("forwards").value == 3
    assert m.as_dict() == {"forwards": 3, "wire_s": 0.25}
    # non-FIELDS attributes behave like normal instance attributes
    m.note = "x"
    assert m.note == "x" and "note" not in m.registry
    with pytest.raises(AttributeError):
        _ = m.nonexistent


def test_monotonic_sampler_with_fake_clock():
    """The monotonic wall-clock seam, ``HostClock``, sums each span's time
    into its counter and hands the caller none."""
    ticks = iter([10.0, 10.5, 11.0, 13.25])
    hc = HostClock(clock=lambda: next(ticks))
    into = Counter("plan_block_s", 0.0)
    with hc.host_span("repro.serve.plan", into) as got:
        assert got is None           # the span hands its caller no time
    assert into.value == pytest.approx(0.5)
    with hc.host_span("repro.serve.plan", into, batch=3):
        pass
    assert into.value == pytest.approx(2.75)
    # an exception still closes the span and counts its time, and escapes
    ticks = iter([0.0, 1.0])
    hc = HostClock(clock=lambda: next(ticks))
    with pytest.raises(KeyError):
        with hc.host_span("repro.serve.plan", into):
            raise KeyError("x")
    assert into.value == pytest.approx(3.75)


def test_host_span_off_is_the_shared_noop_and_builds_no_args():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    calls = []

    def nbytes():
        calls.append(1)
        return 8

    span = host_span("repro.ops.validate", h2d_bytes=nbytes)
    assert span is NO_SPAN and host_span("repro.tm.events") is NO_SPAN
    with span as got:
        assert got is None
    assert calls == []               # the lazy arg was never evaluated


# --------------------------------------------------------------------------
# trace recorder + schema
# --------------------------------------------------------------------------

def test_trace_schema_roundtrip(tmp_path):
    tr = TraceRecorder()
    tr.span("exec", "node0/t0", 1.0, 2.5, txid=7)
    tr.instant("forward", "node0/dtd", ts=1.25, target=1)
    tr.abegin("lease-round", "node1/lease", 42, ts=0.5, ccs=3)
    tr.aend("lease-round", "node1/lease", 42, ts=3.5)
    tr.counter("depth", "node0/gcs", 2.0, 9)
    tr.set_time(8.0)
    tr.instant("late", "node0/gcs")          # ts=None -> last set_time
    assert len(tr) == 6

    path = tmp_path / "t.json"
    tr.export(str(path))
    raw = json.loads(path.read_text())
    assert set(raw) == {"traceEvents", "displayTimeUnit"}
    events = obs_trace.load(str(path))
    meta = [e for e in events if e["ph"] == "M"]
    data = [e for e in events if e["ph"] != "M"]
    # every track got process_name + thread_name metadata
    assert {e["name"] for e in meta} == {"process_name", "thread_name"}
    procs = {e["args"]["name"] for e in meta if e["name"] == "process_name"}
    assert procs == {"node0", "node1"}
    # ph/ts schema: X carries dur, i carries s, b/e carry id; ts is us
    by_ph = {e["ph"]: e for e in data}
    assert by_ph["X"]["dur"] == 2500.0 and by_ph["X"]["ts"] == 1000.0
    assert by_ph["i"]["s"] == "t"
    assert by_ph["b"]["id"] == "42" and by_ph["e"]["id"] == "42"
    assert by_ph["C"]["args"]["value"] == 9
    assert [e["name"] for e in data] == ["exec", "forward", "lease-round",
                                         "lease-round", "depth", "late"]
    assert data[-1]["ts"] == 8000.0
    # distinct tracks get distinct (pid, tid) pairs
    keys = {(e["pid"], e["tid"]) for e in data}
    assert len(keys) == 4

    # summarize sees X durations and matched b/e pairs
    rows = {r["name"]: r for r in obs_trace.summarize(events)}
    assert rows["exec"]["total_us"] == 2500.0
    assert rows["lease-round"]["total_us"] == 3000.0
    assert rows["forward"]["count"] == 1
    # bare-list form loads too
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(events))
    assert obs_trace.load(str(bare)) == events


def test_trace_diff_and_null_recorder():
    a = TraceRecorder()
    a.span("exec", "n0", 0.0, 1.0)
    b = TraceRecorder()
    b.span("exec", "n0", 0.0, 1.0)
    b.span("exec", "n0", 2.0, 3.0)
    b.instant("abort", "n0", ts=1.0)
    rows = {r["name"]: r for r in obs_trace.diff(a.to_events(), b.to_events())}
    assert rows["exec"]["d_count"] == 1
    assert rows["exec"]["d_total_us"] == pytest.approx(3000.0)
    assert rows["abort"]["count_a"] == 0 and rows["abort"]["count_b"] == 1
    # the disabled recorder records nothing and reports enabled=False
    assert NULL.enabled is False and TraceRecorder.enabled is True
    NULL.span("x", "t", 0.0, 1.0)
    NULL.instant("x", "t")
    NULL.counter("x", "t", 0.0, 1)


def test_install_uninstall_singleton():
    assert obs_trace.TRACE is NULL
    rec = TraceRecorder()
    obs_trace.install(rec)
    try:
        assert obs_trace.TRACE is rec
    finally:
        obs_trace.uninstall()
    assert obs_trace.TRACE is NULL


# --------------------------------------------------------------------------
# sim: determinism + zero-perturbation
# --------------------------------------------------------------------------

def _sim_result(trace: bool, lease_mode: str, seed: int = 0):
    cfg = SimConfig(duration_ms=60.0, warmup_ms=10.0, seed=seed,
                    lease_mode=lease_mode, trace=trace)
    wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items, locality=0.8)
    c = make_cluster("LILAC-TM-OPT", wl, cfg)
    m = c.run()
    return c, {"throughput": c.throughput(), "forwards": m.forwards,
               "aborts": m.aborts, "reuse": m.lease_reuse_rate()}


@pytest.mark.parametrize("lease_mode", ["batched", "sequential"])
def test_tracing_does_not_perturb_sim(lease_mode):
    _, off = _sim_result(False, lease_mode)
    c_on, on = _sim_result(True, lease_mode)
    assert off == on
    assert c_on.trace is not None and len(c_on.trace) > 0


def test_seeded_traces_are_byte_identical(tmp_path):
    paths = []
    for i in range(2):
        c, _ = _sim_result(True, "batched")
        p = tmp_path / f"run{i}.json"
        c.trace.export(str(p))
        paths.append(p)
    assert paths[0].read_bytes() == paths[1].read_bytes()
    # and the trace actually carries the protocol vocabulary
    names = {e["name"] for e in obs_trace.load(str(paths[0]))
             if e["ph"] != "M"}
    assert "exec" in names and "lease-round" in names
    assert "certify-batch" in names


def _certify_cluster(seed: int = 3):
    """A small bank cluster whose every certification and lease settle
    takes the device path."""
    cfg = SimConfig(duration_ms=25.0, warmup_ms=5.0, drain_ms=25.0,
                    seed=seed, certify_jax_min=1, lease_jax_min=1)
    wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items, locality=0.8)
    return make_cluster("LILAC-TM-OPT", wl, cfg)


def _profiled_run(trace_dir):
    c = _certify_cluster()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        m = c.run()
    finally:
        jax.profiler.stop_trace()
    return c, m


def _host_spans(trace_dir):
    """``(name, start_ns, end_ns)`` of every ``repro.`` span in the
    profile, read back with ``ProfileData``."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    data = ProfileData.from_file(path[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def test_profiled_certify_nests_its_host_spans(tmp_path):
    _profiled_run(tmp_path)
    spans = _host_spans(tmp_path)
    certs = [s for s in spans if s[0] == "repro.tm.certify"]
    assert certs
    inner = {"repro.tm.write_locks", "repro.stm.pack", "repro.ops.validate",
             "repro.readback", "repro.tm.apply"}
    for _, c0, c1 in certs:
        inside = {n for n, s0, s1 in spans if c0 <= s0 and s1 <= c1}
        assert inner <= inside, inside
    # every certification drain runs inside the event loop's span
    loops = [s for s in spans if s[0] == "repro.tm.events"]
    assert all(any(l0 <= c0 and c1 <= l1 for _, l0, l1 in loops)
               for _, c0, c1 in certs)
    names = {n for n, _, _ in spans}
    assert {"repro.tm.deliver", "repro.tm.execute", "repro.tm.settle",
            "repro.ops.settle"} <= names


def test_profiled_run_matches_unprofiled(tmp_path):
    c_on, m_on = _profiled_run(tmp_path)
    c_off = _certify_cluster()
    m_off = c_off.run()
    assert dataclasses.asdict(m_on) == dataclasses.asdict(m_off)
    assert m_off.cert_batches > 0
    for r_on, r_off in zip(c_on.replicas, c_off.replicas):
        assert np.array_equal(r_on.store.values, r_off.store.values)
        assert np.array_equal(r_on.store.versions, r_off.store.versions)


def test_untraced_sim_allocates_no_recorder():
    cfg = SimConfig(duration_ms=20.0, warmup_ms=5.0, seed=0)
    wl = BankWorkload(n_nodes=cfg.n_nodes, n_items=cfg.n_items)
    c = make_cluster("LILAC-TM-OPT", wl, cfg)
    assert c.trace is None
    c.run()


# --------------------------------------------------------------------------
# engine: per-pod breakdown + zero-perturbation
# --------------------------------------------------------------------------

def _engine_run(trace, pods=2, sessions=8, steps=8, seed=0):
    from repro.configs import get_config
    from repro.serve.engine import MultiPodEngine, Request, SimBackend
    from repro.serve.router import LocalityRouter

    cfg = get_config("mixtral-8x7b")
    kv = 2.0 * 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_layers \
        if cfg.n_kv_heads else 4096.0 * cfg.n_layers
    router = LocalityRouter(pods, policy="short", arbitration="priced",
                            kv_bytes_per_token=kv)
    eng = MultiPodEngine(pods, SimBackend(cfg), router, trace=trace)
    rng = np.random.default_rng(seed)
    for _ in range(steps):
        for _ in range(2 * pods):
            sid = int(rng.integers(sessions))
            origin = sid % pods if rng.random() < 0.5 \
                else int(rng.integers(pods))
            eng.submit(Request(sid=sid, origin=origin, n_tokens=4))
        eng.run_step()
    eng.drain()
    return eng


def test_engine_per_pod_breakdown_sums_to_fleet():
    eng = _engine_run(trace=False)
    m = eng.metrics.as_dict()
    per_pod = m["per_pod"]
    assert set(per_pod) == {0, 1}
    assert sum(p["forwards"] for p in per_pod.values()) == m["forwards"]
    assert sum(p["local"] for p in per_pod.values()) == m["local"]
    assert sum(p["wire_GB"] for p in per_pod.values()) == \
        pytest.approx(m["wire_GB"])
    # fleet token-latency quantiles present and ordered
    assert m["token_lat_p50_s"] <= m["token_lat_p90_s"] \
        <= m["token_lat_p99_s"]
    for p in per_pod.values():
        assert {"token_lat_p50_s", "token_lat_p99_s"} <= set(p)
    # the per-pod histograms partition the fleet histogram
    fleet = eng.metrics.token_latency()
    assert sum(eng.metrics.token_latency(p).count for p in per_pod) \
        == fleet.count


def test_engine_tracing_does_not_perturb_metrics():
    off = _engine_run(trace=False).metrics.as_dict()
    eng_on = _engine_run(trace=True)
    assert eng_on.trace is not None and len(eng_on.trace) > 0
    assert off == eng_on.metrics.as_dict()
    names = {e["name"] for e in eng_on.trace.to_events() if e["ph"] != "M"}
    assert {"wire", "certify", "decode"} <= names


def test_engine_trace_flag_forms():
    assert _engine_run(trace=None, steps=1).trace is None
    assert _engine_run(trace=False, steps=1).trace is None
    rec = TraceRecorder()
    assert _engine_run(trace=rec, steps=1).trace is rec


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def test_repro_trace_cli(tmp_path, capsys):
    from repro.obs import cli

    out = tmp_path / "trace.json"
    rc = cli.main(["export", "--out", str(out), "--steps", "4",
                   "--sessions", "4", "--no-moe"])
    assert rc == 0
    events = obs_trace.load(str(out))
    assert any(e["ph"] == "X" for e in events)
    assert cli.main(["summarize", str(out)]) == 0
    assert cli.main(["diff", str(out), str(out)]) == 0
    text = capsys.readouterr().out
    assert "no per-name differences" in text
    assert cli.main([]) == 2
    assert cli.main(["--help"]) == 0
