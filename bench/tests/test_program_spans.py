"""The program-span reduction (``bench/program_spans.py``) on a trace
recorded here on the CPU, with device intervals laid over it by hand."""
import time
from pathlib import Path

import jax
import pytest
from jax.profiler import TraceAnnotation

import harness
import program_spans as ps
import trace_reduce
from repro.obs.trace import host_span

BENCH = Path(__file__).resolve().parents[1]
DEV = "/device:TPU:0"


def _record(trace_dir, program: bool = True) -> str:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with TraceAnnotation("bench.event_loop"):
            if not program:
                time.sleep(0.002)
            else:
                with host_span("repro.tm.events"):
                    time.sleep(0.002)                    # the loop's own
                    with host_span("repro.tm.certify", batch=3):
                        with host_span("repro.stm.pack"):
                            time.sleep(0.002)
                        with TraceAnnotation("bench.certify_call"):
                            with host_span("repro.ops.validate",
                                           h2d_bytes=lambda: 4096):
                                time.sleep(0.001)
                            time.sleep(0.002)            # probe's read-back
                        with host_span("repro.readback"):
                            time.sleep(0.001)
                        time.sleep(0.001)                # certify's own
                    time.sleep(0.001)
                time.sleep(0.001)                # between program spans
                with host_span("repro.tm.execute"):
                    time.sleep(0.0005)
        time.sleep(0.001)                        # after the program's part
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.find_xplane(str(trace_dir))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("spans")
    spans, _busy, window = ps.load(_record(trace_dir))
    got = {}
    for _line, s, e, name, args in spans:
        assert name not in got, f"{name} recorded twice"
        got[name] = (s, e, args)
    return trace_dir, spans, window, got


def _dur(got, name):
    return got[name][1] - got[name][0]


def test_self_time_nesting_and_args(recorded):
    _, spans, window, got = recorded
    assert set(got) == {"bench.event_loop", "repro.tm.events",
                        "repro.tm.certify", "repro.stm.pack",
                        "bench.certify_call", "repro.ops.validate",
                        "repro.readback", "repro.tm.execute"}
    red = ps.reduce(spans, {DEV: []}, window)
    d = lambda n: _dur(got, n) / 1e9              # noqa: E731
    self_s = red["self_s"]
    assert self_s["repro.tm.certify"] == pytest.approx(
        d("repro.tm.certify") - d("repro.stm.pack")
        - d("bench.certify_call") - d("repro.readback"), abs=1e-12)
    assert self_s["bench.certify_call"] == pytest.approx(
        d("bench.certify_call") - d("repro.ops.validate"), abs=1e-12)
    assert self_s["repro.tm.events"] == pytest.approx(
        d("repro.tm.events") - d("repro.tm.certify"), abs=1e-12)
    assert self_s["repro.ops.validate"] == pytest.approx(
        d("repro.ops.validate"), abs=1e-12)
    # self times partition the outermost span
    assert sum(self_s.values()) == pytest.approx(d("bench.event_loop"),
                                                 abs=1e-12)
    assert red["inside_s"][("repro.tm.events", "repro.ops.validate")] == \
        pytest.approx(d("repro.ops.validate"), abs=1e-12)
    assert red["args"]["repro.ops.validate"] == [{"h2d_bytes": 4096}]
    assert red["args"]["repro.tm.certify"] == [{"batch": 3}]
    assert red["count"]["repro.readback"] == 1
    # no device plane: every second of the program's part is idle, and
    # that part runs from its first span's start to its last one's end
    program = got["repro.tm.execute"][1] - got["repro.tm.events"][0]
    assert red["program_s"] == pytest.approx(program / 1e9, abs=1e-12)
    assert red["idle_total_s"] == pytest.approx(program / 1e9, abs=1e-12)
    assert red["window_s"] == pytest.approx(window / 1e9)
    assert window > program


def test_idle_goes_to_the_innermost_program_span(recorded):
    _, spans, window, got = recorded
    pack, call = got["repro.stm.pack"], got["bench.certify_call"]
    val = got["repro.ops.validate"]
    # the device runs through the packing and the probe's read-back
    busy = [(pack[0], pack[1]), (val[1], call[1])]
    red = ps.reduce(spans, {DEV: busy}, window)
    d = lambda n: _dur(got, n) / 1e9              # noqa: E731
    idle = red["idle_s"]
    assert idle.get("repro.stm.pack", 0.0) == pytest.approx(0.0, abs=1e-12)
    assert idle["repro.ops.validate"] == pytest.approx(
        d("repro.ops.validate"), abs=1e-12)
    assert idle["repro.readback"] == pytest.approx(d("repro.readback"),
                                                   abs=1e-12)
    # the probe's read-back was busy; the drain's own time, and the
    # probe's before the dispatch, name the drain
    assert idle["repro.tm.certify"] == pytest.approx(
        red["self_s"]["repro.tm.certify"] + (val[0] - call[0]) / 1e9,
        abs=1e-12)
    assert idle["repro.tm.events"] == pytest.approx(
        red["self_s"]["repro.tm.events"], abs=1e-12)
    assert idle["repro.tm.execute"] == pytest.approx(d("repro.tm.execute"),
                                                     abs=1e-12)
    # between the two program spans no program span is open; before the
    # first and after the last lies no part of the program
    assert idle[None] == pytest.approx(
        (got["repro.tm.execute"][0] - got["repro.tm.events"][1]) / 1e9,
        abs=1e-12)
    busy_s = sum(e - s for s, e in busy) / 1e9
    assert red["idle_total_s"] == pytest.approx(
        red["program_s"] - busy_s, abs=1e-12)
    want = 100.0 * (idle[None] + idle["repro.tm.events"]) \
        / red["idle_total_s"]
    assert ps.unattributed_share(red) == pytest.approx(want, rel=1e-12)
    # a second chip that is never busy: each chip's idle is averaged
    two = ps.reduce(spans, {DEV: busy, "/device:TPU:1": []}, window)
    assert two["idle_total_s"] == pytest.approx(
        red["program_s"] - busy_s / 2, abs=1e-12)


def _reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def test_readers_over_the_reduction(recorded, monkeypatch):
    _, spans, window, got = recorded
    red = ps.reduce(spans, {DEV: []}, window)
    monkeypatch.setattr(ps, "for_cell", lambda ctx: red)
    ctx = harness.ReadCtx(cell=None, records={"commits": 2}, trace={},
                          peaks={}, devices=[])
    self_ms = {n: 1e3 * v for n, v in red["self_s"].items()}
    prep = _reader("tm_host_ms_per_commit.prep").read(ctx)
    assert prep == pytest.approx(
        (self_ms["repro.tm.certify"] + self_ms["repro.stm.pack"]
         + self_ms["repro.ops.validate"]) / 2, rel=1e-12)
    wait = _reader("tm_host_ms_per_commit.wait").read(ctx)
    assert wait == pytest.approx(
        (self_ms["repro.readback"] + self_ms["bench.certify_call"]) / 2,
        rel=1e-12)
    proto = _reader("tm_host_ms_per_commit.protocol").read(ctx)
    assert proto == pytest.approx(
        (self_ms["repro.tm.events"] + self_ms["repro.tm.execute"]) / 2,
        rel=1e-12)
    assert _reader("tm_h2d_mb_per_certify").read(ctx) == \
        pytest.approx(0.004096)
    for name in ("idle_unattributed_share.tm",
                 "idle_unattributed_share.sat"):
        assert _reader(name).read(ctx) == ps.unattributed_share(red)
    # no engine step in this trace
    assert _reader("step_host_ms.sat").read(ctx) is None


def test_step_host_ms_less_its_readback(monkeypatch):
    spans = [(1, 0.0, 100.0, "repro.serve.step", {}),
             (1, 10.0, 60.0, "repro.serve.decode", {"pod": 0}),
             (1, 40.0, 55.0, "repro.readback", {}),
             (1, 200.0, 300.0, "repro.serve.step", {}),
             (1, 210.0, 240.0, "repro.readback", {})]
    red = ps.reduce(spans, {DEV: [(40.0, 55.0), (210.0, 240.0)]}, 400.0)
    monkeypatch.setattr(ps, "for_cell", lambda ctx: red)
    ctx = harness.ReadCtx(cell=None, records={}, trace={}, peaks={},
                          devices=[])
    # (100 - 15) and (100 - 30) ns, the mean in ms
    assert _reader("step_host_ms.sat").read(ctx) == pytest.approx(
        1e3 * 77.5e-9, rel=1e-12)
    # the program's part is [0, 300): idle 255 ns, 100 between the steps,
    # 10 + 40 + 10 + 60 in the steps' own time, 35 in the decode, none in
    # the read-backs; [300, 400) lies after the program's last span
    assert red["program_s"] == pytest.approx(300e-9)
    assert red["idle_total_s"] == pytest.approx(255e-9)
    assert red["idle_s"][None] == pytest.approx(100e-9)
    assert red["idle_s"]["repro.serve.step"] == pytest.approx(120e-9)
    assert red["idle_s"].get("repro.readback", 0.0) == 0.0
    assert red["idle_s"]["repro.serve.decode"] == pytest.approx(35e-9)
    assert ps.unattributed_share(red) == pytest.approx(
        100.0 * (255.0 - 35.0) / 255.0)


def test_a_trace_without_program_spans_reads_nothing(tmp_path):
    _record(tmp_path, program=False)
    assert ps.for_trace_dir(tmp_path) is None
    assert ps.for_trace_dir(tmp_path / "missing") is None
