"""The reduction from a profiler trace to busy time and program time."""
import json
from pathlib import Path

import numpy as np
import pytest

import trace_reduce as tr

SAMPLE = Path(__file__).parent / "data" / "tpu_trace_sample.json"


def test_hand_made_trace():
    dev = "/device:TPU:0"
    events = [
        (dev, "XLA Modules", "jit_step(11)", 0.0, 100.0),
        (dev, "XLA Ops", "%a = bf16[2] fusion(..)", 10.0, 30.0),
        (dev, "XLA Ops", "%b = bf16[2] fusion(..)", 20.0, 40.0),  # overlaps a
        (dev, "XLA Modules", "jit_f(12)", 150.0, 20.0),
        (dev, "XLA Ops", "%c = s32[] add(..)", 150.0, 5.0),
        ("/host:CPU", "main", "bench.decode", 0.0, 120.0),
        ("/host:CPU", "main", "bench.engine", 0.0, 200.0),
    ]
    red = tr.reduce(events, 200.0)
    assert red["busy_s"][dev] == pytest.approx(55e-9)      # [10,60) + [150,155)
    assert red["module_s"] == pytest.approx({"jit_step": 50e-9,
                                             "jit_f": 5e-9})
    assert red["module_n"] == {"jit_step": 1, "jit_f": 1}
    assert red["op_s"]["jit_step/%b"] == pytest.approx(40e-9)
    # gap [0,10) lies in both spans: the inner one (opened last; on a tie,
    # the first listed) takes it; [60,150) lies mostly outside decode and
    # [155,200) wholly, so engine takes them
    labels = tr.breakdown(red)["idle_gaps"]
    assert labels == [["bench.engine", pytest.approx(135e-9)],
                      ["bench.decode", pytest.approx(10e-9)]]


def test_recorded_tpu_trace_against_a_bitmap():
    """The first 3 ms of device ops of a traced ``glm4-1pod-saturated`` run
    on a TPU v5e (``data/tpu_trace_sample.json``): busy time against a
    nanosecond bitmap of the same ops."""
    data = json.loads(SAMPLE.read_text())
    events = [tuple(e) for e in data["events"]]
    window = data["window_ns"]
    red = tr.reduce(events, window)
    for dev, busy in red["busy_s"].items():
        ops = [e for e in events if e[0] == dev and e[1] == "XLA Ops"]
        bits = np.zeros(int(window) + 1, bool)
        for _, _, _, s, d in ops:
            bits[int(s):int(min(s + d, window))] = True
        assert busy == pytest.approx(bits.sum() / 1e9, abs=len(ops) * 1e-9)
        assert 0 < busy <= window / 1e9
    # the sample cuts through a decode step: no program run lies wholly in
    # it, so every op is filed under "?"
    assert red["module_n"] == {}
    assert sum(red["op_s"].values()) >= sum(red["busy_s"].values())
    assert all(name.startswith("?/%") for name in red["op_s"])
