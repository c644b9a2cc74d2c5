"""A later PR adds a traffic mix and a per-layer metric as new files and
new entries; the harness finds them by name, and no existing file changes."""
import json
import shutil
from pathlib import Path

import harness
import trace_reduce

REPO = Path(__file__).resolve().parents[2]


def test_new_mix_and_metric_from_files_alone(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((REPO / "BENCHMARK.json").read_text())

    mix = {"about": "a test mix", "loop": "closed", "turns_mean": 2,
           "output_tokens": {"median": 8, "sigma": 0.1, "min": 4, "max": 16},
           "home_share": 1.0}
    (tmp_path / "bench" / "traffic" / "test-mix.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "device_ops.test.py").write_text(
        '"""Device ops in the trace."""\n\n\n'
        "def read(ctx):\n"
        "    n = sum(ctx.trace['module_n'].values()) if ctx.trace else 0\n"
        "    return n or None\n")
    bench["workloads"].append({
        "name": "glm4-test", "config": "glm4-9b-cut16", "traffic": "test-mix",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "device_ops.test", "unit": "ops", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "tokens_per_s",
        "workloads": ["glm4-test"]})
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"].append("glm4-test")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve("glm4-test", root=tmp_path)
    assert cell.traffic == mix
    assert [m["name"] for m, _ in cell.per_layer] == ["device_ops.test"]
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}

    dev = "/device:TPU:0"
    events = [(dev, "XLA Modules", "jit_step(1)", 0.0, 10.0),
              (dev, "XLA Ops", "%a = f32[] add(..)", 0.0, 10.0),
              (dev, "XLA Modules", "jit_step(1)", 20.0, 10.0)]
    ctx = harness.ReadCtx(cell=cell, records={},
                          trace=trace_reduce.reduce(events, 40.0),
                          peaks={}, devices=[])
    assert harness.read_per_layer(cell, ctx) == {
        "device_ops.test": {"value": 2.0, "unit": "ops"}}

    after = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
             if p.is_file() and p in before}
    assert after == before
