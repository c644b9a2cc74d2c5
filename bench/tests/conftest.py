"""Tests of the benchmark's own code: ``python -m pytest bench/tests``.

They run on the CPU at small sizes; nothing here looks for a chip.
"""
import os
import sys
from pathlib import Path

# four host devices, so a four-pod open-loop mix runs here as four one-device
# replicas (set before JAX starts; one-device cells use the first)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_cell(name: str):
    """The named cell with its model and data cut to CPU test size; the
    traffic, the drivers, the checks and their limits are the cell's own."""
    import harness

    cell = harness.resolve(name)
    if cell.config["driver"] == "serve":
        cell.config["model"].update(n_layers=2, d_model=64, n_heads=4,
                                    n_kv_heads=2, head_dim=16, d_ff=192,
                                    vocab_size=256)
        cell.config["serving"].update(slots_per_pod=8, max_len=256)
        cell.config["check"]["tokens"] = 64
        cell.traffic["output_tokens"].update(median=16, min=4, max=64)
    else:
        cell.config.update(customers_per_district=30,
                           stock_per_warehouse=200, items=300)
        cell.config["check"]["device_calls_checked"] = 20
    return cell


def run_cell(cell, seed: int = 11, seconds: float = 2.0) -> dict:
    """A whole run after the look for chips: set-up, window, check."""
    import time

    import jax
    import run

    return run.execute(cell, jax.devices()[:int(cell.entry["chips"])], seed,
                       seconds, False, time.perf_counter())


def run_driver(cell, seed: int = 11, seconds: float = 2.0,
               control: bool = False):
    """The driver's :class:`harness.Result` of one run (records kept);
    with ``control``, of the cell's control."""
    import time

    import harness
    import jax

    harness.enable_compile_cache()
    win = harness.Window(False, harness.CACHE_DIR / "test")
    spec = harness.Spec(cell=cell, seed=seed, seconds=seconds, trace=False,
                        devices=jax.devices()[:int(cell.entry["chips"])],
                        t_start=time.perf_counter(), window=win,
                        control=control)
    return cell.driver.run(spec)
