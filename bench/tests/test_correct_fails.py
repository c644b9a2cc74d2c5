"""``correct`` comes out false when the timed path is broken underneath,
and the controls fail the limits that sound runs pass (CPU, small sizes).

Serving: a token altered where it is produced; a step that leaves its
cache unchanged; a step that serves half of its batch and hands the other
half those tokens; the float8 control in the program's place.
Lilac-TM: a verdict altered where it is produced; commits that leave the
replica stores unchanged; the control that skips the read-version check.
"""
import numpy as np
import pytest
from conftest import run_cell, run_driver, tiny_cell


@pytest.fixture
def serve_cell():
    return tiny_cell("glm4-1pod-saturated")


@pytest.fixture
def tm_cell():
    return tiny_cell("tpcc-lilac-4node")


def test_sound_serving_run_is_correct(serve_cell):
    assert run_cell(serve_cell)["correct"] is True


def test_altered_token_fails(serve_cell, monkeypatch):
    from repro.serve.engine import RealBackend

    real = RealBackend.step
    calls = {"n": 0}

    def altered(self, pod, sids):
        out = real(self, pod, sids)
        calls["n"] += 1
        if calls["n"] % 7 == 0:
            for sid in out:
                s = self.stores[pod].sessions[sid]
                s.last_token = out[sid] = (out[sid] + 1) % self.cfg.vocab_size
        return out

    monkeypatch.setattr(RealBackend, "step", altered)
    line = run_cell(serve_cell)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > \
        line["checks"]["max_logit_gap"]["limit"]


def test_step_leaving_its_cache_unchanged_fails(serve_cell, monkeypatch):
    from repro.serve.engine import RealBackend

    real = RealBackend.step

    def stale(self, pod, sids):
        caches = self.stores[pod].caches
        out = real(self, pod, sids)
        self.stores[pod].caches = caches
        return out

    monkeypatch.setattr(RealBackend, "step", stale)
    assert run_cell(serve_cell)["correct"] is False


def test_half_batch_served_to_the_other_half_fails(serve_cell, monkeypatch):
    from repro.serve.engine import RealBackend

    real = RealBackend.step

    def halved(self, pod, sids):
        out = real(self, pod, sids)
        order = sorted(out)
        half = len(order) // 2
        for src, dst in zip(order[:half], order[half:2 * half]):
            out[dst] = out[src]
            self.stores[pod].sessions[dst].last_token = out[src]
        return out

    monkeypatch.setattr(RealBackend, "step", halved)
    line = run_cell(serve_cell)
    assert line["correct"] is False
    assert line["checks"]["max_logit_gap"]["value"] > \
        line["checks"]["max_logit_gap"]["limit"]


def test_check_covers_every_slot(serve_cell):
    res = run_driver(serve_cell, seed=12)
    assert res.correct
    check = [ln for ln in res.lines if ln.startswith("check:")][0]
    assert f"slots={serve_cell.config['serving']['slots_per_pod']} " in check
    prompts = {q[0] for q in res.records["check_seqs"]}
    assert len(prompts) > 1


def test_float8_control_fails_the_limit(serve_cell):
    program = run_driver(serve_cell, seed=13)
    control = run_driver(serve_cell, seed=13, control=True)
    assert program.correct and not control.correct
    gap = {r: [c.value for c in res.checks if c.name == "max_logit_gap"][0]
           for r, res in (("program", program), ("control", control))}
    assert gap["control"] >= 3 * max(gap["program"], 0.01)


def test_sound_tm_run_is_correct(tm_cell):
    assert run_cell(tm_cell)["correct"] is True


def test_altered_verdict_fails(tm_cell, monkeypatch):
    from repro.kernels import ops

    real = ops.validate_transactions
    calls = {"n": 0}

    def flipped(*a, **k):
        out = np.asarray(real(*a, **k)).copy()
        calls["n"] += 1
        if calls["n"] % 5 == 0:
            out[0] = ~out[0]
        return out

    monkeypatch.setattr(ops, "validate_transactions", flipped)
    line = run_cell(tm_cell)
    assert line["correct"] is False
    assert line["checks"]["verdict_mismatches"]["value"] > 0


def test_commits_leaving_stores_unchanged_fail(tm_cell, monkeypatch):
    from repro.core.stm import VersionedStore

    monkeypatch.setattr(VersionedStore, "apply_versioned",
                        lambda self, ws, v: None)
    line = run_cell(tm_cell)
    assert line["correct"] is False
    assert line["checks"]["replica_items_off_replay"]["value"] > 0


def test_skipped_version_check_control_fails(tm_cell):
    res = run_driver(tm_cell, control=True)
    assert not res.correct
    stale = [c for c in res.checks if c.name == "stale_reads_in_replay"][0]
    assert stale.value > stale.limit
