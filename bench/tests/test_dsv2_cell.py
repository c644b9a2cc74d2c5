"""The DeepSeek-V2 serving cell at CPU size: ``correct`` holds for a sound
run and comes out false for the float8 control, for a step that leaves out
the shared experts and for routing without the group limit.  The traffic,
the driver, the checks and their limits are the cell's own; the model
keeps every mechanism and the cell's routing (MLA with YaRN, 160 experts
in 8 groups, top-6 of the best 3 groups, gates scaled by 16, 2 shared
experts, a dense first layer, the held share of group 0) at small
widths."""
import pytest
from conftest import run_cell, run_driver, tiny_cell

CELL = "dsv2-1chip-saturated"


@pytest.fixture
def cell():
    cell = tiny_cell(CELL)
    m = cell.config["model"]
    m.update(n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
             vocab_size=256)
    m["mla"].update(q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=16, v_head_dim=16)
    m["moe"].update(d_expert=32, d_shared=32, d_first_dense=128)
    cell.config["serving"].update(slots_per_pod=8, max_len=256)
    cell.config["check"].update(tokens=64, per_slot_tokens=32)
    cell.traffic["output_tokens"].update(median=16, min=4, max=64)
    return cell


def _gap(res):
    return [c.value for c in res.checks if c.name == "mean_logit_gap"][0]


def test_sound_run_is_correct_and_counts_pairs(cell):
    line = run_cell(cell)
    assert line["correct"] is True
    res = run_driver(cell, seed=12)
    assert res.correct
    steps = res.records["moe_steps"]
    assert steps and all(len(s) == 6 for s in steps)
    # 8 slots, top-6, 2 MoE layers: at most 96 pairs a step
    assert 0 < sum(s[4] for s in steps) <= 96 * len(steps)
    assert all(0 <= s[5] <= 2 * 20 for s in steps)


def test_float8_control_fails_the_limit(cell):
    program = run_driver(cell, seed=13)
    control = run_driver(cell, seed=13, control=True)
    assert program.correct and not control.correct
    assert _gap(control) > _gap(program)


def test_step_without_shared_experts_fails(cell, monkeypatch):
    import jax.numpy as jnp
    from repro.models import moe

    monkeypatch.setattr(moe, "mlp_apply", lambda p, x, act: jnp.zeros_like(x))
    res = run_driver(cell, seed=14)
    assert not res.correct
    assert _gap(res) > cell.config["check"]["mean_logit_gap"]


def test_routing_without_group_limit_fails(cell, monkeypatch):
    import dataclasses

    from repro.models import moe

    route = moe.route

    def ungrouped(x, router, m):
        return route(x, router, dataclasses.replace(m, n_group=1,
                                                    topk_group=1))

    monkeypatch.setattr(moe, "route", ungrouped)
    res = run_driver(cell, seed=15)
    assert not res.correct
    assert _gap(res) > cell.config["check"]["mean_logit_gap"]
