"""The hybrid Mamba-2 / MoE / GQA serving cell at CPU size: ``correct``
holds for a sound run and comes out false for the float8 control, for a
step that carries a slot's recurrent state over into the next session in
that slot, and for routing that drops the correction bias.  The traffic,
the driver, the checks and their limits are the cell's own; the model
keeps every mechanism and the cell's routing (the pattern ``MEMEM*E``;
Mamba-2 with grouped B/C and the grouped gated norm; 128 experts, top-6
of sigmoid scores with a correction bias, gates renormalised x2.5, relu²
experts and a shared one; GQA without rotary) at small widths."""
import pytest
from conftest import run_cell, run_driver, tiny_cell

CELL = "nemo3-1chip-saturated"


@pytest.fixture
def cell():
    cell = tiny_cell(CELL)
    m = cell.config["model"]
    m.update(d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             vocab_size=256)
    m["moe"].update(d_expert=32, d_shared=32)
    m["ssm"].update(heads=4, head_dim=16, d_state=16, n_groups=2, chunk=32)
    cell.config["serving"].update(slots_per_pod=8, max_len=256)
    cell.config["check"].update(tokens=64, per_slot_tokens=32)
    cell.traffic["output_tokens"].update(median=16, min=4, max=64)
    return cell


def _stat(res):
    return [c for c in res.checks if c.name.endswith("_logit_gap")][0]


def test_sound_run_is_correct_and_counts_pairs(cell):
    line = run_cell(cell)
    assert line["correct"] is True
    res = run_driver(cell, seed=12)
    assert res.correct
    steps = res.records["moe_steps"]
    assert steps and all(len(s) == 6 for s in steps)
    # 8 slots, top-6, 3 MoE blocks: 144 pairs a step, all experts held
    assert all(s[4] == 144 for s in steps)
    assert all(0 < s[5] <= 3 * 128 for s in steps)


def test_float8_control_fails_the_limit(cell):
    program = run_driver(cell, seed=13)
    control = run_driver(cell, seed=13, control=True)
    assert program.correct and not control.correct
    assert _stat(control).value > _stat(program).value


def test_state_carried_into_a_reused_slot_fails(cell, monkeypatch):
    """The Mamba blocks are told no slot starts a session (position 0 is
    read as 1), so a new session in a reused slot decodes from the last
    session's state."""
    import jax.numpy as jnp
    from repro.models import decoder

    mamba = decoder.mamba2_block

    def leaky(p, x, cfg, *, cache_index=None, **kw):
        if cache_index is not None:
            cache_index = jnp.where(cache_index == 0, 1, cache_index)
        return mamba(p, x, cfg, cache_index=cache_index, **kw)

    monkeypatch.setattr(decoder, "mamba2_block", leaky)
    res = run_driver(cell, seed=14)
    assert not res.correct
    assert _stat(res).value > _stat(res).limit


def test_routing_without_correction_bias_fails(cell, monkeypatch):
    from repro.models import moe

    route = moe.route
    monkeypatch.setattr(moe, "route",
                        lambda x, router, m, bias=None: route(x, router, m))
    res = run_driver(cell, seed=15)
    assert not res.correct
    assert _stat(res).value > _stat(res).limit
