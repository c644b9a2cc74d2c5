"""``bench/hlo_scopes.py``: the compiled decode step's operations carry the
program's named scopes, and a reduced trace's op seconds add up per scope
(CPU, small sizes)."""
import dataclasses

import hlo_scopes

SCOPES = ("repro.mla", "repro.moe.route", "repro.moe.experts",
          "repro.moe.shared")


def test_decode_step_ops_map_to_every_scope():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.models import decoder
    from repro.models.common import init_params

    base = get_smoke_config("deepseek-v2-236b")
    cfg = dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, held_first=0, n_held=2))
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    caches = decoder.init_cache(cfg, 4, 16, jnp.bfloat16)
    ctx = decoder.RunCtx(mesh=None, use_kernel="ref")
    ids = np.zeros((4,), np.int32)
    text = jax.jit(lambda p, c, t, i: decoder.decode_step(
        cfg, ctx, p, c, t, i, return_stats=True)).lower(
            params, caches, ids, ids).compile().as_text()
    found = hlo_scopes.op_scopes(text, SCOPES)
    assert set(found.values()) == set(SCOPES)
    assert not any(name.startswith("while") for name in found)


def test_innermost_scope_wins_and_containers_are_left_out():
    text = "\n".join([
        '  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%c1, '
        'metadata={op_name="jit(step)/while/body/repro.mla/dot_general"}',
        '  ROOT %fusion.2 = f32[2]{0} fusion(%p), kind=kLoop, calls=%c2, '
        'metadata={op_name="jit(step)/repro.moe.route/repro.moe.experts/'
        'ragged_dot" source_file="moe.py"}',
        '  %copy.3 = f32[2]{0} copy(%p), metadata={op_name="jit(step)/copy"}',
        '  %fusion.4 = f32[2]{0} fusion(%p), kind=kLoop, calls=%c4, '
        'metadata={op_name="jit(step)/repro.mlax/add"}',
        '  %while.5 = (s32[], bf16[4,8]{1,0}) while(%tuple.1), '
        'condition=%cond, body=%body, '
        'metadata={op_name="jit(step)/repro.mla/scatter"}',
    ])
    assert hlo_scopes.op_scopes(text, SCOPES) == {
        "fusion.1": "repro.mla", "fusion.2": "repro.moe.experts"}


def test_scope_seconds_sums_a_programs_ops():
    trace = {"op_s": {"jit_step/%fusion.1": 0.5, "jit_step/%fusion.2": 0.25,
                      "jit_step/%fusion.9": 1.0, "jit_other/%fusion.1": 7.0,
                      "jit_step/%while.3": 2.0}}
    scopes = {"fusion.1": "repro.mla", "fusion.2": "repro.mla",
              "fusion.9": "repro.moe.experts"}
    assert hlo_scopes.scope_seconds(trace, "jit_step", scopes) == {
        "repro.mla": 0.75, "repro.moe.experts": 1.0}
    assert hlo_scopes.scope_seconds(None, "jit_step", scopes) == {}
