"""Serving driver for latent attention and routed experts (DeepSeek-V2).

The same timed path as ``serve.py`` (its ``run``, ``Client``, ``warm_up``,
window and result code, on a private copy of that module) with what the
model changes put in its place:

* the model: ``ModelConfig`` with its ``MLAConfig``, ``MoEConfig`` (the
  expert share this chip holds) and YaRN rotary from the configuration's
  ``model`` block;
* the weights: ``bench/weights.py``'s draw, with ``q_norm`` and ``kv_norm``
  taken as RMSNorm gains (ones) like the ``ln_*`` leaves;
* the check: the served tokens' mean reference logit gap, against
  ``reference/deepseek_v2.py``;
* the records: ``records["moe_steps"]``, each step's entry of
  ``records["steps"]`` with its ``moe_pairs`` and ``moe_experts`` (the
  program's counters, read back with the argmax) appended; in a traced
  run, ``records["op_scopes"]``, each operation of the compiled
  ``jit_step`` mapped to its named scope (``bench/hlo_scopes.py``).
"""
from __future__ import annotations

import importlib.util
import sys
import types
from pathlib import Path
from typing import Dict, List

import harness
import hlo_scopes
import weights

SCOPES = ("repro.mla", "repro.moe.route", "repro.moe.experts",
          "repro.moe.shared")
GAINS = ("q_norm", "kv_norm")


def _private_serve():
    """``serve.py`` loaded as a module of this driver's own, so what is put
    in its place here never reaches the serving cells' driver."""
    path = Path(__file__).with_name("serve.py")
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_serve_mla_moe_base", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


serve = _private_serve()


def model_config(cfg: Dict):
    from repro.models.common import (MLAConfig, MoEConfig, ModelConfig,
                                     YarnScaling)

    m = dict(cfg["model"])
    return ModelConfig(**{**m, "mla": MLAConfig(**m["mla"]),
                          "moe": MoEConfig(**m["moe"]),
                          "rope_scaling": YarnScaling(**m["rope_scaling"])})


def is_gain(name: str) -> bool:
    return weights.is_gain(name) or name.rsplit("/", 1)[-1] in GAINS


def make_params(shapes, seed: int, dtype) -> Dict:
    """The whole tree drawn on the device in one jit, as
    ``weights.make_params`` draws it, the MLA norms' gains as ones."""
    import jax
    import jax.numpy as jnp

    names, leaf_shapes, treedef = weights.leaf_names(shapes)

    @jax.jit
    def build(keys):
        return [jnp.ones(shape, dtype) if is_gain(name)
                else weights.draw(keys[i], shape, shape[-2], dtype)
                for i, (name, shape) in enumerate(zip(names, leaf_shapes))]

    return jax.tree_util.tree_unflatten(
        treedef, build(weights.leaf_keys(seed, names)))


class Client(serve.Client):
    """``serve.Client`` that also records the expert counters each step:
    ``moe_steps`` holds ``(pod, t0, t1, lengths, pairs, experts)``."""

    def _wrap(self) -> None:
        super()._wrap()
        self.moe_steps: List[tuple] = []
        be = self.eng.backend
        timed = be.step

        def counted(pod, sids):
            pairs, experts = be.moe_pairs.value, be.moe_experts.value
            out = timed(pod, sids)
            self.moe_steps.append(self.steps[-1] + (
                be.moe_pairs.value - pairs, be.moe_experts.value - experts))
            return out

        be.step = counted


def op_scopes(eng) -> Dict[str, str]:
    """The compiled decode step's operations, each with its named scope."""
    import numpy as np

    be = eng.backend
    st = be.stores[0]
    ids = np.zeros((st.n_slots,), np.int32)
    text = be._step.lower(be.pod_params[0], st.caches, ids, ids).compile(
    ).as_text()
    return hlo_scopes.op_scopes(text, SCOPES)


def check_served(config: Dict, seed: int, seqs: List[List[int]],
                 control: bool = False, seen: Dict = None
                 ) -> List["harness.Check"]:
    """The mean reference logit gap of the served tokens (with ``control``,
    of the float8 control's first choices instead) against the
    configuration's limit, and the number of tokens compared; ``seen``
    receives the gaps' spread.

    The mean and not the widest gap: where two experts score within the
    program's rounding of each other, the program and the float32
    reference route a token differently, and that token's gap alone can
    reach the float8 control's widest (PERF.md, the cell's findings).
    """
    import numpy as np
    from reference import deepseek_v2

    chk = config["check"]
    n = sum(len(q) - 1 for q in seqs)
    if not n:
        return [harness.Check("served_tokens_checked", 0, chk["tokens"],
                              higher_fails=False)]
    got = np.concatenate(deepseek_v2.gaps(config["model"], seed, seqs,
                                          control=control))
    if seen is not None:
        seen.update(max=float(got.max()),
                    p99=float(np.quantile(got, 0.99)),
                    over_0_2=float((got > 0.2).mean()))
    return [harness.Check("mean_logit_gap", float(got.mean()),
                          chk["mean_logit_gap"]),
            harness.Check("served_tokens_checked", n, chk["tokens"],
                          higher_fails=False)]


serve.model_config = model_config
serve.weights = types.SimpleNamespace(make_params=make_params)
PER_CALL = ("Client", "warm_up", "check_served")


def run(spec: "harness.Spec") -> "harness.Result":
    found: Dict = {}

    def client(*args):
        # keep the counters' list only: the client holds the engine, whose
        # weights and caches must be freed before the reference runs
        made = Client(*args)
        found["moe_steps"] = made.moe_steps
        return made

    saved = {name: getattr(serve, name) for name in PER_CALL}

    def warm_up(eng, client, pods, slots):
        saved["warm_up"](eng, client, pods, slots)
        if spec.trace:
            found["op_scopes"] = op_scopes(eng)

    def check(*args, **kw):
        return check_served(*args, **kw, seen=found.setdefault("gaps", {}))

    serve.Client, serve.warm_up, serve.check_served = client, warm_up, check
    try:
        res = serve.run(spec)
    finally:
        for name, fn in saved.items():
            setattr(serve, name, fn)
    res.records["moe_steps"] = found["moe_steps"]
    res.lines.append("gaps: " + " ".join(
        f"{k}={v!r}" for k, v in found.get("gaps", {}).items()))
    if "op_scopes" in found:
        res.records["op_scopes"] = found["op_scopes"]
        res.lines.append(f"scopes: ops_mapped={len(found['op_scopes'])}")
    return res
