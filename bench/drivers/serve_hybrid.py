"""Serving driver for the hybrid Mamba-2 / MoE / GQA stack (``nemotron_h``).

The MLA / MoE driver (``serve_mla_moe.py``: ``serve.py``'s timed path on a
private copy, its expert-counting ``Client``, its traced map of the
compiled step's operations to their scopes) on a private copy of that
driver, with what this model changes put in its place:

* the model: ``ModelConfig`` with its ``layer_pattern``, ``MoEConfig``
  (sigmoid routing, relu² experts) and ``SSMConfig`` from the
  configuration's ``model`` block;
* the weights: ``reference/nemotron_h.leaf``'s seeded checkpoint (Mamba-2's
  ``A`` and ``dt`` ranges, ``D`` ones, the routers' correction bias drawn),
  the whole tree drawn on the device in one jit;
* the check: the served tokens' reference logit gap, against
  ``reference/nemotron_h.py``, by the statistic the configuration's
  ``check`` names (``max_logit_gap`` or ``mean_logit_gap``);
* the scopes mapped in a traced run: ``repro.mamba`` and ``repro.gqa``
  beside the expert layer's.

``records`` are the MLA / MoE driver's: ``moe_steps`` (each step's
``moe_pairs`` and ``moe_experts`` counters) and, traced, ``op_scopes``.
"""
from __future__ import annotations

import importlib.util
import sys
import types
from pathlib import Path
from typing import Dict, List

import harness
import weights

SCOPES = ("repro.mamba", "repro.gqa", "repro.moe.route", "repro.moe.experts",
          "repro.moe.shared")


def _private_driver():
    """``serve_mla_moe.py`` loaded as a module of this driver's own (with
    its own private ``serve.py``), so what is put in its place here never
    reaches the MLA / MoE cell's driver."""
    path = Path(__file__).with_name("serve_mla_moe.py")
    spec = importlib.util.spec_from_file_location(
        "bench_drivers_serve_hybrid_base", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


base = _private_driver()


def model_config(cfg: Dict):
    from repro.models.common import MoEConfig, ModelConfig, SSMConfig

    m = dict(cfg["model"])
    return ModelConfig(**{**m, "moe": MoEConfig(**m["moe"]),
                          "ssm": SSMConfig(**m["ssm"])})


def make_params(shapes, seed: int, dtype, model: Dict = None) -> Dict:
    """The whole tree drawn on the device in one jit, each leaf as
    ``reference/nemotron_h.leaf`` draws it."""
    import jax
    from reference import nemotron_h

    names, leaf_shapes, treedef = weights.leaf_names(shapes)

    @jax.jit
    def build(keys):
        return [nemotron_h.leaf(model, keys[i], name, shape, dtype)
                for i, (name, shape) in enumerate(zip(names, leaf_shapes))]

    return jax.tree_util.tree_unflatten(
        treedef, build(weights.leaf_keys(seed, names)))


def check_served(config: Dict, seed: int, seqs: List[List[int]],
                 control: bool = False, seen: Dict = None
                 ) -> List["harness.Check"]:
    """The served tokens' reference logit gap, widest or mean as the
    configuration's ``check`` names it (with ``control``, of the float8
    control's first choices instead), against its limit, and the number
    of tokens compared; ``seen`` receives the gaps' spread."""
    import numpy as np
    from reference import nemotron_h

    chk = config["check"]
    n = sum(len(q) - 1 for q in seqs)
    if not n:
        return [harness.Check("served_tokens_checked", 0, chk["tokens"],
                              higher_fails=False)]
    got = np.concatenate(nemotron_h.gaps(config["model"], seed, seqs,
                                         control=control))
    if seen is not None:
        seen.update(max=float(got.max()), mean=float(got.mean()),
                    p99=float(np.quantile(got, 0.99)),
                    over_0_2=float((got > 0.2).mean()))
    stat = "max_logit_gap" if "max_logit_gap" in chk else "mean_logit_gap"
    value = float(got.max() if stat == "max_logit_gap" else got.mean())
    return [harness.Check(stat, value, chk[stat]),
            harness.Check("served_tokens_checked", n, chk["tokens"],
                          higher_fails=False)]


base.serve.model_config = model_config
base.check_served = check_served
base.SCOPES = SCOPES


def run(spec: "harness.Spec") -> "harness.Result":
    base.serve.weights = types.SimpleNamespace(
        make_params=lambda shapes, seed, dtype: make_params(
            shapes, seed, dtype, spec.cell.config["model"]))
    return base.run(spec)
