"""Operations and bytes of a DeepSeek-V2 decode step, computed from shapes.

The yardstick of the MLA / MoE serving cell's roofline and ``mfu`` shares:
what the algorithm has to do, whatever implements it.  ``m`` is the
configuration's ``model`` block (nested ``mla`` and ``moe``).

* A decode token: one multiply-add (2 FLOPs) per weight it multiplies:
  MLA's projections (``wq_a``, ``wq_b``, ``wkv_a``, ``wkv_b`` absorbed into
  the query and the output, ``wo``) in every layer, the dense first
  layer's MLP, each MoE layer's router and shared experts, the LM head;
  per routed pair served by a held expert ``6 d d_expert``; per attended
  latent position, absorbed MLA's ``2 h (2 kv_lora + rope)`` (scores
  against the latent and the rotary key, the weighted sum of latents).
* A decode step over a batch: every weight read once, a routed expert's
  only if it served a pair; the attended latent positions read
  (``kv_lora + rope`` values a position a layer); the new latents written;
  the embedding rows read and the logits written.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def _mla_params(m: Dict) -> int:
    a, d, h = m["mla"], m["d_model"], m["n_heads"]
    qk = a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
    return (d * a["q_lora_rank"] + a["q_lora_rank"] * h * qk
            + d * (a["kv_lora_rank"] + a["qk_rope_head_dim"])
            + a["kv_lora_rank"] * h * (a["qk_nope_head_dim"] + a["v_head_dim"])
            + h * a["v_head_dim"] * d)


def _moe_layers(m: Dict) -> int:
    return m["n_layers"] - m["moe"]["first_dense_layers"]


def _expert_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["moe"]["d_expert"]


def dense_params(m: Dict) -> int:
    """Weights every decode token multiplies (routed experts aside)."""
    mo, d = m["moe"], m["d_model"]
    dense = 3 * d * mo["d_first_dense"] * mo["first_dense_layers"]
    per_moe = d * mo["n_experts"] + 3 * d * mo["d_shared"] * mo["n_shared"]
    return (m["n_layers"] * _mla_params(m) + dense
            + _moe_layers(m) * per_moe + d * m["vocab_size"])


def latent_bytes_per_position(m: Dict, itemsize: int = 2) -> int:
    """Cache bytes a position holds over every layer."""
    a = m["mla"]
    return m["n_layers"] * (a["kv_lora_rank"] + a["qk_rope_head_dim"]) * itemsize


def attention_flops(m: Dict, positions: int) -> float:
    """Absorbed MLA over ``positions`` attended positions, every layer."""
    a = m["mla"]
    per = 2 * m["n_heads"] * (2 * a["kv_lora_rank"] + a["qk_rope_head_dim"])
    return float(m["n_layers"] * per * positions)


def decode_flops(m: Dict, lengths: Iterable[int], pairs: int) -> float:
    """Model FLOPs of one step: its tokens at their attended ``lengths``,
    and the ``pairs`` routed pairs its held experts served."""
    lengths = list(lengths)
    return (2.0 * dense_params(m) * len(lengths)
            + attention_flops(m, sum(lengths))
            + 2.0 * _expert_params(m) * pairs)


def mla_work(m: Dict, lengths: Iterable[int],
             itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of the step's attention (the ``repro.mla`` scope):
    MLA's projections and the attended latents, every layer."""
    lengths = list(lengths)
    b = len(lengths)
    flops = (2.0 * m["n_layers"] * _mla_params(m) * b
             + attention_flops(m, sum(lengths)))
    lat = latent_bytes_per_position(m, itemsize)
    nbytes = (m["n_layers"] * _mla_params(m) * itemsize
              + sum(lengths) * lat + b * lat)
    return flops, float(nbytes)


def expert_work(m: Dict, pairs: int, experts: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of the routed experts (the ``repro.moe.experts``
    scope): ``experts`` held experts that served a pair, read once (summed
    over the MoE layers), and ``pairs`` rows in and out."""
    flops = 2.0 * _expert_params(m) * pairs
    nbytes = (experts * _expert_params(m) * itemsize
              + 2 * pairs * m["d_model"] * itemsize)
    return flops, float(nbytes)


def weight_bytes(m: Dict, experts: int, itemsize: int = 2) -> int:
    """Weights a step reads: all but the routed experts, the routed
    experts that served a pair, the RMSNorm gains."""
    a, d = m["mla"], m["d_model"]
    gains = (m["n_layers"] * (2 * d + a["q_lora_rank"] + a["kv_lora_rank"])
             + d)
    return ((dense_params(m) + experts * _expert_params(m) + gains)
            * itemsize)


def decode_step_work(m: Dict, lengths: Iterable[int], pairs: int,
                     experts: int, itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of one batched decode step.

    ``lengths`` are the attended lengths of the sessions decoded, the new
    token included; ``pairs`` and ``experts`` the step's ``moe_pairs`` and
    ``moe_experts`` counts.
    """
    lengths = list(lengths)
    b = len(lengths)
    lat = latent_bytes_per_position(m, itemsize)
    nbytes = (weight_bytes(m, experts, itemsize)
              + b * m["d_model"] * itemsize           # embedding rows
              + sum(lengths) * lat                     # attended latents
              + b * lat                                # new latents
              + b * m["vocab_size"] * itemsize)        # logits
    return decode_flops(m, lengths, pairs), float(nbytes)
