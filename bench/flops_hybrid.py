"""Operations and bytes of a hybrid Mamba-2 / MoE / GQA decode step
(``nemotron_h``), computed from shapes.

The yardstick of the hybrid serving cell's roofline and ``mfu`` shares:
what the algorithm has to do, whatever implements it.  ``m`` is the
configuration's ``model`` block (``layer_pattern``, nested ``ssm`` and
``moe``); its pattern counts the blocks of each kind.

* A decode token: one multiply-add (2 FLOPs) per weight it multiplies:
  each Mamba block's ``W_in`` and ``W_out`` and its conv taps, each
  attention block's projections, each MoE block's router and shared
  expert, the LM head; per routed pair ``4 d d_expert`` (relu²: two
  matmuls); per attended position ``4 Hq head_dim`` an attention block;
  per Mamba block the recurrence, ``5 H P N`` (the state's decay and
  update, 3 an element, and its read-out against ``C``, 2).
* A decode step over a batch: every weight read once, a routed expert's
  only if it served a pair; each slot's SSM state (float32) and conv
  state (bfloat16) read and written, every Mamba block; the attended K/V
  read and the new K/V written; the embedding rows read and the logits
  written.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple

SSM_ITEMSIZE = 4             # the recurrent state is held in float32


def _count(m: Dict, kind: str) -> int:
    return m["layer_pattern"].count(kind)


def _ssm(m: Dict) -> Tuple[int, int, int, int, int]:
    """``(heads, head_dim, state, groups, conv taps)``."""
    s = m["ssm"]
    return s["heads"], s["head_dim"], s["d_state"], s["n_groups"], s["d_conv"]


def _conv_dim(m: Dict) -> int:
    h, p, n, g, _ = _ssm(m)
    return h * p + 2 * g * n


def mamba_params(m: Dict) -> int:
    """Matmul weights of one Mamba block (``W_in``, ``W_out``, the conv)."""
    h, p, n, g, k = _ssm(m)
    d, di = m["d_model"], h * p
    return d * (2 * di + 2 * g * n + h) + di * d + k * _conv_dim(m)


def mamba_gain_params(m: Dict) -> int:
    """A Mamba block's vectors: conv bias, ``A_log``, ``D``, ``dt_bias``,
    the gated norm's gain and the block's RMSNorm gain."""
    h, p, _, _, _ = _ssm(m)
    return _conv_dim(m) + 3 * h + h * p + m["d_model"]


def attn_params(m: Dict) -> int:
    d, hq, hkv, hd = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * (hq + 2 * hkv) * hd + hq * hd * d


def _expert_params(m: Dict) -> int:
    return 2 * m["d_model"] * m["moe"]["d_expert"]


def dense_params(m: Dict) -> int:
    """Weights every decode token multiplies (routed experts aside)."""
    mo, d = m["moe"], m["d_model"]
    per_moe = d * mo["n_experts"] + 2 * d * mo["d_shared"] * mo["n_shared"]
    return (_count(m, "M") * mamba_params(m) + _count(m, "*") * attn_params(m)
            + _count(m, "E") * per_moe + d * m["vocab_size"])


def state_bytes_per_slot(m: Dict, itemsize: int = 2) -> int:
    """A slot's recurrent state over every Mamba block: the SSM state and
    the conv state's last ``d_conv - 1`` inputs."""
    h, p, n, _, k = _ssm(m)
    return _count(m, "M") * (h * p * n * SSM_ITEMSIZE
                             + (k - 1) * _conv_dim(m) * itemsize)


def kv_bytes_per_position(m: Dict, itemsize: int = 2) -> int:
    return _count(m, "*") * 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def recurrence_flops(m: Dict) -> float:
    """A token's SSM recurrence over every Mamba block."""
    h, p, n, _, _ = _ssm(m)
    return float(_count(m, "M") * 5 * h * p * n)


def attention_flops(m: Dict, positions: int) -> float:
    return float(_count(m, "*") * 4 * m["n_heads"] * m["head_dim"] * positions)


def decode_flops(m: Dict, lengths: Iterable[int], pairs: int) -> float:
    """Model FLOPs of one step: its tokens at their attended ``lengths``,
    and the ``pairs`` routed pairs its experts served."""
    lengths = list(lengths)
    return (2.0 * dense_params(m) * len(lengths)
            + recurrence_flops(m) * len(lengths)
            + attention_flops(m, sum(lengths))
            + 2.0 * _expert_params(m) * pairs)


def mamba_work(m: Dict, batch: int, itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of the step's Mamba blocks (the ``repro.mamba``
    scope): their weights read once, each slot's state read and written,
    the projections and the recurrence."""
    layers = _count(m, "M")
    flops = (2.0 * layers * mamba_params(m) + recurrence_flops(m)) * batch
    nbytes = (layers * (mamba_params(m) + mamba_gain_params(m)) * itemsize
              + 2 * batch * state_bytes_per_slot(m, itemsize))
    return flops, float(nbytes)


def expert_work(m: Dict, pairs: int, experts: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of the routed experts (the ``repro.moe.experts``
    scope): ``experts`` experts that served a pair, read once (summed over
    the MoE blocks), and ``pairs`` rows in and out."""
    flops = 2.0 * _expert_params(m) * pairs
    nbytes = (experts * _expert_params(m) * itemsize
              + 2 * pairs * m["d_model"] * itemsize)
    return flops, float(nbytes)


def weight_bytes(m: Dict, experts: int, itemsize: int = 2) -> int:
    """Weights a step reads: all but the routed experts, the routed
    experts that served a pair, the gains and vectors."""
    d, e = m["d_model"], m["moe"]["n_experts"]
    vectors = (_count(m, "M") * mamba_gain_params(m)
               + _count(m, "E") * (d + e) + _count(m, "*") * d + d)
    return (dense_params(m) + experts * _expert_params(m) + vectors) * itemsize


def decode_step_work(m: Dict, lengths: Iterable[int], pairs: int,
                     experts: int, itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of one batched decode step.

    ``lengths`` are the attended lengths of the sessions decoded, the new
    token included; ``pairs`` and ``experts`` the step's ``moe_pairs`` and
    ``moe_experts`` counts.
    """
    lengths = list(lengths)
    b = len(lengths)
    kv = kv_bytes_per_position(m, itemsize)
    nbytes = (weight_bytes(m, experts, itemsize)
              + 2 * b * state_bytes_per_slot(m, itemsize)   # state r/w
              + b * m["d_model"] * itemsize                # embedding rows
              + sum(lengths) * kv                           # attended K/V
              + b * kv                                      # new K/V
              + b * m["vocab_size"] * itemsize)             # logits
    return decode_flops(m, lengths, pairs), float(nbytes)
