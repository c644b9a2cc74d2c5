"""Operations and bytes that the work needs, computed from shapes.

The yardstick for every roofline and ``mfu`` share: what the algorithm
has to do, whatever a kernel does to get there.

* A decode token of a dense GQA decoder: one multiply-add per matmul
  weight (2 FLOPs), and attention over the ``length`` positions it
  attends (2 FLOPs per score and per weighted value, per head).
* A decode step over a batch: every weight read once, the attended part
  of each session's cache read, the new key/value written, the logits
  written.
* A batched certification call: per read entry the item id, its snapshot
  version and the store version it is compared with; per write entry the
  item id and its lock; one verdict per row.  One compare per entry.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Tuple

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add them with their source")
    return table[device_kind]


def matmul_params(m: Dict) -> int:
    """Weights that one decode token multiplies: layers and the LM head."""
    d, hq, hkv, hd, ff = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                          m["head_dim"], m["d_ff"])
    per_layer = d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff
    return m["n_layers"] * per_layer + d * m["vocab_size"]


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    """Matmul weights plus the RMSNorm gains, as stored."""
    gains = (2 * m["n_layers"] + 1) * m["d_model"]
    return (matmul_params(m) + gains) * itemsize


def kv_bytes_per_token(m: Dict, itemsize: int = 2) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * itemsize


def decode_token_flops(m: Dict, length: int) -> float:
    """FLOPs of one decode token that attends ``length`` positions."""
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * length
    return 2.0 * matmul_params(m) + attn


def decode_step_work(m: Dict, lengths: Iterable[int],
                     itemsize: int = 2) -> Tuple[float, float]:
    """``(flops, bytes)`` of one batched decode step.

    ``lengths`` are the attended lengths of the sessions decoded, the new
    token included.
    """
    lengths = list(lengths)
    b = len(lengths)
    flops = sum(decode_token_flops(m, n) for n in lengths)
    kv = kv_bytes_per_token(m, itemsize)
    nbytes = (weight_bytes(m, itemsize)
              + b * m["d_model"] * itemsize          # embedding rows
              + sum(lengths) * kv                     # attended cache read
              + b * kv                                # new key/value write
              + b * m["vocab_size"] * itemsize)       # logits
    return flops, float(nbytes)


def validate_work(live_reads: int, live_writes: int, rows: int,
                  read_width: int, write_width: int) -> Tuple[float, float]:
    """``(ops, bytes)`` of one certification call of ``rows`` padded rows.

    The packed ``[rows, read_width]`` ids and versions and ``[rows,
    write_width]`` write ids are read; each live entry gathers one int32
    (store version or lock); one verdict byte per row is written.
    """
    ops = float(live_reads + live_writes)
    nbytes = (8 * rows * read_width + 4 * live_reads
              + 4 * rows * write_width + 4 * live_writes + rows)
    return ops, float(nbytes)


def least_time(flops: float, nbytes: float, pk: Dict[str, float],
               flops_key: str = "bf16_flops") -> Tuple[float, str]:
    """The chip's least time for the work, and which bound sets it."""
    t_f = flops / pk[flops_key]
    t_b = nbytes / pk["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
