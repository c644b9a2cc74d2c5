"""FLOPs and bytes from shapes, checked by hand on a small model."""
import pytest

import flops

M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 32}


def test_matmul_params_by_hand():
    # per layer: wq 8x8 + wk, wv 8x4 each + wo 8x8 + 3 x 8x16 = 576;
    # two layers and the 8x32 head
    assert flops.matmul_params(M) == 2 * (64 + 32 + 32 + 64 + 384) + 256


def test_decode_token_flops_by_hand():
    # 2 per weight, and 2 x 2 per attended position per head-dim per layer
    assert flops.decode_token_flops(M, 5) == 2 * 1408 + 4 * 2 * 4 * 2 * 5


def test_decode_step_work_by_hand():
    f, b = flops.decode_step_work(M, [3, 5])
    assert f == flops.decode_token_flops(M, 3) + flops.decode_token_flops(M, 5)
    kv = 2 * 2 * 2 * 2 * 2                  # layers x (k, v) x heads x dim x 2B
    weights = (1408 + 5 * 8) * 2            # matmul weights + 5 RMSNorm gains
    assert b == weights + 2 * 8 * 2 + 8 * kv + 2 * kv + 2 * 32 * 2


def test_validate_work_by_hand():
    ops, b = flops.validate_work(live_reads=5, live_writes=3, rows=8,
                                 read_width=8, write_width=8)
    assert ops == 8
    assert b == 8 * 8 * 8 + 4 * 5 + 4 * 8 * 8 + 4 * 3 + 8


def test_least_time_names_its_bound():
    pk = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 10.0, pk) == (10.0, "flops")
    assert flops.least_time(10.0, 1000.0, pk) == (100.0, "bytes")


def test_unknown_device_is_an_error():
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("cpu")
