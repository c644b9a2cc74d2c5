"""NVIDIA-Nemotron-3-Nano-30B-A3B [hf: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
``model_type`` nemotron_h].

52 single-mixer blocks behind pre-norm residuals, in the pattern
``MEMEM*EMEMEM*...``: 23 Mamba-2 (64 heads x 64, state 128, 8 groups,
conv 4), 23 MoE (128 routed experts of 1856, top-6, sigmoid scores with a
correction bias for the choice, renormalised gates x2.5, non-gated relu²,
one shared expert of 3712), 6 GQA without rotary (32 query / 2 KV heads x
128); d_model 2688, vocab 131072, untied head, RMSNorm eps 1e-5.
"""
import dataclasses

from repro.models.common import ModelConfig, MoEConfig, SSMConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b",
    family="hybrid",
    n_layers=len(PATTERN),
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=131072,
    mlp_act="relu2",
    norm_eps=1e-5,
    partial_rotary=0.0,          # nemotron_h's attention has no rotary
    layer_pattern=PATTERN,
    moe=MoEConfig(n_experts=128, top_k=6, d_expert=1856, n_shared=1,
                  d_shared=3712, router_scale=2.5, scoring="sigmoid",
                  norm_topk=True, expert_act="relu2"),
    ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64, n_groups=8,
                  chunk=128, heads=64),
    max_seq_len=262144,
)


def smoke_config() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, n_layers=7, layer_pattern="MEMEM*E", d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=32, vocab_size=256,
        moe=dataclasses.replace(CONFIG.moe, n_experts=8, top_k=2, d_expert=32,
                                d_shared=48),
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, n_groups=2,
                      chunk=32, heads=4),
        max_seq_len=512,
    )
