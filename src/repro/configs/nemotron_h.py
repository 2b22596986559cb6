"""NVIDIA Nemotron 3 Nano 30B-A3B — interleaved Mamba-2, MoE and GQA layers.

[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json] 52 blocks in
``hybrid_override_pattern``; each block is one pre-norm residual sublayer
``x + f(RMSNorm(x))``: "M" a Mamba-2 mixer (64 heads of 64, so an inner
width of 4,096, 8 B/C groups, state 128, conv 4 with bias, a gated RMSNorm
over groups of 512 channels), "E" an expert layer (128 relu^2 experts of
width 1,856, top-6 by a sigmoid router with a correction bias, weights
renormalised and scaled by 2.5, plus one shared relu^2 expert of width
3,712), "*" GQA attention (32 query and 2 KV heads of 128, no RoPE).
Untied embedding and head over 131,072 rows; RMSNorm epsilon 1e-5.

The sigmoid router with its correction bias, the absence of RoPE and the
inner width taken as heads x head dim come from the model's released
modelling code (``modeling_nemotron_h.py``), not from ``config.json``.

``nemotron3-nano-ep8`` is one chip's share of a v5e-8 host serving it:
the 8 chips share every expert layer by expert parallelism, 16 of the 128
routed experts each (this chip holds experts 0-15); the mixers, the shared
expert, the embedding and the head are whole on every chip.  Its depth is
the first 21 blocks, three whole periods of ``MEMEM*E``; the other blocks
would be further pipeline stages.  Every width is as published.
"""
import dataclasses

from repro.configs.base import ModelConfig, register

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

NANO = register(ModelConfig(
    name="nemotron3-nano-30b-a3b",
    family="hybrid",
    n_layers=52,
    layer_pattern=PATTERN,
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=131_072,
    rope_theta=0.0,           # no RoPE
    act="relu2",
    gated_ffn=False,
    n_experts=128,
    top_k=6,
    n_shared_experts=1,
    moe_d_ff=1856,
    shared_d_ff=3712,
    router="sigmoid",
    routed_scale=2.5,
    ssm_state=128,
    ssm_expand=2,
    ssm_head_dim=64,
    ssm_heads=64,
    ssm_groups=8,
    ssm_conv=4,
    ssm_chunk=128,
    ssm_conv_bias=True,
    norm_eps=1e-5,
    tie_embeddings=False,
    max_seq_len=262_144,
    source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 config.json; "
           "router, no RoPE and inner width from modeling_nemotron_h.py",
))

register(dataclasses.replace(
    NANO, name="nemotron3-nano-ep8", n_layers=21,
    layer_pattern=PATTERN[:21], expert_first=0, experts_held=16,
    source=NANO.source + "; one v5e chip's share of an 8-chip "
           "expert-parallel host, first 21 blocks"))
