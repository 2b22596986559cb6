"""How the program holds and costs Nemotron-H, interleaved Mamba-2, expert
and attention layers (reference: ``bench/reference/nemotron_h.py``)."""
from __future__ import annotations

import numpy as np

from bench.arith import ITEM

LAYOUT = {
    "top": {"embed": ("embed", "table"),
            "final_norm": ("final_norm", "scale"),
            "lm_head": ("lm_head", "w")},
    "layer": {"ln1": ("ln1", "scale"), "ln2": ("ln2", "scale"),
              "in_z": ("ssm", "in_z"), "in_x": ("ssm", "in_x"),
              "in_B": ("ssm", "in_B"), "in_C": ("ssm", "in_C"),
              "in_dt": ("ssm", "in_dt"), "conv_x": ("ssm", "conv_x"),
              "conv_x_bias": ("ssm", "conv_x_bias"),
              "conv_B": ("ssm", "conv_B"),
              "conv_B_bias": ("ssm", "conv_B_bias"),
              "conv_C": ("ssm", "conv_C"),
              "conv_C_bias": ("ssm", "conv_C_bias"),
              "dt_bias": ("ssm", "dt_bias"), "A_log": ("ssm", "A_log"),
              "D": ("ssm", "D"), "gnorm": ("ssm", "norm_scale"),
              "out": ("ssm", "out"),
              "q": ("attn", "wq"), "k": ("attn", "wk"), "v": ("attn", "wv"),
              "o": ("attn", "wo"),
              "router": ("ffn", "router", "w"),
              "router_bias": ("ffn", "router", "bias"),
              "up": ("ffn", "experts", "w_up"),
              "down": ("ffn", "experts", "w_down"),
              "shared_up": ("ffn", "shared", "w_up"),
              "shared_down": ("ffn", "shared", "w_down")},
}

# The program's RMSNorms multiply by (1 + scale); the canonical weights
# hold the multiplier itself, as published.
OFFSET_NORMS = {"final_norm", "ln1", "ln2", "gnorm"}

# ``n_routed_experts`` is the experts this chip holds, from
# ``first_held_expert``; the router spans ``n_routed_experts_published``.
SIZES = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "hybrid_override_pattern": lambda cfg: cfg.layer_pattern,
         "mamba_num_heads": "ssm_heads_", "mamba_head_dim": "ssm_head_dim",
         "n_groups": "ssm_groups", "ssm_state_size": "ssm_state",
         "conv_kernel": "ssm_conv", "use_conv_bias": "ssm_conv_bias",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim_",
         "n_routed_experts": "experts_held_",
         "n_routed_experts_published": "n_experts",
         "first_held_expert": "expert_first",
         "num_experts_per_tok": "top_k",
         "moe_intermediate_size": "moe_d_ff",
         "moe_shared_expert_intermediate_size": "shared_d_ff_",
         "routed_scaling_factor": "routed_scale",
         "mlp_hidden_act": lambda cfg: cfg.act,
         "tie_word_embeddings": "tie_embeddings",
         "vocab_size": "vocab_size", "layer_norm_epsilon": "norm_eps"}


class NemotronH:
    """Interleaved M, E and * layers; ``c`` is the configuration file."""

    def __init__(self, c: dict, kv_dtype: str = "bfloat16",
                 state_dtype: str = "float32"):
        pat = c["hybrid_override_pattern"]
        E = self.E = c["hidden_size"]
        nM, nE, nA = pat.count("M"), pat.count("E"), pat.count("*")
        H, P = c["mamba_num_heads"], c["mamba_head_dim"]
        Di, GN = H * P, c["n_groups"] * c["ssm_state_size"]
        N, K = c["ssm_state_size"], c["conv_kernel"]
        Hq, G, D = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
        self.V, self.Hq, self.D, self.nA = c["vocab_size"], Hq, D, nA
        conv_ch = Di + 2 * GN
        # multiply-adds per token outside attention's context: the mixers'
        # projections and SSD update, the router, the shared expert, the
        # routed picks that land on held experts (top-k x held / router),
        # the attention projections
        held = c["num_experts_per_tok"] * c["n_routed_experts"] \
            / c["n_routed_experts_published"]
        mixer = E * (2 * Di + 2 * GN + H) + Di * E
        ssd = K * conv_ch + 2.5 * H * P * N
        expert = 2 * E * c["moe_intermediate_size"]
        shared = 2 * E * c["moe_shared_expert_intermediate_size"]
        router = E * c["n_routed_experts_published"]
        attn = E * (Hq * D + 2 * G * D) + Hq * D * E
        self.token_macs = nM * (mixer + ssd) + nE * (
            router + shared + held * expert) + nA * attn
        self.state_bytes = nM * (H * P * N * ITEM[state_dtype]
                                 + (K - 1) * conv_ch * 2)
        self.kv_token_bytes = nA * 2 * G * D * ITEM[kv_dtype]

    def _attn_flops(self, ctx):
        return 4 * self.nA * self.Hq * self.D * ctx   # scores + weighted sum

    def decode_token(self, ctx: int):
        """One token: each mixer's state read and written once, and the
        attention layers' KV of ``ctx`` positions (itself included)."""
        fl = 2 * self.token_macs + 2 * self.E * self.V + \
            self._attn_flops(ctx)
        return fl, 2 * self.state_bytes + self.kv_token_bytes * ctx

    def prefill(self, n: int, chunk: int):
        """A prompt of n tokens in chunks of ``chunk``: causal attention,
        logits for the last token only; the state read and written and the
        KV prefix read once per chunk."""
        fl = 2 * self.token_macs * n + 2 * self.E * self.V + \
            self._attn_flops(n * (n + 1) // 2)
        starts = np.arange(0, n, chunk)
        by = 2 * self.state_bytes * len(starts) + \
            self.kv_token_bytes * (n + int(starts.sum()))
        return fl, by


def costs(cfgfile: dict) -> NemotronH:
    srv = cfgfile["serving"]
    return NemotronH(cfgfile, srv["kv_dtype"],
                     srv.get("ssm_state_dtype") or "float32")
