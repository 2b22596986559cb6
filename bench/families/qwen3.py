"""How the program holds and costs the Qwen3 dense GQA decoder
(reference: ``bench/reference/qwen3.py``)."""
from __future__ import annotations

import numpy as np

from bench.arith import ITEM

LAYOUT = {
    "top": {"embed": ("embed", "table"),
            "final_norm": ("final_norm", "scale")},
    "layer": {"ln1": ("ln1", "scale"), "q": ("attn", "wq"),
              "k": ("attn", "wk"), "v": ("attn", "wv"),
              "o": ("attn", "wo"), "q_norm": ("attn", "q_norm"),
              "k_norm": ("attn", "k_norm"), "ln2": ("ln2", "scale"),
              "gate": ("ffn", "w_gate"), "up": ("ffn", "w_up"),
              "down": ("ffn", "w_down")},
}

# The program's RMSNorms multiply by (1 + scale); the canonical weights
# hold the multiplier itself, as published.
OFFSET_NORMS = {"final_norm", "ln1", "ln2", "q_norm", "k_norm"}

SIZES = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
         "num_attention_heads": "n_heads",
         "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim_",
         "intermediate_size": "d_ff", "vocab_size": "vocab_size",
         "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}


class Qwen3:
    """Dense GQA decoder; ``c`` is the configuration file."""

    def __init__(self, c: dict, kv_dtype: str = "bfloat16"):
        self.E, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.H, self.G = c["num_attention_heads"], c["num_key_value_heads"]
        self.D, self.F = c["head_dim"], c["intermediate_size"]
        self.V = c["vocab_size"]
        E, H, G, D, F = self.E, self.H, self.G, self.D, self.F
        self.layer_matmul = E * H * D + 2 * E * G * D + H * D * E + 3 * E * F
        self.kv_token_bytes = self.L * 2 * G * D * ITEM[kv_dtype]

    def _attn_flops(self, ctx):
        return 4 * self.L * self.H * self.D * ctx     # scores + weighted sum

    def decode_token(self, ctx: int):
        """One token attending ``ctx`` positions (itself included)."""
        fl = 2 * self.L * self.layer_matmul + 2 * self.E * self.V \
            + self._attn_flops(ctx)
        return fl, self.kv_token_bytes * ctx

    def prefill(self, n: int, chunk: int):
        """A prompt of n tokens in chunks of ``chunk``: causal attention,
        logits for the last token only, the prefix read once per chunk."""
        fl = 2 * self.L * self.layer_matmul * n + 2 * self.E * self.V \
            + self._attn_flops(n * (n + 1) // 2)
        starts = np.arange(0, n, chunk)
        by = self.kv_token_bytes * (n + int(starts.sum()))
        return fl, by


def costs(cfgfile: dict) -> Qwen3:
    return Qwen3(cfgfile, cfgfile["serving"]["kv_dtype"])
