"""How the program holds and costs the Mamba-2 SSD stack
(reference: ``bench/reference/mamba2.py``)."""
from __future__ import annotations

from bench.arith import ITEM

LAYOUT = {
    "top": {"embed": ("embed", "table"),
            "final_norm": ("final_norm", "scale")},
    "layer": {"norm": ("ln1", "scale"), "in_z": ("ssm", "in_z"),
              "in_x": ("ssm", "in_x"), "in_B": ("ssm", "in_B"),
              "in_C": ("ssm", "in_C"), "in_dt": ("ssm", "in_dt"),
              "conv_x": ("ssm", "conv_x"), "conv_B": ("ssm", "conv_B"),
              "conv_C": ("ssm", "conv_C"), "dt_bias": ("ssm", "dt_bias"),
              "A_log": ("ssm", "A_log"), "D": ("ssm", "D"),
              "gnorm": ("ssm", "norm_scale"), "out": ("ssm", "out")},
}

# The program's RMSNorms multiply by (1 + scale); the canonical weights
# hold the multiplier itself, as published.
OFFSET_NORMS = {"final_norm", "norm", "gnorm"}

# ``vocab_size`` is the embedding's rows: the file's vocabulary padded to
# its ``pad_vocab_size_multiple``.
SIZES = {"n_layer": "n_layers", "d_model": "d_model",
         "d_state": "ssm_state", "expand": "ssm_expand",
         "headdim": "ssm_head_dim", "d_conv": "ssm_conv",
         "vocab_size": "vocab_size", "norm_epsilon": "norm_eps"}


class Mamba2:
    """Attention-free SSD stack; ``c`` is the configuration file."""

    def __init__(self, c: dict, state_dtype: str = "float32"):
        E = c["d_model"]
        self.E, self.L = E, c["n_layer"]
        Di = c["expand"] * E
        self.N, self.K = c["d_state"], c["d_conv"]
        self.H, self.P = Di // c["headdim"], c["headdim"]
        m = c["pad_vocab_size_multiple"]
        self.V = -(-c["vocab_size"] // m) * m
        self.layer_matmul = E * (2 * Di + 2 * self.N + self.H) + Di * E
        conv_ch = Di + 2 * self.N
        # per token and layer: the convolution, the state decay, the outer
        # product into the state, and the read-out (multiply-adds x 2)
        self.layer_ssm = 2 * self.K * conv_ch + 5 * self.H * self.P * self.N
        self.state_bytes = self.L * (self.H * self.P * self.N
                                     * ITEM[state_dtype]
                                     + (self.K - 1) * conv_ch * 2)

    def _token_flops(self):
        return self.L * (2 * self.layer_matmul + self.layer_ssm)

    def decode_token(self, ctx: int):
        """One token: the state is read and written once."""
        return (self._token_flops() + 2 * self.E * self.V,
                2 * self.state_bytes)

    def prefill(self, n: int, chunk: int):
        """A prompt of n tokens: the state read and written once a chunk."""
        n_chunks = -(-n // chunk)
        return (n * self._token_flops() + 2 * self.E * self.V,
                2 * self.state_bytes * n_chunks)


def costs(cfgfile: dict) -> Mamba2:
    return Mamba2(cfgfile, cfgfile["serving"].get("ssm_state_dtype")
                  or "float32")
