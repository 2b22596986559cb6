"""Operations and bytes that the served work needs, and the chip's peaks.

The counts are what the algorithm needs at the configuration's stated
dtypes, not what the program happens to move: each decode token reads the
context it attends once (pages for attention, the recurrent state for
Mamba-2) and writes its own state; each prefill chunk reads the context
before it once; each tick that ran a step reads every weight once.
Matrix products count 2 operations per multiply-add.  ``whole_step_share``
turns the window's work into the share of the chip's roofline: the least
time the chip could have taken (the larger of operations over peak
FLOP/s and bytes over peak bandwidth) over the window's seconds.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(k for k in table if not k.startswith('_'))}")
    return table[device_kind]


def weight_bytes(spec: dict) -> int:
    """Bytes of every served weight (a tied head counts once)."""
    return int(sum(np.prod(shape) * _ITEM[dtype]
                   for shape, dtype, _, _ in spec.values()))


class Qwen3:
    """Dense GQA decoder; ``c`` is the configuration file."""

    def __init__(self, c: dict, kv_dtype: str = "bfloat16"):
        self.E, self.L = c["hidden_size"], c["num_hidden_layers"]
        self.H, self.G = c["num_attention_heads"], c["num_key_value_heads"]
        self.D, self.F = c["head_dim"], c["intermediate_size"]
        self.V = c["vocab_size"]
        E, H, G, D, F = self.E, self.H, self.G, self.D, self.F
        self.layer_matmul = E * H * D + 2 * E * G * D + H * D * E + 3 * E * F
        self.kv_token_bytes = self.L * 2 * G * D * _ITEM[kv_dtype]

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
                                     * _ITEM[state_dtype]
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


def model_costs(cfgfile: dict):
    """The cost model of a configuration file's family."""
    srv = cfgfile["serving"]
    if cfgfile["reference"] == "mamba2":
        return Mamba2(cfgfile, srv.get("ssm_state_dtype") or "float32")
    return Qwen3(cfgfile, srv["kv_dtype"])


def window_work(obs, costs, w_bytes: int):
    """-> (operations, bytes) of the work the window completed: decode
    tokens served in it with the context each attended, prompts whose
    first token came in it, and one read of the weights per tick in it
    that held a request."""
    fl = by = 0
    for rec in obs.records:
        L = len(rec.job.prompt)
        for k, t in enumerate(rec.token_t):
            if not obs.in_window(t):
                continue
            if k == 0:
                f, b = costs.prefill(L, obs.chunk)
            else:
                f, b = costs.decode_token(L + k)
            fl += f
            by += b
    by += w_bytes * sum(1 for _, _, busy in obs.window_ticks() if busy)
    return fl, by


def whole_step_share(obs, costs, w_bytes: int, peak: dict) -> float:
    """Percent of the chip's roofline the window's work would fill."""
    fl, by = window_work(obs, costs, w_bytes)
    t_min = max(fl / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"])
    return 100.0 * t_min / obs.window_s
