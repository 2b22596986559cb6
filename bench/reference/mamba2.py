"""Plain float32 forward of Mamba-2 (arXiv:2405.21060, state-spaces/mamba2-370m).

Written from the published description, not from the program: each layer
is ``x + Mamba2(RMSNorm(x))``; the mixer projects the normed input to z,
x, B, C (one group) and dt, runs a causal depthwise convolution of width
``d_conv`` over x, B and C followed by SiLU, takes dt = softplus(dt +
dt_bias) and A = -exp(A_log), and runs the selective state-space
recurrence token by token:

    h_t = exp(dt_t * A) h_{t-1} + dt_t * (x_t outer B_t)
    y_t = h_t . C_t + D * x_t

then the gated RMSNorm ``RMSNorm(y * silu(z))`` over all of d_inner (one
group, norm before the gate off) and the output projection.  A final
RMSNorm and an LM head tied to the (vocabulary-padded) embedding close the
stack.  The recurrence is the sequential definition, not the chunked SSD
algorithm.  Matrix products run in float32 at ``HIGHEST``; ``cast`` rounds
every matmul operand for the lower-precision control.

The configuration file states the block as it is served, and two of its
settings depart from the published block (``PERF.md`` names them):
``ssm_cfg.conv_bias`` false (published true), which this reference
requires, and ``residual_in_fp32`` false (published true), under which
the residual stream is rounded to bfloat16 after every add, as a bfloat16
model without that option does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def dims(c: dict) -> dict:
    E = c["d_model"]
    Di = c["expand"] * E
    m = c["pad_vocab_size_multiple"]
    return {"E": E, "L": c["n_layer"], "Di": Di, "P": c["headdim"],
            "H": Di // c["headdim"], "N": c["d_state"], "K": c["d_conv"],
            "V": -(-c["vocab_size"] // m) * m}


def weight_spec(c: dict) -> dict:
    """name -> (shape, dtype, init, scale); see ``bench/weights.py``."""
    d = dims(c)
    E, L, Di, H, N, K, V = (d[k] for k in ("E", "L", "Di", "H", "N", "K",
                                           "V"))
    bf, f32, res = "bfloat16", "float32", 1.0 / math.sqrt(2 * L)
    if c.get("ssm_cfg", {}).get("conv_bias", True):
        raise ValueError("the mamba2 reference has no convolution bias; "
                         "the configuration has to state conv_bias false")
    return {
        "embed": ((V, E), bf, "normal", 0.06),
        "final_norm": ((E,), bf, "norm", 0.1),
        "norm": ((L, E), bf, "norm", 0.1),
        "in_z": ((L, E, Di), bf, "normal", E ** -0.5),
        "in_x": ((L, E, Di), bf, "normal", E ** -0.5),
        "in_B": ((L, E, N), bf, "normal", E ** -0.5),
        "in_C": ((L, E, N), bf, "normal", E ** -0.5),
        "in_dt": ((L, E, H), bf, "normal", 0.5 * E ** -0.5),
        "conv_x": ((L, Di, K), bf, "normal", 0.5),
        "conv_B": ((L, N, K), bf, "normal", 0.5),
        "conv_C": ((L, N, K), bf, "normal", 0.5),
        "dt_bias": ((L, H), f32, "dt_bias", 0.0),
        "A_log": ((L, H), f32, "a_log", 0.0),
        "D": ((L, H), bf, "norm", 0.1),
        "gnorm": ((L, Di), bf, "norm", 0.1),
        "out": ((L, Di, E), bf, "normal", Di ** -0.5 * res),
    }


LAYER_KEYS = ("norm", "in_z", "in_x", "in_B", "in_C", "in_dt", "conv_x",
              "conv_B", "conv_C", "dt_bias", "A_log", "D", "gnorm", "out")


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _conv(u, w):
    """Causal depthwise conv: u (B, S, C), w (C, K); tap K-1 is the
    current token."""
    K = w.shape[1]
    S = u.shape[1]
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(up[:, i:i + S] * w[:, i] for i in range(K))


def hidden(w: dict, tokens, c: dict, cast=lambda a: a):
    """tokens: (S,) int32 -> final-normed hidden states (S, E) float32.
    Causal, so padding after the last real token changes nothing before
    it.  ``cast`` rounds every matmul operand (identity: the reference)."""
    d = dims(c)
    H, P = d["H"], d["P"]
    eps = c["norm_epsilon"]
    Bsz, S = 1, tokens.shape[0]
    x = w["embed"].astype(jnp.float32)[tokens[None]]

    def residual(a):
        if c.get("residual_in_fp32", True):
            return a
        # bfloat16's rounding, kept in float32: XLA folds away a round trip
        # through astype
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def mm(spec, a, b):
        return jnp.einsum(spec, cast(a.astype(jnp.float32)),
                          cast(b.astype(jnp.float32)), precision=HI)

    def layer(x, lw):
        h = _rms(x, lw["norm"], eps)
        z = mm("bse,ei->bsi", h, lw["in_z"])
        xs = jax.nn.silu(_conv(mm("bse,ei->bsi", h, lw["in_x"]),
                               lw["conv_x"]))
        Bm = jax.nn.silu(_conv(mm("bse,en->bsn", h, lw["in_B"]),
                               lw["conv_B"]))
        Cm = jax.nn.silu(_conv(mm("bse,en->bsn", h, lw["in_C"]),
                               lw["conv_C"]))
        dt = jax.nn.softplus(mm("bse,eh->bsh", h, lw["in_dt"])
                             + lw["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(lw["A_log"].astype(jnp.float32))
        Dv = lw["D"].astype(jnp.float32)
        xh = xs.reshape(Bsz, S, H, P)

        def step(state, t):                  # state: (B, H, P, N)
            x_t, dt_t, B_t, C_t = t
            state = state * jnp.exp(dt_t * A)[..., None, None] + \
                (dt_t[..., None, None] * x_t[..., None] * B_t[:, None, None])
            y = jnp.einsum("bhpn,bn->bhp", state, C_t, precision=HI)
            return state, y + Dv[None, :, None] * x_t

        state0 = jnp.zeros((Bsz, H, P, d["N"]), jnp.float32)
        seq = (xh.swapaxes(0, 1), dt.swapaxes(0, 1), Bm.swapaxes(0, 1),
               Cm.swapaxes(0, 1))
        _, y = jax.lax.scan(step, state0, seq)
        y = y.swapaxes(0, 1).reshape(Bsz, S, H * P)
        g = _rms(y * jax.nn.silu(z), lw["gnorm"], eps)
        return residual(x + mm("bsi,ie->bse", g, lw["out"])), None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in LAYER_KEYS})
    return _rms(x[0], w["final_norm"], eps)


def head(w: dict, c: dict):
    """The LM head, (V_padded, E): tied to the embedding."""
    return w["embed"]
