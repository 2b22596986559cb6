"""Plain float32 forward of Nemotron-H (hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16).

Written from the published configuration and the released modelling code
(``NemotronHForCausalLM``), not from the program.  The layers follow
``hybrid_override_pattern``, one character a layer, and each layer is one
pre-norm residual sublayer ``x + f(RMSNorm(x))``:

- ``M``, a Mamba-2 mixer: the normed input projects to z and x (heads x
  head dim wide), B and C (``n_groups`` groups of ``ssm_state_size``) and
  dt (one per head); a causal depthwise convolution of width
  ``conv_kernel`` with bias over x, B and C, then SiLU; dt = softplus(dt +
  dt_bias), A = -exp(A_log); the selective recurrence token by token, each
  head reading its group's B and C (heads / n_groups heads a group):

      h_t = exp(dt_t * A) h_{t-1} + dt_t * (x_t outer B_t)
      y_t = h_t . C_t + D * x_t

  then RMSNorm(y * silu(z)) over each group's channels and the output
  projection.
- ``E``, an expert layer: scores sigmoid(x . w) over the router's
  experts; the top ``num_experts_per_tok`` of score + the correction bias
  are picked, and their scores, renormalised to sum to 1, are scaled by
  ``routed_scaling_factor``.  Each expert is relu^2, not gated: down(relu(
  up(x))^2).  The layer's output is the picked experts' outputs weighted
  by their gates, plus one shared relu^2 expert.
- ``*``, causal grouped-query attention scaled by head_dim^-0.5, with no
  positional embedding.

A final RMSNorm and an untied LM head close the stack.  The recurrence is
the sequential definition, not the chunked algorithm.  Matrix products run
in float32 at ``HIGHEST``; ``cast`` rounds every matmul operand for the
lower-precision control.

The configuration file may hold a share of the experts: ``n_routed_experts``
of them, from ``first_held_expert``, out of a router over
``n_routed_experts_published``.  The reference then gives those experts
the same share the program computes: picks on other experts are another
chip's part of the layer and are left out.  ``residual_in_fp32`` false
rounds the residual stream to bfloat16 after every add, as the model's
bfloat16 residual does.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (bounds the scores)


def dims(c: dict) -> dict:
    pat = c["hybrid_override_pattern"]
    H, P = c["mamba_num_heads"], c["mamba_head_dim"]
    return {"E": c["hidden_size"], "pattern": pat,
            "nM": pat.count("M"), "nE": pat.count("E"), "nA": pat.count("*"),
            "H": H, "P": P, "Di": H * P, "G": c["n_groups"],
            "N": c["ssm_state_size"], "K": c["conv_kernel"],
            "Hq": c["num_attention_heads"], "Hkv": c["num_key_value_heads"],
            "D": c["head_dim"], "F": c["moe_intermediate_size"],
            "Fs": c["moe_shared_expert_intermediate_size"],
            "n": c["n_routed_experts"],
            "R": c["n_routed_experts_published"],
            "first": c["first_held_expert"], "V": c["vocab_size"]}


def weight_spec(c: dict) -> dict:
    """name -> (shape, dtype, init, scale).  A layer tensor has one row per
    layer that holds it, in layer order: ``ln1`` the M and * layers'
    norms, ``ln2`` the E layers'.  ``normal``: N(0, scale^2); ``norm``: 1 +
    N(0, scale^2).  Projections keep unit scale through a layer; residual
    branches are damped by sqrt(2L); the router's scores spread well
    across the experts.  The routed experts' output projection is damped
    by 4 more: with random weights the top-k margins are narrow, so bf16
    rounding flips some picks (as it does in any bf16 model), and each
    flip moves the logits in proportion to the routed output.  Undamped,
    those flips alone read about as large a gap as the float8 control
    does (``PERF.md``, PR 16)."""
    d = dims(c)
    E, H, Di, G, N, K = (d[k] for k in ("E", "H", "Di", "G", "N", "K"))
    Hq, Hkv, D, F, Fs = (d[k] for k in ("Hq", "Hkv", "D", "F", "Fs"))
    nM, nE, nA, n, R, V = (d[k] for k in ("nM", "nE", "nA", "n", "R", "V"))
    L = len(d["pattern"])
    if not c.get("use_conv_bias") or c.get("tie_word_embeddings") or \
            c.get("residual_in_fp32"):
        raise ValueError("the nemotron_h reference takes a convolution bias, "
                         "an untied head and a bfloat16 residual")
    bf, f32, res = "bfloat16", "float32", 1.0 / math.sqrt(2 * L)
    return {
        "embed": ((V, E), bf, "normal", 1.0),
        "final_norm": ((E,), bf, "norm", 0.1),
        "lm_head": ((V, E), bf, "normal", 2.0 * E ** -0.5),
        "ln1": ((nM + nA, E), bf, "norm", 0.1),
        "in_z": ((nM, E, Di), bf, "normal", E ** -0.5),
        "in_x": ((nM, E, Di), bf, "normal", E ** -0.5),
        "in_B": ((nM, E, G * N), bf, "normal", E ** -0.5),
        "in_C": ((nM, E, G * N), bf, "normal", E ** -0.5),
        "in_dt": ((nM, E, H), bf, "normal", 0.5 * E ** -0.5),
        "conv_x": ((nM, Di, K), bf, "normal", 0.5),
        "conv_x_bias": ((nM, Di), bf, "normal", 0.1),
        "conv_B": ((nM, G * N, K), bf, "normal", 0.5),
        "conv_B_bias": ((nM, G * N), bf, "normal", 0.1),
        "conv_C": ((nM, G * N, K), bf, "normal", 0.5),
        "conv_C_bias": ((nM, G * N), bf, "normal", 0.1),
        "dt_bias": ((nM, H), f32, "dt_bias", 0.0),
        "A_log": ((nM, H), f32, "a_log", 0.0),
        "D": ((nM, H), bf, "norm", 0.1),
        "gnorm": ((nM, Di), bf, "norm", 0.1),
        "out": ((nM, Di, E), bf, "normal", Di ** -0.5 * res),
        "q": ((nA, E, Hq, D), bf, "normal", E ** -0.5),
        "k": ((nA, E, Hkv, D), bf, "normal", E ** -0.5),
        "v": ((nA, E, Hkv, D), bf, "normal", E ** -0.5),
        "o": ((nA, Hq, D, E), bf, "normal", (Hq * D) ** -0.5 * res),
        "ln2": ((nE, E), bf, "norm", 0.1),
        "router": ((nE, E, R), f32, "normal", E ** -0.5),
        "router_bias": ((nE, R), f32, "normal", 0.1),
        "up": ((nE, n, E, F), bf, "normal", E ** -0.5),
        "down": ((nE, n, F, E), bf, "normal", 0.25 * F ** -0.5 * res),
        "shared_up": ((nE, E, Fs), bf, "normal", E ** -0.5),
        "shared_down": ((nE, Fs, E), bf, "normal", Fs ** -0.5 * res),
    }


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _conv(u, w, b):
    """Causal depthwise conv with bias: u (S, C), w (C, K), b (C,); tap
    K-1 is the current token."""
    K = w.shape[1]
    S = u.shape[0]
    up = jnp.pad(u, ((K - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(up[i:i + S] * w[:, i] for i in range(K)) + \
        b.astype(jnp.float32)


def hidden(w: dict, tokens, c: dict, cast=lambda a: a):
    """tokens: (S,) int32 -> final-normed hidden states (S, E) float32.
    Causal, so padding after the last real token changes nothing before
    it.  ``cast`` rounds every matmul operand (identity: the reference)."""
    d = dims(c)
    H, P, G, N = d["H"], d["P"], d["G"], d["N"]
    Hq, Hkv, D = d["Hq"], d["Hkv"], d["D"]
    eps = c["layer_norm_epsilon"]
    S = tokens.shape[0]
    nb = -(-S // Q_BLOCK)
    pos = jnp.arange(S)
    x = w["embed"].astype(jnp.float32)[tokens]

    def mm(spec, a, b):
        return jnp.einsum(spec, cast(a.astype(jnp.float32)),
                          cast(b.astype(jnp.float32)), precision=HI)

    def residual(a):
        # bfloat16's rounding, kept in float32: XLA folds away a round trip
        # through astype
        return jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)

    def mamba(h, i):
        lw = {k: w[k][i] for k in ("in_z", "in_x", "in_B", "in_C", "in_dt",
                                   "conv_x", "conv_x_bias", "conv_B",
                                   "conv_B_bias", "conv_C", "conv_C_bias",
                                   "dt_bias", "A_log", "D", "gnorm", "out")}
        z = mm("se,ei->si", h, lw["in_z"])
        xs = jax.nn.silu(_conv(mm("se,ei->si", h, lw["in_x"]), lw["conv_x"],
                               lw["conv_x_bias"])).reshape(S, H, P)
        Bm = jax.nn.silu(_conv(mm("se,en->sn", h, lw["in_B"]), lw["conv_B"],
                               lw["conv_B_bias"])).reshape(S, G, N)
        Cm = jax.nn.silu(_conv(mm("se,en->sn", h, lw["in_C"]), lw["conv_C"],
                               lw["conv_C_bias"])).reshape(S, G, N)
        # head h reads group h // (H / G)
        Bm, Cm = jnp.repeat(Bm, H // G, axis=1), jnp.repeat(Cm, H // G,
                                                            axis=1)
        dt = jax.nn.softplus(mm("se,eh->sh", h, lw["in_dt"])
                             + lw["dt_bias"].astype(jnp.float32))
        A = -jnp.exp(lw["A_log"].astype(jnp.float32))
        Dv = lw["D"].astype(jnp.float32)

        def step(state, t):                  # state: (H, P, N)
            x_t, dt_t, B_t, C_t = t
            state = state * jnp.exp(dt_t * A)[:, None, None] + \
                dt_t[:, None, None] * x_t[:, :, None] * B_t[:, None, :]
            y = jnp.einsum("hpn,hn->hp", state, C_t, precision=HI)
            return state, y + Dv[:, None] * x_t

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                            (xs, dt, Bm, Cm))
        g = (y.reshape(S, H * P) * jax.nn.silu(z)).reshape(S, G, -1)
        g = _rms(g, lw["gnorm"].reshape(G, -1), eps).reshape(S, H * P)
        return mm("si,ie->se", g, lw["out"])

    def attention(h, i):
        q = mm("se,ehd->shd", h, w["q"][i])
        k = jnp.repeat(mm("se,ehd->shd", h, w["k"][i]), Hq // Hkv, axis=1)
        v = jnp.repeat(mm("se,ehd->shd", h, w["v"][i]), Hq // Hkv, axis=1)
        qp = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))

        def block(j):
            qb = jax.lax.dynamic_slice_in_dim(qp, j * Q_BLOCK, Q_BLOCK)
            s = mm("qhd,khd->hqk", qb, k) / math.sqrt(D)
            qpos = j * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            return mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, Hq, D)
        return mm("shd,hde->se", o[:S], w["o"][i])

    def experts(h, i):
        scores = jax.nn.sigmoid(mm("se,er->sr", h, w["router"][i]))
        _, idx = jax.lax.top_k(scores + w["router_bias"][i], c[
            "num_experts_per_tok"])
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        if c["norm_topk_prob"]:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        gates = gates * c["routed_scaling_factor"]

        def expert(j):                       # held expert first + j
            gate = jnp.sum(jnp.where(idx == d["first"] + j, gates, 0.0), -1)
            u = jnp.square(jax.nn.relu(mm("se,ef->sf", h, w["up"][i, j])))
            return gate[:, None] * mm("sf,fe->se", u, w["down"][i, j])

        y = jnp.sum(jax.lax.map(expert, jnp.arange(d["n"])), axis=0)
        u = jnp.square(jax.nn.relu(mm("se,ef->sf", h, w["shared_up"][i])))
        return y + mm("sf,fe->se", u, w["shared_down"][i])

    ranks = {"M": 0, "E": 0, "*": 0}
    n1 = 0                                   # rank among the ln1 holders
    for kind in d["pattern"]:
        i = ranks[kind]
        ranks[kind] += 1
        if kind == "E":
            f = experts(_rms(x, w["ln2"][i], eps), i)
        else:
            h = _rms(x, w["ln1"][n1], eps)
            n1 += 1
            f = mamba(h, i) if kind == "M" else attention(h, i)
        x = residual(x + f)
    return _rms(x, w["final_norm"], eps)


def head(w: dict, c: dict):
    """The LM head, (V, E): untied from the embedding."""
    return w["lm_head"]
