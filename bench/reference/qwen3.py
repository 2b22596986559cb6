"""Plain float32 forward of the Qwen3 dense decoder (hf:Qwen/Qwen3-0.6B).

Written from the published description (Qwen3 technical report and the
``Qwen3ForCausalLM`` configuration), not from the program: pre-norm
decoder layers with RMSNorm, grouped-query attention with RMSNorm on each
query and key head before rotary embeddings (split-half rotation,
``rope_theta``), causal softmax attention scaled by head_dim^-0.5, a SwiGLU
feed-forward, a final RMSNorm and an LM head tied to the embedding.  No
biases.  Every matrix product runs in float32 at ``HIGHEST`` precision; a
``cast`` function applied to every matmul operand gives the lower-precision
control.

Weights are the benchmark's own, made from the seed by ``bench/weights.py``
from ``weight_spec`` below.  Norm weights are multipliers, as published.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (bounds the scores)


def dims(c: dict) -> dict:
    return {"E": c["hidden_size"], "L": c["num_hidden_layers"],
            "H": c["num_attention_heads"], "G": c["num_key_value_heads"],
            "D": c["head_dim"], "F": c["intermediate_size"],
            "V": c["vocab_size"]}


def weight_spec(c: dict) -> dict:
    """name -> (shape, dtype, init, scale).  ``normal``: N(0, scale^2);
    ``norm``: 1 + N(0, scale^2).  Projections keep unit scale through the
    layer, residual branches are damped by sqrt(2L), and the embedding
    (tied to the head) is scaled so the logits spread by about two units."""
    d = dims(c)
    E, L, H, G, D, F, V = (d[k] for k in "ELHGDFV")
    bf, res = "bfloat16", 1.0 / math.sqrt(2 * L)
    return {
        "embed": ((V, E), bf, "normal", 0.06),
        "final_norm": ((E,), bf, "norm", 0.1),
        "ln1": ((L, E), bf, "norm", 0.1),
        "q": ((L, E, H, D), bf, "normal", E ** -0.5),
        "k": ((L, E, G, D), bf, "normal", E ** -0.5),
        "v": ((L, E, G, D), bf, "normal", E ** -0.5),
        "q_norm": ((L, D), bf, "norm", 0.1),
        "k_norm": ((L, D), bf, "norm", 0.1),
        "o": ((L, H, D, E), bf, "normal", (H * D) ** -0.5 * res),
        "ln2": ((L, E), bf, "norm", 0.1),
        "gate": ((L, E, F), bf, "normal", E ** -0.5),
        "up": ((L, E, F), bf, "normal", E ** -0.5),
        "down": ((L, F, E), bf, "normal", F ** -0.5 * res),
    }


LAYER_KEYS = ("ln1", "q", "k", "v", "q_norm", "k_norm", "o", "ln2", "gate",
              "up", "down")


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (S, heads, D); pos: (S,).  HF ``rotate_half`` convention."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # (S, D/2)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(w: dict, tokens, c: dict, cast=lambda a: a):
    """tokens: (S,) int32 -> final-normed hidden states (S, E) float32.
    Causal, so padding after the last real token changes nothing before
    it.  ``cast`` rounds every matmul operand (identity: the reference)."""
    d = dims(c)
    H, G, D = d["H"], d["G"], d["D"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    S = tokens.shape[0]
    nb = -(-S // Q_BLOCK)
    pos = jnp.arange(S)
    x = w["embed"].astype(jnp.float32)[tokens]

    def mm(spec, a, b):
        return jnp.einsum(spec, cast(a.astype(jnp.float32)),
                          cast(b.astype(jnp.float32)), precision=HI)

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = _rms(mm("se,ehd->shd", h, lw["q"]), lw["q_norm"], eps)
        k = _rms(mm("se,ehd->shd", h, lw["k"]), lw["k_norm"], eps)
        v = mm("se,ehd->shd", h, lw["v"])
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        kx = jnp.repeat(k, H // G, axis=1)                     # (S, H, D)
        vx = jnp.repeat(v, H // G, axis=1)
        qp = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
            s = mm("qhd,khd->hqk", qb, kx) / math.sqrt(D)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, None, :] <= qpos[None, :, None], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return mm("hqk,khd->qhd", p, vx)

        o = jax.lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, H, D)
        x = x + mm("shd,hde->se", o[:S], lw["o"])
        h = _rms(x, lw["ln2"], eps)
        f = jax.nn.silu(mm("se,ef->sf", h, lw["gate"])) * \
            mm("se,ef->sf", h, lw["up"])
        return x + mm("sf,fe->se", f, lw["down"]), None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in LAYER_KEYS})
    return _rms(x, w["final_norm"], eps)


def head(w: dict, c: dict):
    """The LM head, (V, E): tied to the embedding."""
    return w["embed"]
