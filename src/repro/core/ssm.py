"""Mamba-2 SSD (state-space duality) blocks — chunked scan + decode step.

Per-shard shapes (heads sharded on the model axis per the paper's
head-parallel partitioning; SSD heads are mutually independent exactly like
attention heads):

    x  : (B, S, H, P)   local heads H, head dim P
    dt : (B, S, H)      softplus-activated step sizes
    Bm, Cm : (B, S, N)  state projections shared by every head (one
                        group), or (B, S, H, N): each head's own group's
    A  : (H,)           negative per-head decay
    state : (B, H, P, N)

The chunked algorithm is exact (not an approximation): intra-chunk quadratic
term + inter-chunk state recurrence under ``lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(x, w, state=None, tail_idx=None, bias=None):
    """Depthwise causal conv.  x: (B, S, C), w: (C, K), bias: (C,) or None.
    state: (B, K-1, C) previous inputs (decode) or None (prefill).
    tail_idx: scalar index of the last *valid* input row — the returned
    state is the K-1 inputs ending there (inclusive), so a chunk whose
    tail is padding (chunked prefill past the prompt's end) still hands
    the next step the true conv history.  None = S - 1 (all rows valid).
    Returns (y, new_state)."""
    B, S, C = x.shape
    K = w.shape[1]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + S, :] * w[:, i].astype(x.dtype) for i in range(K))
    if bias is not None:
        y = y + bias.astype(x.dtype)
    if K == 1:
        return y, jnp.zeros((B, 0, C), x.dtype)
    if tail_idx is None:
        new_state = xp[:, -(K - 1):, :]
    else:
        # input row s sits at xp index K-1+s; the K-1 rows ending at
        # tail_idx inclusive are xp[tail_idx+1 : tail_idx+K]
        new_state = jax.lax.dynamic_slice_in_dim(xp, tail_idx + 1, K - 1,
                                                 axis=1)
    return y, new_state


def ssd_chunked(x, dt, Bm, Cm, A, D, chunk: int, state0=None,
                return_extras: bool = False):
    """Exact chunked SSD.  Returns (y (B,S,H,P), final_state (B,H,P,N)).

    With ``return_extras``: also (cum_decay (B,S,H) = exp(prefix-sum of a),
    total_decay (B,H)) — the linear-correction terms context parallelism
    needs to fold an upstream shard's incoming state into local outputs:
        y(state_in) = y(0) + (C_t . state_in) * cum_decay_t
        state_out   = state_local + total_decay * state_in
    """
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    per_head = Bm.ndim == 4            # (B,S,H,N): each head's group
    hn = "hn" if per_head else "n"     # the B/C axes in the einsums
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bc_pad = ((0, 0), (0, pad)) + ((0, 0),) * (Bm.ndim - 2)
        Bm = jnp.pad(Bm, bc_pad)
        Cm = jnp.pad(Cm, bc_pad)
    Sp = S + pad
    nc, Q = Sp // chunk, chunk

    xf = x.astype(jnp.float32).reshape(B, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(B, nc, Q, H)
    Bf = Bm.astype(jnp.float32).reshape((B, nc, Q) + Bm.shape[2:])
    Cf = Cm.astype(jnp.float32).reshape((B, nc, Q) + Cm.shape[2:])
    Af = A.astype(jnp.float32)

    a = dtf * Af                                   # (B,nc,Q,H), <= 0
    cs = jnp.cumsum(a, axis=2)                     # inclusive
    cs_last = cs[:, :, -1]                         # (B,nc,H)

    # intra-chunk: y[i] = sum_{j<=i} (C_i.B_j) exp(cs_i-cs_j) dt_j x_j
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]   # (B,nc,Q,Q,H) i,j
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    if per_head:
        W = jnp.einsum("bcihn,bcjhn->bcijh", Cf, Bf) * L
    else:
        G = jnp.einsum("bcin,bcjn->bcij", Cf, Bf)  # (B,nc,Q,Q)
        W = G[..., None] * L                       # (B,nc,Q,Q,H)
    xdt = xf * dtf[..., None]                      # (B,nc,Q,H,P)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", W, xdt)

    # chunk state contributions: sum_j exp(cs_last - cs_j) dt_j B_j (x) x_j
    decay_to_end = jnp.exp(cs_last[:, :, None, :] - cs)       # (B,nc,Q,H)
    contrib = jnp.einsum(f"bcjh,bcj{hn},bcjhp->bchpn",
                         decay_to_end * dtf, Bf, xf)          # (B,nc,H,P,N)
    chunk_decay = jnp.exp(cs_last)                            # (B,nc,H)

    def step(S_prev, inp):
        dec, con = inp                                        # (B,H), (B,H,P,N)
        S_new = S_prev * dec[..., None, None] + con
        return S_new, S_prev

    S0 = (jnp.zeros((B, H, P, N), jnp.float32) if state0 is None
          else state0.astype(jnp.float32))
    S_final, S_prevs = jax.lax.scan(
        step, S0, (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(contrib, 1, 0)))
    S_prevs = jnp.moveaxis(S_prevs, 0, 1)                     # (B,nc,H,P,N)

    # inter-chunk: y[i] += C_i . (exp(cs_i) * S_prev)
    y_inter = jnp.einsum(f"bci{hn},bchpn->bcihp", Cf, S_prevs) * \
        jnp.exp(cs)[..., None]
    y = (y_intra + y_inter).reshape(B, Sp, H, P)[:, :S]
    y = y + xf.reshape(B, Sp, H, P)[:, :S] * D.astype(jnp.float32)[None, None, :, None]
    if return_extras:
        # global prefix-sum of a across the whole local sequence
        chunk_prefix = jnp.cumsum(cs_last, axis=1) - cs_last   # (B,nc,H)
        cum_a = cs + chunk_prefix[:, :, None, :]               # (B,nc,Q,H)
        cum_decay = jnp.exp(cum_a).reshape(B, Sp, H)[:, :S]
        total_decay = jnp.exp(chunk_prefix[:, -1] + cs_last[:, -1])
        return y.astype(x.dtype), S_final, cum_decay, total_decay
    return y.astype(x.dtype), S_final


def ssd_decode_step(x, dt, Bm, Cm, A, D, state):
    """One token.  x: (B,H,P) dt: (B,H) Bm/Cm: (B,N), or (B,H,N) per
    head, state: (B,H,P,N)."""
    hn = "hn" if Bm.ndim == 3 else "n"
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dec = jnp.exp(dtf * A.astype(jnp.float32))                # (B,H)
    contrib = jnp.einsum(f"bh,b{hn},bhp->bhpn", dtf, Bm.astype(jnp.float32),
                         xf)
    state = state * dec[..., None, None] + contrib
    y = jnp.einsum(f"b{hn},bhpn->bhp", Cm.astype(jnp.float32), state)
    y = y + xf * D.astype(jnp.float32)[None, :, None]
    return y.astype(x.dtype), state
