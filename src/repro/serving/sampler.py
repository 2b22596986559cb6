"""Token samplers over (possibly vocab-padded) logits."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


@dataclass
class SamplerConfig:
    temperature: float = 0.0      # 0 => greedy
    top_k: int = 0
    top_p: float = 0.0


class DeviceRows:
    """Rows of logits left on the device, with their greedy tokens
    already picked there (``core.steps.greedy_ids``): what a greedy
    engine hands its row sampler in place of fetched logits.  A greedy
    ``sample_from_logits`` takes ``ids`` and reads no logits.  A slice of
    rows is a ``DeviceRows`` of those rows; any other read (another index,
    ``np.asarray``) calls ``fetch`` once for the float32 logits and reads
    them."""

    def __init__(self, ids: np.ndarray, fetch):
        self.ids = ids                  # (N,) int32
        self._fetch = fetch
        self._values: Optional[np.ndarray] = None

    def values(self) -> np.ndarray:
        if self._values is None:
            self._values = self._fetch()
        return self._values

    def __getitem__(self, key):
        if isinstance(key, slice):
            return DeviceRows(self.ids[key], lambda: self.values()[key])
        return self.values()[key]

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.values(), dtype)


def sample_from_logits(logits, cfg: SamplerConfig, vocab_size: int,
                       rng: np.random.RandomState):
    """logits: (B, V_pad) float32, or a ``DeviceRows`` -> (B,) int32."""
    if isinstance(logits, DeviceRows):
        if cfg.temperature <= 0:
            return logits.ids
        logits = logits.values()
    lg = logits[:, :vocab_size].astype(np.float64)
    if cfg.temperature <= 0:
        return lg.argmax(axis=-1).astype(np.int32)
    lg = lg / cfg.temperature
    if cfg.top_k:
        kth = np.partition(lg, -cfg.top_k, axis=-1)[:, -cfg.top_k][:, None]
        lg = np.where(lg < kth, -np.inf, lg)
    p = np.exp(lg - lg.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    if cfg.top_p:
        srt = np.argsort(-p, axis=-1)
        out = np.zeros(lg.shape[0], np.int32)
        for b in range(lg.shape[0]):
            ps = p[b, srt[b]]
            keep = np.cumsum(ps) - ps < cfg.top_p
            keep[0] = True
            sel = srt[b, keep]
            pp = p[b, sel] / p[b, sel].sum()
            out[b] = rng.choice(sel, p=pp)
        return out
    return np.array([rng.choice(lg.shape[1], p=p[b])
                     for b in range(lg.shape[0])], np.int32)


def speculative_sample(logits: np.ndarray, draft, cfg: SamplerConfig,
                       vocab_size: int, rng: np.random.RandomState):
    """Accept/emit loop over verify-step logits — the deterministic-draft
    special case of rejection sampling, token-identical to the one-token
    path by construction.

    ``logits``: (Q, V_pad) where row i is the model's next-token
    distribution after consuming the last accepted token plus draft[:i];
    ``draft``: the kd <= Q-1 proposed tokens.  Row i is sampled exactly as
    ``sample_from_logits`` would on the one-token path (greedy consumes no
    RNG; temperature > 0 consumes one draw per emitted row, in emission
    order), the sampled token is emitted, and drafting continues past row
    i only while the sample agrees with draft[i].  Because the draft is a
    point mass, "target sample == draft token" IS the rejection test, and
    the first disagreeing row already holds the corrected sample — no
    residual-distribution resample is needed.  -> emitted tokens
    (1 <= len <= len(draft) + 1)."""
    out = []
    for i in range(len(draft) + 1):
        tok = int(sample_from_logits(logits[i:i + 1], cfg, vocab_size,
                                     rng)[0])
        out.append(tok)
        if i < len(draft) and tok != int(draft[i]):
            break
    return out


def merged_topk_sample(local_logits_gathered, cfg, vocab_size, rng):
    """Exact sampling from per-shard top-k candidates (serving on a TP mesh):
    the global top-k is a subset of the union of per-shard top-k's.

    Applies the full ``SamplerConfig`` semantics — temperature, top-k AND
    top-p — over the merged candidate set, consuming the request's RNG
    stream exactly like ``sample_from_logits`` does on the single-host
    path, so a TP mesh and a single host draw identical tokens from the
    same seed."""
    vals, ids = local_logits_gathered                  # (tp*k,), (tp*k,)
    mask = ids < vocab_size
    vals = np.where(mask, vals, -np.inf).astype(np.float64)
    if cfg.temperature <= 0:
        return int(ids[int(np.argmax(vals))])
    k = cfg.top_k or len(vals)
    order = np.argsort(-vals)[:k]
    v = vals[order] / cfg.temperature
    p = np.exp(v - v.max())
    p /= p.sum()
    if cfg.top_p:
        # nucleus filter over the merged candidates: `order` is already
        # probability-descending, so the cumulative mask mirrors the
        # single-host path token for token (which draws over sel in that
        # same order)
        keep = np.cumsum(p) - p < cfg.top_p
        keep[0] = True
        sel = order[keep]
        pp = p[keep] / p[keep].sum()
        return int(ids[sel[int(rng.choice(len(sel), p=pp))]])
    # without top_p the single-host path draws over the FULL vocab in
    # token-id order; zero-probability gaps don't shift the CDF, so
    # drawing over the candidates sorted by token id consumes the same
    # uniform identically
    by_id = np.argsort(ids[order], kind="stable")
    return int(ids[order[by_id][int(rng.choice(len(order), p=p[by_id]))]])
