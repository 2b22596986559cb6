"""Whether what the timed path served is correct.

Once the window has closed and the engine is gone, a sample of the greedy
requests the engine finished, drawn from the seed and holding the longest
of them, is run through the plain float32 reference
(``bench/reference/<family>.py``) over each prompt with its served tokens.
At every served position the number compared is how far the served
token's logit lies below the reference's best logit there: zero where the
program chose what the reference would, small where bf16 rounding flipped
a near tie.  The widest such gap over the sample is held to the cell's
limit.

The control (``control=True``, for calibration only) puts a lower
precision in the program's place: the same reference with every matmul
operand rounded to float8 (e4m3), whose own first choice at each position
is read against the float32 reference the same way, and judged by the
same ``verdict`` against the same limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic, weights

ROW_BLOCK = 128


def sample(obs, check: dict, seed: int) -> list:
    """Finished greedy requests: the longest (by served tokens), then
    others drawn from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [r for r in obs.records if r.job.greedy and r.req.done
            and r.req.out_tokens]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.req.out_tokens), r.job.index))
    rng = traffic.seed_words(seed, "check")
    rest = [done[i] for i in rng.permutation(len(done) - 1) + 1] \
        if len(done) > 1 else []
    out, n = [done[0]], len(done[0].req.out_tokens)
    for r in rest:
        if n >= check["min_tokens"] or len(out) >= check["max_requests"]:
            break
        out.append(r)
        n += len(r.req.out_tokens)
    return out


def verdict(res: dict, chk: dict, gap: str = "served_gap") -> tuple:
    """-> (correct, the numbers compared, each with its limit) for a
    result of ``compare``: the widest gap of ``gap`` ("served_gap" for the
    program, "control_gap" for the control) within the cell's limit, over
    at least the cell's least number of served tokens."""
    numbers = {
        gap: {"value": res[gap], "limit": chk["limit"]},
        "tokens_compared": {"value": res["tokens"],
                            "limit": chk["min_tokens_required"]}}
    correct = res[gap] is not None and res[gap] <= chk["limit"] \
        and res["tokens"] >= chk["min_tokens_required"]
    return bool(correct), numbers


def fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _gaps(ref, c, control, w, tokens, rows, targets):
    """-> per row (gap of the target, gap of the control's first choice)."""
    hp = jax.lax.Precision.HIGHEST
    head = ref.head(w, c).astype(jnp.float32)
    h = ref.hidden(w, tokens, c)[rows]
    hc = ref.hidden(w, tokens, c, fp8)[rows] if control else h
    nb = rows.shape[0] // ROW_BLOCK

    def block(i):
        sl = functools.partial(jax.lax.dynamic_slice_in_dim,
                               start_index=i * ROW_BLOCK,
                               slice_size=ROW_BLOCK)
        lg = jnp.einsum("re,ve->rv", sl(h), head, precision=hp)
        best = lg.max(-1)
        tgt = jnp.take_along_axis(lg, sl(targets)[:, None], -1)[:, 0]
        if control:
            lc = jnp.einsum("re,ve->rv", fp8(sl(hc)), fp8(head),
                            precision=hp)
            pick = lc.argmax(-1)
            ctl = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        else:
            ctl = best
        return best - tgt, best - ctl

    g, gc = jax.lax.map(block, jnp.arange(nb))
    return g.reshape(-1), gc.reshape(-1)


def compare(ref, cfgfile: dict, spec: dict, seed32: int, recs: list,
            seq_len: int, max_rows: int, control: bool = False) -> dict:
    """-> {"served_gap", "control_gap", "tokens", "requests"} over
    ``recs``; sequences are padded to ``seq_len`` and served positions to
    ``max_rows`` so that one compiled reference serves every request."""
    if not recs:
        return {"served_gap": None, "control_gap": None, "tokens": 0,
                "requests": 0}
    w = weights.canonical(spec, seed32)
    n_rows = -(-max_rows // ROW_BLOCK) * ROW_BLOCK
    fn = jax.jit(functools.partial(_gaps, ref, cfgfile, control))
    worst, worst_c, n_tok = 0.0, 0.0, 0
    for rec in recs:
        prompt = np.asarray(rec.job.prompt, np.int32)
        out = np.asarray(rec.req.out_tokens, np.int32)
        seq = np.concatenate([prompt, out])
        if len(seq) > seq_len or len(out) > n_rows:
            raise ValueError(f"request {rec.job.index} holds {len(seq)} "
                             f"tokens; the check is built for {seq_len}")
        tokens = np.zeros(seq_len, np.int32)
        tokens[:len(seq)] = seq
        rows = np.zeros(n_rows, np.int32)
        rows[:len(out)] = len(prompt) - 1 + np.arange(len(out))
        targets = np.zeros(n_rows, np.int32)
        targets[:len(out)] = out
        g, gc = jax.device_get(fn(w, jnp.asarray(tokens), jnp.asarray(rows),
                                  jnp.asarray(targets)))
        worst = max(worst, float(np.max(g[:len(out)])))
        worst_c = max(worst_c, float(np.max(gc[:len(out)])))
        n_tok += len(out)
    del w
    return {"served_gap": worst, "control_gap": worst_c if control else None,
            "tokens": n_tok, "requests": len(recs)}
