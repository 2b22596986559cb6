"""The plain references against the engine's paged path, at a reduced
size on the CPU: the logits the sampler receives for greedy requests
(chunked prefill into the pools or slabs, then decode through them)
match the float32 reference's, and the served tokens' gap is nought."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cellfiles  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

# bf16 weights and activations in the program against float32 in the
# reference: over two layers the logits agree to a few bf16 steps of
# their scale (2^-8 = 0.4% each); a wrong page, position or state moves
# them by their whole scale
LOGIT_RTOL = 0.05


def _serve(cfgfile, seed, n_req=3):
    import importlib

    from bench import program, traffic
    from repro.serving import Request
    ref = importlib.import_module(f"bench.reference.{cfgfile['reference']}")
    spec = ref.weight_spec(cfgfile)
    cell = {"slots": 2, "seq_budget": 128}
    eng = program.build(cfgfile, cell, {"sampling": {}}, spec,
                        traffic.seed32(seed, "weights"), 1)
    rows = {}
    sample = eng._sample_row

    def keep(logits, b, req):
        rows.setdefault(req.rid, []).append(
            np.asarray(logits[b, :eng.cfg.vocab_size], np.float32).copy())
        return sample(logits, b, req)

    eng._sample_row = keep
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(2, 500, int(n)).astype(
        np.int32), max_new_tokens=6) for i, n in enumerate((45, 70, 9)[:n_req])]
    for r in reqs:
        eng.submit(r)
    eng.run()
    return ref, spec, reqs, rows


@pytest.mark.parametrize("name", ["tiny.json", "mamba2-tiny.json"])
def test_reference_matches_paged_engine_logits(name):
    import jax
    import jax.numpy as jnp

    from bench import traffic, weights
    cfgfile = json.loads((DATA / name).read_text())
    ref, spec, reqs, rows = _serve(cfgfile, seed=11)
    w = weights.canonical(spec, traffic.seed32(11, "weights"))
    head = np.asarray(ref.head(w, cfgfile), np.float32)
    worst = 0.0
    for r in reqs:
        assert r.done and len(r.out_tokens) == 6
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1],
                                                   np.int32)])
        with jax.default_matmul_precision("highest"):
            h = np.asarray(ref.hidden(w, jnp.asarray(seq), cfgfile))
        L = len(r.prompt)
        want = h[L - 1:] @ head.T[:, :]
        got = np.stack(rows[r.rid])
        assert got.shape == want.shape
        worst = max(worst, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
    assert worst <= LOGIT_RTOL, worst


@pytest.mark.parametrize("name", ["tiny.json", "mamba2-tiny.json"])
def test_served_gap_is_nought_and_control_reads_higher(name):
    """The check's two readings at a reduced size: the program's greedy
    tokens sit on the reference's best logit, and the float8 control's
    first choices do not, so the run's own verdict passes the program and
    fails the control at the scratch cell's limit."""
    from bench import check, loop, traffic
    cfgfile = json.loads((DATA / name).read_text())
    ref, spec, reqs, _ = _serve(cfgfile, seed=12)
    recs = [loop.Record(job=traffic.Job(index=r.rid, prompt=r.prompt,
                                        max_new_tokens=6, greedy=True),
                        req=r, due=0.0) for r in reqs]
    res = check.compare(ref, cfgfile, spec, traffic.seed32(12, "weights"),
                        recs, seq_len=128, max_rows=8, control=True)
    assert res["tokens"] == 18
    assert res["served_gap"] <= 0.02, res
    assert res["control_gap"] > 4 * max(res["served_gap"], 0.005), res
    chk = cellfiles.CELL["check"]
    assert check.verdict(res, chk)[0] is True, res
    correct, numbers = check.verdict(res, chk, "control_gap")
    assert correct is False, res
    assert numbers["control_gap"]["value"] > numbers["control_gap"]["limit"]


def test_verdict_takes_the_limit_and_the_least_tokens():
    from bench import check
    chk = {"limit": 1.0, "min_tokens_required": 100}
    res = {"served_gap": 0.5, "control_gap": 4.0, "tokens": 400}
    assert check.verdict(res, chk) == (True, {
        "served_gap": {"value": 0.5, "limit": 1.0},
        "tokens_compared": {"value": 400, "limit": 100}})
    assert check.verdict(res, chk, "control_gap")[0] is False
    assert check.verdict(dict(res, tokens=99), chk)[0] is False
    assert check.verdict(dict(res, served_gap=None), chk)[0] is False
