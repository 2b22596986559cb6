"""The yardstick's pure parts: the traffic generator, the peaks table and
the operation/byte counts of one tick, against hand counts."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import arith, loop, traffic  # noqa: E402

BENCH = ROOT / "bench"


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", ["chat", "docs"])
def test_same_seed_same_requests(mix):
    m = _mix(mix)
    seed = 2**40 + 12345                    # wider than 32 bits
    a = traffic.make_jobs(m, seed, 100, 151936, rate_rps=1.5)
    b = traffic.make_jobs(m, seed, 100, 151936, rate_rps=1.5)
    for x, y in zip(a, b, strict=True):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.greedy, x.due_s) == \
            (y.max_new_tokens, y.greedy, y.due_s)
    c = traffic.make_jobs(m, seed + 1, 100, 151936, rate_rps=1.5)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a, c, strict=True))


@pytest.mark.parametrize("mix", ["chat", "docs"])
def test_every_seed_gets_the_same_sizes_and_arrivals(mix):
    m = _mix(mix)
    blk = m["block"]
    shape = None
    for seed in (1, 2, 3**30):
        jobs = traffic.make_jobs(m, seed, 2 * blk, 50277, rate_rps=2.0)
        p = [len(j.prompt) for j in jobs]
        o = [j.max_new_tokens for j in jobs]
        assert m["prompt"]["min"] <= min(p) and max(p) <= m["prompt"]["max"]
        assert m["output"]["min"] <= min(o) and max(o) <= m["output"]["max"]
        assert all(int(j.prompt.min()) >= traffic.FIRST_TOKEN_ID
                   and int(j.prompt.max()) < 50277 for j in jobs)
        got = (p, o, [j.due_s for j in jobs], [j.greedy for j in jobs])
        assert shape is None or got == shape
        shape = got
        # each block holds the same strata of lengths
        assert sorted(p[:blk]) == sorted(p[blk:])
        assert sum(j.greedy for j in jobs[:blk]) == -(-blk //
                                                        m["greedy_every"])


def test_open_loop_rate_is_exact_per_block():
    m = _mix("chat")
    jobs = traffic.make_jobs(m, 9, m["block"] + 1, 1000, rate_rps=2.5)
    assert jobs[0].due_s == 0.0
    assert jobs[m["block"]].due_s == pytest.approx(m["block"] / 2.5)
    assert all(b.due_s > a.due_s for a, b in zip(jobs, jobs[1:]))


def test_peaks_lookup():
    p = arith.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        arith.peaks("cpu")


def _obs(records, window=(0.0, 1.0), ticks=()):
    return loop.Observation(records=records, window=window,
                            ticks=list(ticks), collect_wait_s=0.0,
                            slots=16, chunk=128,
                            lateness_s=[], waiting=[])


def _rec(prompt_len, token_t):
    job = traffic.Job(index=0, prompt=np.zeros(prompt_len, np.int32),
                      max_new_tokens=len(token_t), greedy=True)
    r = loop.Record(job=job, req=None, due=0.0)
    r.token_t = list(token_t)
    return r


def test_qwen3_tick_against_hand_count():
    c = json.loads((BENCH / "configs" / "qwen3-0.6b.json").read_text())
    costs = arith.model_costs(c)
    # one layer's matmul weights: q 1024x2048, k and v 1024x1024 each,
    # o 2048x1024, gate/up/down 3 x 1024x3072
    layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    assert layer == 15_728_640
    head = 2 * 1024 * 151_936
    kv_token = 28 * 2 * 8 * 128 * 2                 # K and V, bf16
    fl, by = costs.decode_token(1000)
    assert fl == 2 * 28 * layer + head + 4 * 28 * 16 * 128 * 1000
    assert by == kv_token * 1000 == 114_688_000
    fl, by = costs.prefill(300, 128)
    assert fl == 300 * 2 * 28 * layer + head + 4 * 28 * 16 * 128 * 45_150
    assert by == kv_token * (300 + 0 + 128 + 256)
    # the served weights: embedding (tied head) once, 28 layers, norms
    w = 2 * (151_936 * 1024 + 1024 + 28 * (layer + 2 * 1024 + 2 * 128))
    from bench.reference import qwen3
    assert arith.weight_bytes(qwen3.weight_spec(c)) == w
    # a tick: a prompt of 300 finishing (its first token) and two decode
    # tokens of a 1000-token prompt, in a 0.5 s window with one busy tick
    obs = _obs([_rec(300, [0.1]), _rec(1000, [-1.0, 0.2, 0.3])],
               window=(0.0, 0.5), ticks=[(0.0, 0.4, True)])
    fl, by = arith.window_work(obs, costs, w)
    f1, b1 = costs.prefill(300, 128)
    f2, b2 = costs.decode_token(1001)
    f3, b3 = costs.decode_token(1002)
    assert (fl, by) == (f1 + f2 + f3, b1 + b2 + b3 + w)
    share = arith.whole_step_share(obs, costs, w, arith.peaks("TPU v5 lite"))
    assert share == pytest.approx(100 * max(fl / 197e12, by / 819e9) / 0.5)


def test_mamba2_tick_against_hand_count():
    c = json.loads((BENCH / "configs" / "mamba2-370m.json").read_text())
    costs = arith.model_costs(c)
    # in_z, in_x 1024x2048 each, B and C 1024x128, dt 1024x32, out 2048x1024
    layer = 1024 * (2 * 2048 + 2 * 128 + 32) + 2048 * 1024
    ssm = 2 * 4 * (2048 + 256) + 5 * 32 * 64 * 128
    state = 48 * (32 * 64 * 128 * 4 + 3 * (2048 + 256) * 2)
    fl, by = costs.decode_token(5000)               # context does not matter
    assert fl == 48 * (2 * layer + ssm) + 2 * 1024 * 50_288 == 799_080_448
    assert by == 2 * state == 101_990_400
    fl, by = costs.prefill(300, 128)
    assert fl == 300 * 48 * (2 * layer + ssm) + 2 * 1024 * 50_288
    assert by == 2 * state * 3
