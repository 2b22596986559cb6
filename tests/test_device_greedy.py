"""Greedy tokens picked on the device: ``steps.greedy_ids`` against the
host's argmax, and a greedy paged engine, which fetches token ids and not
logits, against the host path that fetches the logits and takes the
argmax of each row."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import model, steps
from repro.core.partition import ShardingPlan
from repro.serving import Request, SamplerConfig, ServingEngine
from repro.serving.sampler import DeviceRows, sample_from_logits

PLAN = ShardingPlan(tp=1, kv_cache_dtype="float32")
V, V_PAD = 200, 256


def _logits(case: str) -> np.ndarray:
    """(6, V_PAD) float32 rows that bf16 holds exactly, for ``case``."""
    rng = np.random.default_rng(7)
    x = np.round(rng.normal(size=(6, V_PAD)) * 16) / 16
    if case == "padding_largest":
        x[:, V:] = 100.0             # padding columns hold the largest values
    elif case == "ties":
        x[:] = 0.0
        for r, cols in enumerate([(3, 9), (0, 199), (5, 6, 7), (198, 199),
                                  tuple(range(V)), (42, 43)]):
            x[r, list(cols)] = 2.5   # the first of equal maxima must win
    return x.astype(np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["random", "padding_largest", "ties"])
def test_greedy_ids_equal_host_argmax(case, dtype):
    x = _logits(case)
    dev = jnp.asarray(x, dtype)
    want = np.argmax(np.asarray(dev, np.float64)[:, :V], axis=-1)
    got = np.asarray(steps.greedy_ids(dev, V))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    greedy = SamplerConfig(temperature=0.0)
    np.testing.assert_array_equal(
        sample_from_logits(np.asarray(dev, np.float32), greedy, V, None),
        want)


def test_device_rows_fetch_only_when_read():
    """A greedy sampler takes the ids and fetches nothing; a slice of
    rows stays unfetched; any other read fetches the logits once."""
    x = _logits("random")
    ids = np.argmax(x[:, :V], axis=-1).astype(np.int32)
    calls = []

    def fetch():
        calls.append(1)
        return x

    rows = DeviceRows(ids, fetch)
    greedy = SamplerConfig(temperature=0.0)
    for b in range(len(ids)):
        assert sample_from_logits(rows[b:b + 1], greedy, V, None)[0] == ids[b]
    assert not calls
    np.testing.assert_array_equal(rows[2, :V], x[2, :V])
    np.testing.assert_array_equal(np.asarray(rows[1:3]), x[1:3])
    hot = SamplerConfig(temperature=1.0)
    tok = sample_from_logits(rows[4:5], hot, V, np.random.RandomState(0))
    assert 0 <= tok[0] < V
    assert len(calls) == 1


def _tiny(name):
    cfg = reduced(get_config(name), dtype="float32")
    return cfg, model.init_params(cfg, PLAN, seed=2)


def _serve(cfg, params, mesh, scenario, sampler, sample_row=None):
    """Serve a few requests; -> (requests, stats).  ``sample_row``, where
    given, replaces the engine's row sampler."""
    slots, dp = (1, 1) if scenario == "preempt" else \
        (2, 2 if scenario == "replicas" else 1)
    eng = ServingEngine.build_paged(cfg, PLAN, mesh, slots, 64, params,
                                    page_size=8, prefill_chunk=8, dp=dp,
                                    sampler=sampler, eos_id=-1)
    if sample_row is not None:
        eng._sample_row = sample_row(eng)
    rng = np.random.RandomState(4)
    new = 1 if scenario == "prefill" else 6
    reqs = [Request(rid=i, prompt=rng.randint(2, cfg.vocab_size, L)
                    .astype(np.int32), max_new_tokens=new)
            for i, L in enumerate([13, 9, 21, 5])]
    for r in reqs:
        eng.submit(r)
    if scenario == "preempt":
        # the first request is preempted mid-decode and resumes by
        # prefilling its prompt and the tokens it had emitted
        for _ in range(200):
            if len(reqs[0].out_tokens) >= 3:
                break
            eng.tick()
        eng.preempt(0)
    eng.run(max_ticks=2000)
    assert all(r.done and len(r.out_tokens) == new for r in reqs)
    if scenario == "preempt":
        assert eng.stats.preemptions == 1
    return reqs, eng.stats


def _recorder(rows: dict):
    """A row sampler that records each row it is handed and returns the
    host's argmax of it."""
    def wrap(eng):
        def sample_row(logits, b, req):
            row = np.asarray(logits[b, :eng.cfg.vocab_size], np.float64)
            rows.setdefault(req.rid, []).append(row)
            return int(np.argmax(row))
        return sample_row
    return wrap


@pytest.mark.parametrize("scenario, arch", [
    ("decode", "tinyllama-42m"),
    ("decode", "mamba2-370m"),
    ("prefill", "tinyllama-42m"),
    ("preempt", "tinyllama-42m"),
    ("replicas", "tinyllama-42m"),
    ("picks", "nemotron3-nano-ep8"),
])
def test_device_greedy_serves_the_host_argmax(mesh1, scenario, arch):
    """The greedy engine's tokens (picked on the device) equal the host
    path's, which fetches every row and takes its argmax (an engine at a
    temperature whose row sampler is replaced by that argmax); a row
    sampler replaced on the greedy engine still reads each row's logits,
    the same as the host path's.  The counters say which path chose each
    token."""
    cfg, params = _tiny(arch)
    greedy, hot = SamplerConfig(temperature=0.0), SamplerConfig(temperature=1.0)
    dev, dst = _serve(cfg, params, mesh1, scenario, greedy)
    dev_rows, host_rows = {}, {}
    tap, tst = _serve(cfg, params, mesh1, scenario, greedy,
                      _recorder(dev_rows))
    host, hst = _serve(cfg, params, mesh1, scenario, hot,
                       _recorder(host_rows))
    for d, t, h in zip(dev, tap, host, strict=True):
        assert d.out_tokens == h.out_tokens == t.out_tokens, d.rid
        assert d.expert_picks == h.expert_picks
        assert len(host_rows[h.rid]) == len(h.out_tokens)
        for got, want in zip(dev_rows[d.rid], host_rows[h.rid], strict=True):
            np.testing.assert_array_equal(got, want)
    if scenario == "picks":
        assert all(len(r.expert_picks) == len(r.out_tokens) for r in dev)
    n = sum(len(r.out_tokens) for r in dev)
    assert (dst.device_sampled, dst.host_sampled) == (n, 0)
    assert (tst.device_sampled, tst.host_sampled) == (n, 0)
    assert (hst.device_sampled, hst.host_sampled) == (0, n)
