"""The reduction by the program's own names (``bench/phases.py``) on two
traces recorded on a v5e chip, and the queue-wait reader on a synthetic
observation.

``qwen3-chat.xplane.pb.gz`` comes from a program whose compiled steps were
all ``jit_per_shard`` and which opened no span of its own;
``qwen3-chat-named.xplane.pb.gz`` holds two qwen3-chat ticks of the named
steps and engine spans, one slot decoding while another prefills."""
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import loop, phases, trace, traffic  # noqa: E402

DATA = ROOT / "bench" / "testdata"


@pytest.fixture(scope="module")
def old():
    pd = trace.load(str(DATA / "qwen3-chat.xplane.pb.gz"))
    return trace.reduce(pd), phases.reduce(pd)


def test_unnamed_program_has_no_step_and_no_phase(old):
    base, red = old
    assert set(red["modules"]) == {"jit_per_shard"}
    assert phases.step_ms(red, "jit_paged_decode") is None
    assert phases.step_ms(red, "jit_prefill_chunk") is None
    assert set(red["idle_by_phase"]) <= {phases.TICK_OTHER, phases.IDLE}
    # every idle second is put down to one phase, once
    assert sum(red["idle_by_phase"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    # the ten longest gaps are the harness's, named the same way
    assert [g for _, g in red["idle_gaps_by_phase"]] == pytest.approx(
        [g for _, g in base["idle_gaps"]], rel=1e-12)
    assert {n for n, _ in red["idle_gaps_by_phase"]} == {phases.TICK_OTHER}


@pytest.fixture(scope="module")
def named():
    pd = trace.load(str(DATA / "qwen3-chat-named.xplane.pb.gz"))
    return trace.reduce(pd), phases.reduce(pd)


def test_named_steps_and_their_device_times(named):
    _, red = named
    mods = red["modules"]
    assert set(mods) == {"jit_paged_decode", "jit_prefill_chunk"}
    # the chunk still running when the profiler stopped is cut short in
    # the record and left out
    assert len(mods["jit_paged_decode"]) == 2
    assert len(mods["jit_prefill_chunk"]) == 1
    assert phases.step_ms(red, "jit_paged_decode") == pytest.approx(
        95.7025865, rel=1e-9)
    assert phases.step_ms(red, "jit_prefill_chunk") == pytest.approx(
        48.974903, rel=1e-9)
    assert phases.step_ms(red, "jit_verify") is None


def test_idle_time_by_engine_phase(named):
    base, red = named
    by = red["idle_by_phase"]
    assert sum(by.values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    assert list(by) == ["engine.wait", "engine.dispatch", "engine.sample",
                        phases.TICK_OTHER, phases.IDLE]
    assert by["engine.wait"] == pytest.approx(0.008662008, rel=1e-6)
    assert by["engine.dispatch"] == pytest.approx(0.004330562, rel=1e-6)
    assert by["engine.sample"] == pytest.approx(0.00188362, rel=1e-6)
    named_share = 1 - (by[phases.TICK_OTHER] + by[phases.IDLE]) / \
        sum(by.values())
    assert named_share > 0.95
    gaps = red["idle_gaps_by_phase"]
    assert [g for _, g in gaps] == pytest.approx(
        [g for _, g in base["idle_gaps"]], rel=1e-12)
    assert gaps[0] == ["engine.wait", pytest.approx(0.006685284, rel=1e-9)]
    assert {n for n, _ in gaps} <= set(phases.PHASE_SPANS) | \
        {phases.TICK_OTHER, phases.IDLE}


def test_innermost_span_takes_the_time():
    ms = 1_000_000
    spans = [(0, 100 * ms, "tick"), (10 * ms, 40 * ms, "engine.sample"),
             (50 * ms, 60 * ms, "engine.wait"), (120 * ms, 130 * ms, "submit")]
    cuts, names = phases._segments(0, 150 * ms, spans)
    got = phases._phase_times(5 * ms, 125 * ms, cuts, names)
    assert got == pytest.approx({"tick-other": 0.055, "engine.sample": 0.03,
                                 "engine.wait": 0.01, "idle": 0.02,
                                 "submit": 0.005})
    assert phases.module_name("jit_paged_decode(123)") == "jit_paged_decode"


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _rec(due, t_submit, t_admitted=None, stamped=True):
    job = traffic.Job(index=0, prompt=np.zeros(4, np.int32),
                      max_new_tokens=1, greedy=True)
    req = SimpleNamespace(t_submit=t_submit)
    if stamped:
        req.t_admitted = t_admitted
    return loop.Record(job=job, req=req, due=due)


def _obs(records):
    return loop.Observation(records=records, window=(10.0, 20.0), ticks=[],
                            collect_wait_s=0.0, slots=4, chunk=8,
                            lateness_s=[], waiting=[])


def test_queue_wait_reader():
    read = _reader("queue_wait_ms_p90")
    recs = [_rec(10.0 + i, 10.0 + i, 10.0 + i + 0.001 * i)
            for i in range(10)]
    recs += [_rec(5.0, 5.0, 9.0),          # due before the window
             _rec(12.0, 12.0, None)]        # never admitted
    waits = [1.0 * i for i in range(10)]
    assert read(_obs(recs)) == pytest.approx(np.percentile(waits, 90))
    # a program that stamps no admission reads nothing
    assert read(_obs([_rec(11.0, 11.0, stamped=False)])) is None
