"""The trace reduction on a small trace recorded on a v5e chip: a quarter
second of qwen3-chat ticks, with the harness's host spans."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "qwen3-chat.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(str(RECORDED)))


def test_busy_and_window(reduced):
    assert reduced["n_devices"] == 1
    assert reduced["window_s"] == pytest.approx(0.265701735, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.246990873, rel=1e-9)
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_top_device_ops(reduced):
    ops = reduced["device_ops"]
    assert len(ops) == 10
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    # the paged decode step's layer loop leads; names are HLO op names
    assert ops[0][0] == "while.4"
    assert ops[0][1] == pytest.approx(0.156371122, rel=1e-9)
    assert all(" " not in name and not name.startswith("%")
               for name, _ in ops)


def test_idle_gaps_named_by_host_span(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 10
    assert [g for _, g in gaps] == sorted((g for _, g in gaps), reverse=True)
    assert {name for name, _ in gaps} <= set(trace.HOST_SPANS) | {"idle"}
    assert gaps[0] == ["tick", pytest.approx(0.006567437, rel=1e-9)]
    # busy plus every gap cannot exceed the slice
    assert reduced["busy_s"] + sum(g for _, g in gaps) <= \
        reduced["window_s"] + 1e-9


def test_op_name():
    assert trace.op_name("%while.4 = (s32[]) while(...)") == "while.4"
    assert trace.op_name("fusion.3") == "fusion.3"
