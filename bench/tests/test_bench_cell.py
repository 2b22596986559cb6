"""A cell defined only by data files is found and run end to end at a
tiny size on the CPU, and the harness refuses a device that is not a TPU."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import cellfiles  # noqa: E402


def test_scratch_cell_runs_from_data_files_alone(tmp_path, capsys):
    from bench import run
    bench_file = cellfiles.write(tmp_path)
    assert run.main(cellfiles.argv(bench_file), require_chip=False) == 0
    out = cellfiles.last_json(capsys.readouterr().out)
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p50_ms", "itl_p99_ms",
                                   "output_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "check"
    assert out["check"]["served_gap"]["value"] <= \
        out["check"]["served_gap"]["limit"]
    assert out["device"]["count"] == 1


def test_refuses_a_device_that_is_not_a_tpu(tmp_path, capsys):
    from bench import run
    bench_file = cellfiles.write(tmp_path)
    assert run.main(cellfiles.argv(bench_file)) != 0
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "not a TPU" in cap.err
