"""A scratch benchmark made of data files only: a tiny qwen3 cell that the
harness finds by name and runs on the CPU without any edit of its code.
``python bench/tests/cellfiles.py <dir>`` runs it once with the
configuration at tp=2 on two forced host devices and prints its result
line."""
import copy
import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"

BENCHMARK = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 3,
    "configs": [{"name": "qwen3-tiny", "source": "test",
                 "file": "bench/configs/qwen3-tiny.json", "reduced": [],
                 "why": "test"}],
    "workloads": [{"name": "tiny-chat", "config": "qwen3-tiny",
                   "traffic": "tinychat", "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": n, "unit": u, "better": b, "bound": 0.25,
         "source": "host_clock"}
        for n, u, b in (("ttft_p90_ms", "ms", "lower"),
                        ("itl_p50_ms", "ms", "lower"),
                        ("itl_p99_ms", "ms", "lower"),
                        ("output_tokens_per_s", "tokens/s", "higher"),
                        ("setup_s", "s", "lower"))],
    "per_layer": [
        {"name": "ttft_ticks_p90", "unit": "ticks", "better": "lower",
         "source": "program_counter", "layer": "scheduler",
         "moves": "ttft_p90_ms", "workloads": ["tiny-chat"]},
        {"name": "host_ms_per_tick", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "engine host loop",
         "moves": "itl_p50_ms", "workloads": ["tiny-chat"]}],
}
MIX = {"loop": "open", "lead_in_s": 1, "block": 8,
       "prompt": {"median": 40, "sigma": 0.8, "min": 8, "max": 120},
       "output": {"median": 12, "sigma": 0.8, "min": 4, "max": 64},
       "sampling": {"temperature": 0.7, "top_k": 40}, "greedy_every": 2}
# at this size the program's greedy tokens read a gap of 0.0 and the
# float8 control about 0.2 (CPU)
CELL = {"slots": 4, "seq_budget": 256, "rate_rps": 4.0, "trace_seconds": 1,
        "check": {"min_tokens": 60, "max_requests": 4,
                  "min_tokens_required": 10, "min_finished": 2,
                  "limit": 0.05}}


def tiny_config() -> dict:
    return json.loads((DATA / "tiny.json").read_text())


def write(root: Path, config: dict | None = None,
          chips: int | None = None) -> Path:
    """Lay the scratch benchmark out under ``root``, its configuration
    ``config`` (the tiny qwen3 file by default) and its cell on ``chips``
    (the configuration's tp x dp by default); -> its BENCHMARK.json."""
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    config = tiny_config() if config is None else config
    if chips is None:
        chips = config["serving"]["tp"] * config["serving"]["dp"]
    (root / "bench/configs/qwen3-tiny.json").write_text(json.dumps(config))
    (root / "bench/traffic/tinychat.json").write_text(json.dumps(MIX))
    (root / "bench/cells/tiny-chat.json").write_text(json.dumps(CELL))
    bench = copy.deepcopy(BENCHMARK)
    bench["workloads"][0]["chips"] = chips
    path = root / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def argv(bench_file: Path, trace: int = 0, seed: int = 123456789012):
    return ["--workload", "tiny-chat", "--seed", str(seed), "--seconds", "3",
            "--trace", str(trace), "--benchmark", str(bench_file)]


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def main(root: str) -> int:
    """Run the scratch cell once at tp=2 on two host devices."""
    import os
    import sys
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=2")
    top = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(top), str(top / "src")]
    from bench import program, run
    build = program.build

    def logged(*args, **kwargs):
        engine = build(*args, **kwargs)
        print(f"mesh {dict(engine.mesh.shape)} tp {engine.plan.tp}",
              file=sys.stderr)
        return engine

    program.build = logged
    config = tiny_config()
    config["serving"]["tp"] = 2
    return run.main(argv(write(Path(root), config)), require_chip=False)


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1]))
