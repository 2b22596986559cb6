#!/usr/bin/env python
"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell's entry in ``BENCHMARK.json`` names
its configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<mix>.json``); its deployment (slots, token budget,
offered rate, the check's sample and limit) is ``bench/cells/<cell>.json``;
each metric is read by ``bench/metrics/<metric>.py``.  A model family,
named by the configuration's ``reference`` key, is two files: the plain
reference ``bench/reference/<family>.py`` (``weight_spec``, ``hidden``,
``head``; it imports nothing of the program) and the adapter
``bench/families/<family>.py`` (the program's layout of each canonical
weight, the sizes checked against the file, the norms held less one, any
init of its own, and the cost model; ``bench/families/__init__.py`` lists
what it must define).  The configuration's ``serving`` tp x dp is the
cell's chip count.

A run: refuse a device that is not a TPU (or too few chips); make the
weights from the seed; build the paged engine; warm up the cell's shapes
(set-up ends here); serve the mix for a window of ``--seconds`` (with
``--trace 1``, profile a slice of it); read the device's peak memory;
free the engine; compare a sample of the served greedy requests with the
reference; print the numbers compared on standard error and, as the last
line of standard output, one JSON object.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import arith, check, loop, traffic  # noqa: E402
from bench import trace as tracefile  # noqa: E402
from bench.metrics._gaps import gaps_ms  # noqa: E402


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench_file: Path, name: str) -> dict:
    """-> the cell's entry, files and the metrics that apply to it."""
    bench = load_json(bench_file)
    data = bench_file.parent
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in names
                              else [])]
    cfgfile = load_json(data / conf["file"])
    srv = cfgfile["serving"]
    if cell["chips"] != srv["tp"] * srv["dp"]:
        raise SystemExit(f"bench: workload {name} asks for {cell['chips']} "
                         f"chips; its configuration serves on tp={srv['tp']}"
                         f" x dp={srv['dp']}")
    return {"cell": cell, "deploy": load_json(data / "bench" / "cells"
                                              / f"{name}.json"),
            "cfgfile": cfgfile,
            "mix": load_json(data / "bench" / "traffic"
                             / f"{cell['traffic']}.json"),
            "end_to_end": e2e, "per_layer": layer}


def reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Tracer:
    """Profiles one slice of the window into a temporary directory."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.done = False
        self._span = None

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(tracefile.WINDOW)
        self._span.__enter__()

    def stop(self):
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.done = True


def n_jobs_for(mix: dict, deploy: dict, seconds: float) -> int:
    block = int(mix.get("block", 32))
    if mix["loop"] == "open":
        span = float(mix["lead_in_s"]) + seconds + loop.DRAIN_CAP_S
        return int(math.ceil(deploy["rate_rps"] * span)) + block
    return int(mix["outstanding"]) + int(math.ceil(seconds)) * \
        deploy["slots"] + block


class GcClock:
    """Records the host time of every garbage collection, for the log."""

    def __init__(self):
        self.pauses = []            # (start, seconds, generation)
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None:
            self.pauses.append((self._t, now - self._t, info["generation"]))
            self._t = None

    def close(self):
        gc.callbacks.remove(self._on)


def log_window_shape(obs, gcs: GcClock):
    """Log how the window's ticks spread over time, its longest ticks and
    the collections in it: whether a slow run stalled once or all along."""
    w0 = obs.window[0]
    ticks = obs.window_ticks()
    per10 = [0] * max(1, int(math.ceil(obs.window_s / 10)))
    for a, _, _ in ticks:
        per10[min(int((a - w0) // 10), len(per10) - 1)] += 1
    longest = sorted(ticks, key=lambda t: t[0] - t[1])[:8]
    log("ticks per 10 s of the window: " + " ".join(map(str, per10)))
    log("longest ticks (ms at s into the window): " + ", ".join(
        f"{(b - a) * 1e3:.1f}@{a - w0:.1f}" for a, b, _ in longest))
    gaps = gaps_ms(obs)
    log(f"inter-token gaps in the window: {len(gaps)}; p90 p95 p98 p99 "
        f"p99.5 max (ms): " + " ".join(
            f"{loop.percentile(gaps, q) or 0.0:.1f}"
            for q in (90, 95, 98, 99, 99.5, 100)))
    inw = [p for p in gcs.pauses if obs.in_window(p[0])]
    log(f"collections in the window: {len(inw)}, "
        f"{sum(p[1] for p in inw) * 1e3:.3f} ms in all, longest "
        f"{max((p[1] for p in inw), default=0.0) * 1e3:.3f} ms, generation 2: "
        f"{sum(1 for p in inw if p[2] == 2)}")


def warm_up(engine, chunk: int, vocab: int):
    """Serve one request that spans two prefill chunks and three decode
    steps: every compiled step (and eager update) the window will use."""
    import numpy as np

    from repro.serving import Request
    rng = np.random.default_rng(0)
    req = Request(rid=2**31 - 1, prompt=rng.integers(traffic.FIRST_TOKEN_ID, vocab,
                                              chunk + 1).astype(np.int32),
                  max_new_tokens=4)
    engine.submit(req)
    engine.run()


def main(argv=None, require_chip: bool = True) -> int:
    t_main = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark", type=Path, default=ROOT / "BENCHMARK.json",
                    help="the benchmark file (default: the checkout's)")
    args = ap.parse_args(argv)
    r = resolve(args.benchmark, args.workload)
    cell, deploy, cfgfile, mix = r["cell"], r["deploy"], r["cfgfile"], \
        r["mix"]

    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            log(f"bench: the first device is {devs[0].platform!r}, not a "
                f"TPU; refusing to run")
            return 3
        if len(devs) < cell["chips"]:
            log(f"bench: {cell['chips']} chips needed, {len(devs)} found")
            return 3
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = devs[:cell["chips"]]
    peak = arith.peaks(devs[0].device_kind) if require_chip else None
    log(f"devices: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")

    from bench import program
    ref = importlib.import_module(f"bench.reference.{cfgfile['reference']}")
    spec = ref.weight_spec(cfgfile)
    seed32 = traffic.seed32(args.seed, "weights")
    counter = loop.CompileCounter()
    engine = program.build(cfgfile, deploy, mix, spec, seed32,
                           traffic.seed32(args.seed, "engine"))
    vocab_in = int(mix.get("prompt_vocab", cfgfile.get("vocab_size")))
    jobs = traffic.make_jobs(mix, args.seed, n_jobs_for(mix, deploy,
                                                        args.seconds),
                             vocab_in, deploy.get("rate_rps", 0.0))
    greedy = {j.index for j in jobs if j.greedy}
    program.greedy_rows(engine, greedy)
    warm_up(engine, engine.chunk, vocab_in)
    setup_s = time.perf_counter() - t_main
    log(f"set-up {setup_s:.3f} s ({counter.n} lowerings)")

    tracer = Tracer(float(deploy["trace_seconds"])) if args.trace else None
    gcs = GcClock()
    try:
        obs = loop.run(engine, jobs, mix, args.seconds, tracer=tracer,
                       counter=counter,
                       min_finished=deploy["check"]["min_finished"])
    finally:
        gcs.close()
    obs.setup_s, obs.cfgfile = setup_s, cfgfile
    obs.peaks, obs.weight_bytes = peak, arith.weight_bytes(spec)
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in devs]
    engine.params = engine.cache = None
    del engine
    gc.collect()

    if tracer is not None:
        obs.trace = tracefile.reduce(tracefile.load(tracer.dir))
        shutil.rmtree(tracer.dir, ignore_errors=True)

    if mix["loop"] == "open":
        due = [rec for rec in obs.records if obs.in_window(rec.due)]
        attempted, failed = len(due), sum(1 for rec in due
                                          if not rec.token_t)
    else:
        due = [rec for rec in obs.records if rec.submitted < obs.window[1]
               and (rec.done_t < 0 or rec.done_t >= obs.window[0])]
        attempted, failed = len(due), 0
    late = loop.percentile(obs.lateness_s, 99)
    log(f"window {obs.window_s:.3f} s: {len(obs.window_ticks())} ticks, "
        f"{attempted} requests attempted, {failed} failed; compilations in "
        f"the window: {obs.compiles_in_window}; generator lateness p99 "
        f"{(late or 0.0) * 1e3:.3f} ms")
    log_window_shape(obs, gcs)

    metrics = {}
    for m in (r["per_layer"] if args.trace else r["end_to_end"]):
        v = reader(m["name"])(obs)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t_ref = time.perf_counter()
    chk = deploy["check"]
    recs = check.sample(obs, chk, args.seed)
    res = check.compare(ref, cfgfile, spec, seed32, recs,
                        seq_len=deploy["seq_budget"],
                        max_rows=int(mix["output"]["max"]))
    correct, numbers = check.verdict(res, chk)
    log(f"reference check {time.perf_counter() - t_ref:.3f} s over "
        f"{res['requests']} requests, {res['tokens']} served tokens")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(max(mem))}
    out = {"correct": correct, "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if obs.trace is not None:
        device["busy_s"] = obs.trace["busy_s"]
        device["window_s"] = obs.trace["window_s"]
        out["breakdown"] = {"device_ops": obs.trace["device_ops"],
                            "idle_gaps": obs.trace["idle_gaps"]}
    out["check"] = numbers
    for name, n in numbers.items():
        log(f"check {name} {n['value']} limit {n['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
