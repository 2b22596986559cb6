#!/usr/bin/env python
"""Readings that the check's limit is set from, and the knee sweep.

    python bench/calibrate.py check --workload <cell> --seeds 1,2,3 --seconds 20
    python bench/calibrate.py sweep --workload <cell> --rates 1,2,3 --seconds 30
    python bench/calibrate.py sampling --workload <cell>

``check``: for each seed, in one process, build the cell's engine with
that seed's weights, serve the mix at the cell's own load for a short
window, and compare a sample of the served greedy requests with the
reference (the lower reading, ``served_gap``) and with the control, the
reference rounded to float8 in the program's place (the upper reading,
``control_gap``).  Each is judged by the run's own ``check.verdict``
against the cell's limit: ``correct`` for the program, which has to read
true, and ``control_correct`` for the control, which has to read false.
``--also key=value`` adds ``served_gap_also``: the same served tokens
against the reference built from the configuration with that key changed
(the published precision, where the file states another).  One JSON line
per seed.

``sampling``: the host time of the program's sampler for one row of the
configuration's vocabulary, greedy and at the mix's temperature and top-k.

``sweep``: one engine serves the open-loop mix at each rate in turn; for
each, the line gives the growth of the admitted-but-waiting queue over the
window (slope, requests per second, by least squares), the queue at the
window's end, and the tail metrics.  The knee is the highest rate whose
queue does not grow.

Both refuse to run on anything but a TPU, like ``bench/run.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import check, loop, program, run, traffic  # noqa: E402


def _setup(args, require_chip=True):
    r = run.resolve(args.benchmark, args.workload)
    import jax
    dev = jax.devices()[0]
    if require_chip:
        if dev.platform != "tpu":
            raise SystemExit(f"calibrate: {dev.platform!r} is not a TPU")
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    ref = importlib.import_module(
        f"bench.reference.{r['cfgfile']['reference']}")
    return r, ref, ref.weight_spec(r["cfgfile"])


def _engine(r, spec, seed):
    engine = program.build(r["cfgfile"], r["deploy"], r["mix"], spec,
                           traffic.seed32(seed, "weights"),
                           traffic.seed32(seed, "engine"))
    vocab = int(r["cfgfile"]["vocab_size"])
    run.warm_up(engine, engine.chunk, vocab)
    return engine, vocab


def cmd_check(args, require_chip=True):
    r, ref, spec = _setup(args, require_chip)
    mix, deploy = r["mix"], r["deploy"]
    for seed in args.seeds:
        t0 = time.perf_counter()
        engine, vocab = _engine(r, spec, seed)
        jobs = traffic.make_jobs(mix, seed, run.n_jobs_for(
            mix, deploy, args.seconds), vocab, deploy.get("rate_rps", 0.0))
        program.greedy_rows(engine, {j.index for j in jobs if j.greedy})
        obs = loop.run(engine, jobs, mix, args.seconds,
                       min_finished=deploy["check"]["min_finished"])
        del engine
        gc.collect()
        recs = check.sample(obs, deploy["check"], seed)
        cmp = functools.partial(check.compare, ref, spec=spec,
                                seed32=traffic.seed32(seed, "weights"),
                                recs=recs, seq_len=deploy["seq_budget"],
                                max_rows=int(mix["output"]["max"]))
        res = cmp(cfgfile=r["cfgfile"], control=True)
        res["correct"], _ = check.verdict(res, deploy["check"])
        res["control_correct"], _ = check.verdict(res, deploy["check"],
                                                  "control_gap")
        res["limit"] = deploy["check"]["limit"]
        if args.also:
            key, val = args.also.split("=", 1)
            res["served_gap_also"] = cmp(
                cfgfile=dict(r["cfgfile"], **{key: json.loads(val)})
            )["served_gap"]
            res["also"] = args.also
        res.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(res), flush=True)


def cmd_sweep(args, require_chip=True):
    r, ref, spec = _setup(args, require_chip)
    mix, deploy = r["mix"], r["deploy"]
    engine, vocab = _engine(r, spec, args.seeds[0])
    for k, rate in enumerate(args.rates):
        jobs = traffic.make_jobs(mix, args.seeds[0], run.n_jobs_for(
            mix, dict(deploy, rate_rps=rate), args.seconds), vocab, rate)
        jobs = [dataclasses.replace(j, index=j.index + 1_000_000 * (k + 1))
                for j in jobs]
        program.greedy_rows(engine, {j.index for j in jobs if j.greedy})
        obs = loop.run(engine, jobs, mix, args.seconds)
        w = [(t, n) for t, n in obs.waiting if obs.in_window(t)]
        ts = [t - obs.window[0] for t, _ in w]
        ns = [n for _, n in w]
        mt, mn = sum(ts) / len(ts), sum(ns) / len(ns)
        slope = sum((t - mt) * (n - mn) for t, n in zip(ts, ns, strict=True)) \
            / max(sum((t - mt) ** 2 for t in ts), 1e-9)
        out = {"rate_rps": rate, "queue_slope_per_s": slope,
               "queue_end": ns[-1], "queue_max": max(ns)}
        for name in ("ttft_p90_ms", "itl_p50_ms", "itl_p99_ms",
                     "output_tokens_per_s"):
            out[name] = run.reader(name)(obs)
        print(json.dumps(out), flush=True)
        # start the next rate empty: abort what is in flight (its pages go
        # back to the pool) and drop what still waits
        engine._barrier()
        engine.drain()
        for sched in engine.scheds:
            sched.queue.clear()


def cmd_sampling(args, require_chip=True):
    import numpy as np

    from repro.serving.sampler import SamplerConfig, sample_from_logits
    r, _, _ = _setup(args, require_chip)
    cfg = program.model_config(r["cfgfile"])
    samp = r["mix"].get("sampling", {})
    rng = np.random.RandomState(0)
    logits = np.random.default_rng(0).normal(
        size=(8, cfg.vocab_size)).astype(np.float32)
    for name, sc in (("greedy", SamplerConfig(temperature=0.0)),
                     ("mix", SamplerConfig(
                         temperature=float(samp.get("temperature", 0.0)),
                         top_k=int(samp.get("top_k", 0))))):
        n, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < 2.0:
            sample_from_logits(logits[n % 8:n % 8 + 1], sc, cfg.vocab_size,
                               rng)
            n += 1
        print(json.dumps({"sampler": name, "vocab": cfg.vocab_size,
                          "ms_per_row": (time.perf_counter() - t0) / n * 1e3,
                          "rows": n}), flush=True)


def main(argv=None, require_chip=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("check", "sweep", "sampling"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1",
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--rates", default="1",
                    type=lambda s: [float(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--also", default="",
                    help="check: key=value (JSON) of the configuration for "
                         "a second reading of the served tokens")
    ap.add_argument("--benchmark", type=Path, default=ROOT / "BENCHMARK.json")
    args = ap.parse_args(argv)
    {"check": cmd_check, "sweep": cmd_sweep,
     "sampling": cmd_sampling}[args.what](args, require_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
