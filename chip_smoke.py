#!/usr/bin/env python
"""Chip smoke test: paged serving of Qwen3-0.6B on a TPU, checked.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the multi-chip paths, four chips

The default run serves ``qwen3-0.6b`` at its published widths and depth
(28 layers, d_model 1024, 16 query / 8 KV heads of 128, d_ff 3072, vocab
151,936) with seeded random weights, through the serving launcher's own
engine construction (``repro.launch.serve.build_engine`` ->
``ServingEngine.build_paged``): one replica, 8 slots, a 2048-token budget
in 16-token bf16 pages, greedy sampling.  It checks that every request
completes, that every logits row the sampler sees is finite, that the
engine survived no page-transfer failure, and that the first generated
token's logits from the paged chunked-prefill path agree with the
contiguous prefill step (``core.steps.make_prefill_step``, the repo's
oracle) on two prompts.

``--four-chips`` runs only what exists across chips, each against its
one-chip reference:

* paged serving at dp=4 on a ``(data=4, model=1)`` mesh against dp=1 on
  one chip, on the same requests (logits and tokens), with each chip
  shown to hold only its own replica's page pool;
* a forced migration through the compiled page-transfer step: a dp=8
  engine (two replicas per chip) scales down to dp=4 mid-run, so four
  replicas hand their in-flight page runs across chips; its tokens must
  match dp=1 and no transfer failure may be survived;
* the contiguous decode step (``core.steps.make_decode_step``) at tp=4
  against tp=1.

Everything runs in this one process: a chip belongs to one process at a
time.  If the first device is not a TPU, if any phase raises, or if a
check fails, the script exits non-zero and does not print the ``ok``
line.  Otherwise the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed are host wall-clock seconds, compilation included where
said.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ONE_CHIP_ARGS = ["--arch", "qwen3-0.6b", "--slots", "8",
                 "--seq-budget", "2048", "--page-size", "16",
                 "--prefill-chunk", "128", "--kv-dtype", "fp16",
                 "--temperature", "0", "--seed", "0"]
# the four-chip phases check the mesh paths, not capacity: fewer slots and
# a shorter budget keep their compiles and runs short at full width
FOUR_CHIP_ARGS = ["--arch", "qwen3-0.6b", "--slots", "4",
                  "--seq-budget", "1024", "--page-size", "16",
                  "--prefill-chunk", "128", "--kv-dtype", "fp16",
                  "--temperature", "0", "--seed", "0"]

# Logits from two paths that compute the same function in bf16 agree only
# to bf16 rounding.  bf16 keeps 8 significant bits (a relative step of
# 2^-8 = 0.4%), and the paths round at different points: chunk and page
# boundaries, gathered versus contiguous K/V, psum-split matmuls at tp=4.
# Over 28 residual layers these steps accumulate to a few percent of the
# logits' scale, so the bound is 5% of the reference's largest |logit|.
# A wrong page, position or mask moves logits by their own scale (~100%).
LOGIT_RTOL = 0.05


def log(msg: str):
    print(msg, flush=True)


def require_tpu(n_chips: int):
    """-> jax.devices(), or exit non-zero when they are not n TPU chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: the first device is "
                         f"{devs[0].platform!r}, not a TPU; refusing to run")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: {n_chips} chips needed, "
                         f"{len(devs)} found")
    return devs


def device_bytes(devs) -> str:
    """Per-device bytes in use / peak, as the backend reports them."""
    out = []
    for d in devs:
        st = d.memory_stats() or {}
        out.append(f"{d.id}:{st.get('bytes_in_use', 0) / 2**30:.3f}/"
                   f"{st.get('peak_bytes_in_use', 0) / 2**30:.3f}GiB")
    return "device bytes in use/peak " + " ".join(out)


class LogitLog:
    """Wraps an engine's sampler hook: every logits row it samples from is
    checked for finiteness; rows are kept per request id (all of them, or
    only the first — the first generated token's)."""

    def __init__(self, engine, keep_all: bool = False):
        self.vocab = engine.cfg.vocab_size
        self.keep_all = keep_all
        self.rows: dict = {}
        self.n_rows = 0
        self.n_nonfinite = 0
        sample = engine._sample_row

        def sample_row(logits, b, req):
            row = logits[b, :self.vocab]
            self.n_rows += 1
            self.n_nonfinite += int(not np.isfinite(row).all())
            if keep_all or req.rid not in self.rows:
                self.rows.setdefault(req.rid, []).append(row.copy())
            return sample(logits, b, req)

        engine._sample_row = sample_row


def make_requests(vocab: int, n: int, prompt_lens, new_tokens, seed=0,
                  rid0=0):
    from repro.serving import Request
    rng = np.random.RandomState(seed)
    return [Request(rid=rid0 + i,
                    prompt=rng.randint(2, vocab, int(rng.randint(
                        *prompt_lens))).astype(np.int32),
                    max_new_tokens=int(rng.randint(*new_tokens)))
            for i in range(n)]


def clone(reqs):
    from repro.serving import Request
    return [Request(rid=r.rid, prompt=r.prompt.copy(),
                    max_new_tokens=r.max_new_tokens) for r in reqs]


def serve_all(engine, reqs, max_ticks=100_000) -> float:
    """Submit, run to completion; -> host seconds."""
    t0 = time.perf_counter()
    for r in reqs:
        engine.submit(r)
    engine.run(max_ticks=max_ticks)
    return time.perf_counter() - t0


def completed(reqs) -> bool:
    return all(r.done and 1 <= len(r.out_tokens) <= r.max_new_tokens
               for r in reqs)


def rel_diff(ref, got) -> float:
    ref = np.asarray(ref, np.float64)
    got = np.asarray(got, np.float64)
    return float(np.abs(ref - got).max() / max(np.abs(ref).max(), 1e-30))


def contiguous_first_logits(engine, prompts):
    """The contiguous prefill step's last-position logits per prompt."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.core import steps
    cfg, plan, mesh = engine.cfg, engine.plan, engine.mesh
    fn, _, _ = steps.make_prefill_step(
        cfg, plan, mesh, ShapeConfig("oracle", "decode", engine.S, 1))
    fn = jax.jit(fn)
    out = []
    for p in prompts:
        cache = steps.zero_cache_for(cfg, plan, mesh, 1, engine.S)
        with mesh:
            logits, _ = fn(engine.params, jnp.asarray(p[None]), cache)
        out.append(np.asarray(jax.device_get(logits),
                              np.float32)[0, :cfg.vocab_size])
    return out


def describe(cfg) -> str:
    return (f"model {cfg.name}: n_layers={cfg.n_layers} "
            f"d_model={cfg.d_model} n_heads={cfg.n_heads} "
            f"n_kv_heads={cfg.n_kv_heads} head_dim={cfg.head_dim_} "
            f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype}")


def one_chip_phase(serve_argv, n_requests=8, prompt_lens=(128, 513),
                   new_tokens=(32, 65), n_oracle=2) -> bool:
    """Serve through the launcher's engine on one device and check it."""
    from repro.launch import serve
    args = serve.parse_args(serve_argv)
    t0 = time.perf_counter()
    engine = serve.build_engine(args)
    cfg = engine.cfg
    log(describe(cfg))
    log(f"engine: paged dp={engine.R} slots={engine.Bp} "
        f"seq_budget={engine.S} page_size={engine.page_size} "
        f"n_pages={engine.n_pool_pages} prefill_chunk={engine.chunk} "
        f"kv_dtype={engine.plan.kv_cache_dtype}")
    log(f"host time: engine build incl. parameter init "
        f"{time.perf_counter() - t0:.2f} s")
    rec = LogitLog(engine)
    devs = list(engine.mesh.devices.flat)

    # one request that crosses a chunk boundary compiles the prefill-chunk
    # and decode steps; its wall time is the compile cost
    warm = make_requests(cfg.vocab_size, 1, (engine.chunk + 1,
                                             engine.chunk + 2), (2, 3),
                         seed=99, rid0=10_000)
    t_warm = serve_all(engine, warm)
    log(f"host time: warm-up request incl. compiles {t_warm:.2f} s")
    log("after warm-up: " + device_bytes(devs))

    reqs = make_requests(cfg.vocab_size, n_requests, prompt_lens,
                         new_tokens)
    t_run = serve_all(engine, reqs)
    st = engine.stats
    n_tok = sum(len(r.out_tokens) for r in reqs)
    log(f"served requests={len(reqs)} prompt_tokens="
        f"{sum(len(r.prompt) for r in reqs)} new_tokens={n_tok} "
        f"ticks={st.ticks} prefills={st.prefills}; "
        f"host time {t_run:.2f} s")
    log("after serving: " + device_bytes(devs))

    t0 = time.perf_counter()
    refs = contiguous_first_logits(engine, [r.prompt for r in
                                            reqs[:n_oracle]])
    log(f"host time: contiguous prefill oracle incl. compiles "
        f"{time.perf_counter() - t0:.2f} s")
    diffs = []
    for r, ref in zip(reqs, refs):
        got = rec.rows[r.rid][0]
        diffs.append(rel_diff(ref, got))
        log(f"rid {r.rid} prompt_len={len(r.prompt)}: paged vs contiguous "
            f"first-token logits max|diff|="
            f"{np.abs(ref - got).max():.6f} max|ref|={np.abs(ref).max():.4f}"
            f" rel={diffs[-1]:.6f} (bound {LOGIT_RTOL}) argmax "
            f"{'same' if int(ref.argmax()) == int(got.argmax()) else 'differs'}")

    checks = {
        "all requests completed": completed(reqs + warm),
        "every logits row finite": rec.n_nonfinite == 0
        and rec.n_rows == n_tok + sum(len(r.out_tokens) for r in warm),
        "no page-transfer failure survived": st.transfer_failures == 0,
        "paged logits match contiguous prefill":
            len(diffs) == n_oracle and max(diffs) <= LOGIT_RTOL,
    }
    for name, ok in checks.items():
        log(f"check {'ok  ' if ok else 'FAIL'} {name}")
    return all(checks.values())


def compare_runs(ref_reqs, ref_rec, reqs, rec):
    """-> (requests with identical tokens, max relative logit difference
    over the rows where both runs had emitted the same tokens so far)."""
    same, worst = 0, 0.0
    for a, b in zip(ref_reqs, reqs):
        same += a.out_tokens == b.out_tokens
        ra, rb = ref_rec.rows[a.rid], rec.rows[b.rid]
        for i, (x, y) in enumerate(zip(ra, rb)):
            worst = max(worst, rel_diff(x, y))
            if a.out_tokens[i:i + 1] != b.out_tokens[i:i + 1]:
                break
    return same, worst


def dp_phase(serve_argv, n_requests=16, prompt_lens=(64, 257),
             new_tokens=(16, 33)) -> dict:
    """dp=4 vs dp=1 paged serving, then a dp=8 -> dp=4 migration."""
    import jax

    from repro.launch import serve
    from repro.serving import ServingEngine

    args1 = serve.parse_args(serve_argv + ["--dp", "1"])
    e1 = serve.build_engine(args1)
    cfg = e1.cfg
    log(describe(cfg))
    reqs = make_requests(cfg.vocab_size, n_requests, prompt_lens,
                         new_tokens)
    ref_reqs = clone(reqs)
    ref_rec = LogitLog(e1, keep_all=True)
    t1 = serve_all(e1, ref_reqs)
    log(f"dp=1 on device {e1.mesh.devices.flat[0].id}: requests="
        f"{len(ref_reqs)} new_tokens="
        f"{sum(len(r.out_tokens) for r in ref_reqs)}; host time incl. "
        f"compiles {t1:.2f} s")
    del e1
    jax.clear_caches()

    e4 = serve.build_engine(serve.parse_args(serve_argv + ["--dp", "4"]))
    devs = list(e4.mesh.devices.flat)
    log(f"dp=4 mesh {dict(e4.mesh.shape)} over devices "
        f"{[d.id for d in devs]}")
    own_pool = all(
        len({s.device for s in leaf.addressable_shards}) == len(devs)
        and all(s.data.shape[1] * len(devs) == leaf.shape[1]
                for s in leaf.addressable_shards)
        for leaf in jax.tree_util.tree_leaves(e4.cache))
    log(f"dp=4 page pools: each of {len(devs)} devices holds one "
        f"replica's rows: {own_pool}")
    log("dp=4 after build: " + device_bytes(devs))
    dp_reqs = clone(reqs)
    rec4 = LogitLog(e4, keep_all=True)
    t4 = serve_all(e4, dp_reqs)
    log("dp=4 after serving: " + device_bytes(devs))
    same4, worst4 = compare_runs(ref_reqs, ref_rec, dp_reqs, rec4)
    log(f"dp=4 vs dp=1: identical tokens {same4}/{len(reqs)} requests, "
        f"max rel logit diff {worst4:.6f} (bound {LOGIT_RTOL}); "
        f"routed per replica {[rs.routed for rs in e4.stats.replicas]}; "
        f"host time incl. compiles {t4:.2f} s")

    # two replicas per chip; scaling to four moves replicas 4..7 (chips
    # 2, 3) onto replicas 0..3 (chips 0, 1) through the transfer step
    e8 = ServingEngine.build_paged(
        cfg, e4.plan, e4.mesh, e4.Bp, e4.S, e4.params,
        page_size=e4.page_size, prefill_chunk=e4.chunk,
        sampler=e4.sampler, rng_seed=e4.rng_seed, dp=8)
    del e4
    mig_reqs = clone(reqs)
    rec8 = LogitLog(e8, keep_all=True)
    for r in mig_reqs:
        e8.submit(r)
    for _ in range(64):
        e8.tick()
        if sum(e8.slot_state[b] == "decode"
               for b in range(4 * e8.Bp, 8 * e8.Bp)) >= 4:
            break
    in_flight = sum(a is not None for a in e8.admissions[4 * e8.Bp:])
    t_scale = e8.stats.ticks
    e8.scale_to(4)
    e8.run(max_ticks=100_000)
    st = e8.stats
    same8, worst8 = compare_runs(ref_reqs, ref_rec, mig_reqs, rec8)
    log(f"dp=8 -> dp=4 at tick {t_scale}: in-flight "
        f"on leaving replicas {in_flight}, migrations={st.migrations} "
        f"migrated_pages={st.migrated_pages} "
        f"transfer_failures={st.transfer_failures}; identical tokens "
        f"{same8}/{len(reqs)}, max rel logit diff {worst8:.6f}")
    checks = {
        "dp=1, dp=4 and migration runs completed":
            completed(ref_reqs) and completed(dp_reqs)
            and completed(mig_reqs),
        "every logits row finite":
            ref_rec.n_nonfinite == rec4.n_nonfinite == rec8.n_nonfinite == 0,
        "dp=4 pools: one replica per device": own_pool,
        "dp=4 logits match dp=1": worst4 <= LOGIT_RTOL,
        "migration moved pages across chips":
            st.migrations > 0 and st.migrated_pages > 0,
        "no page-transfer failure survived": st.transfer_failures == 0,
        "migrated run logits match dp=1": worst8 <= LOGIT_RTOL,
    }
    del e8
    jax.clear_caches()
    return checks


def tp_phase(serve_argv, tp=4, batch=4, budget=256, n_steps=4) -> dict:
    """The contiguous decode step at tp vs tp=1, token by token."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.core import model, steps
    from repro.core.partition import ShardingPlan
    from repro.launch import serve
    from repro.launch.mesh import host_mesh

    args = serve.parse_args(serve_argv)
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    shape = ShapeConfig("tp", "decode", budget, batch)
    rng = np.random.RandomState(args.seed)
    tokens = rng.randint(2, cfg.vocab_size, (batch, n_steps)).astype(np.int32)
    out = {}
    for t in (1, tp):
        mesh = host_mesh(tp=t, dp=1)
        plan = ShardingPlan(tp=t)
        params = model.init_params(cfg, plan, seed=args.seed, mesh=mesh)
        dec, _, _ = steps.make_decode_step(cfg, plan, mesh, shape)
        dec = jax.jit(dec, donate_argnums=(1,))
        cache = steps.zero_cache_for(cfg, plan, mesh, batch, budget)
        rows = []
        with mesh:
            for i in range(n_steps):
                lg, cache = dec(params, cache, jnp.asarray(tokens[:, i:i + 1]),
                                jnp.full((batch,), i, jnp.int32))
                rows.append(np.asarray(jax.device_get(lg),
                                       np.float32)[:, :cfg.vocab_size])
        if t == tp:
            log(f"tp={tp} mesh {dict(mesh.shape)}: "
                + device_bytes(list(mesh.devices.flat)))
        out[t] = np.stack(rows)
        del params, cache
    worst = rel_diff(out[1], out[tp])
    finite = bool(np.isfinite(out[1]).all() and np.isfinite(out[tp]).all())
    log(f"tp={tp} vs tp=1 decode logits over {n_steps} steps x {batch} rows: "
        f"max|diff|={np.abs(out[1] - out[tp]).max():.6f} "
        f"max|ref|={np.abs(out[1]).max():.4f} rel={worst:.6f} "
        f"(bound {LOGIT_RTOL}); argmax agreement "
        f"{float((out[1].argmax(-1) == out[tp].argmax(-1)).mean()):.3f}")
    jax.clear_caches()
    return {f"tp={tp} logits finite": finite,
            f"tp={tp} logits match tp=1": worst <= LOGIT_RTOL}


def four_chip_phase(serve_argv) -> bool:
    checks = dp_phase(serve_argv)
    checks.update(tp_phase(serve_argv))
    for name, ok in checks.items():
        log(f"check {'ok  ' if ok else 'FAIL'} {name}")
    return all(checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Serve qwen3-0.6b on a TPU through the paged engine "
                    "and check the result.")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip phases (dp=4 and a "
                         "cross-chip migration vs dp=1, tp=4 vs tp=1) on "
                         "a four-chip host")
    args = ap.parse_args(argv)
    devs = require_tpu(4 if args.four_chips else 1)
    log(f"devices: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    ok = four_chip_phase(FOUR_CHIP_ARGS) if args.four_chips \
        else one_chip_phase(ONE_CHIP_ARGS)
    log(f"host time: total {time.perf_counter() - t0:.2f} s")
    if not ok:
        log("chip_smoke: FAILED")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
