"""Serving launcher: batched requests through the ServingEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-42m --smoke \
        --requests 16 --slots 4 --max-new 16

Builds the prefill/decode steps for a host mesh, spins up the
continuous-batching engine, pushes synthetic requests, and reports
TTFT / per-token latency / throughput.

``--arch`` accepts any registered decode-capable config
(``repro.configs``), including hybrid/SSM archs (e.g. ``hymba-1.5b``,
``mamba2-370m`` — paged serving gives them per-request recurrent-state
slabs) and encoder-decoder archs (e.g. ``seamless-m4t-large-v2`` — the
launcher synthesizes encoder frame embeddings per request;
``--frame-groups K`` spreads requests over K distinct frame tensors so
the cross-KV cache's shared-encode path is exercised).  Vision-frontend
archs are not servable paged and fail with a precise error.
``--prefix-cache`` is attention-only-decoder territory: SSM state is not
addressable by token-id prefixes and enc-dec self-KV depends on the
frames, so the engine rejects those combinations (cross-KV sharing for
enc-dec is automatic instead).

Scheduling policy is selected with ``--policy {fcfs,priority,fair}``;
``--policy priority --preemption`` additionally evicts low-priority slots
when urgent requests arrive, and ``--policy fair --preemption`` enables
preemptive DRR (paged engine only; see README §Serving).
``--high-priority-every N`` marks every Nth request urgent and the report
then splits TTFT per class; ``--clients N`` spreads requests across N
client ids for the fair policy.

``--speculative K`` (paged engine, attention-only archs) turns on
speculative decoding: prompt-lookup self-drafts of up to K tokens are
verified in one fused K+1-position step per tick, outputs stay
token-identical to the one-token path, and the report adds
accepted-tokens/tick and the draft hit rate.

``--dp N`` (paged engine only) runs N data-parallel replicas, each with
``--slots`` slots and its own replica-local page pool / prefix cache /
scheduler; a router assigns requests by prefix affinity then page load,
and the report splits stats per replica.  Replicas shard over the mesh's
data axis when enough devices exist (they co-locate otherwise).

``--disagg P:D`` (requires ``--dp`` with P + D replicas) disaggregates
prefill from decode: P replicas chunk-prefill fresh requests and hand
each finished KV page run to one of D decode replicas through the
compiled page-transfer step, so long prefills never steal decode ticks
(README §Disaggregated serving).  The paged engine plans one tick ahead
by default (``--overlap``); ``--no-overlap`` restores the serial
plan-dispatch-collect loop for debugging — outputs are token-identical
either way, and the report adds the device-busy fraction plus plan-ahead
/ invalidation counts.

``--scale-events T:N[,T:N...]`` (paged engine, no ``--disagg``) replays
an elastic membership schedule under the live load: at tick T the engine
scales to N replicas (``scale_to`` — leaving replicas drain by migrating
their in-flight KV page runs to survivors), and a ``T:kill:R`` entry
instead injects a replica-R failure (``kill_replica`` — its requests
re-admit elsewhere as re-prefills).  Outputs stay token-identical to an
undisturbed run; the report adds migration / recovery counters
(README §Elastic serving).
"""
from __future__ import annotations

import argparse
import functools
import sys
import time

import numpy as np


def parse_args(argv=None):
    """-> the parsed command line, validated; ``disagg`` becomes a
    ``(P, D)`` tuple (or None) and ``scale_events`` a sorted list of
    ``(tick, "scale" | "kill", value)``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help="registered config id (repro.configs): dense/MoE "
                         "decoders, hybrid/SSM (paged: recurrent-state "
                         "slabs), enc-dec (paged: cross-KV pages + "
                         "shared-frame encode reuse)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seq-budget", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel replicas with replica-local page "
                         "pools and a prefix-affinity router (implies "
                         "--paged)")
    ap.add_argument("--disagg", default=None, metavar="P:D",
                    help="disaggregate prefill from decode: P prefill "
                         "replicas hand finished page runs to D decode "
                         "replicas via the compiled page-transfer step "
                         "(requires --dp P+D)")
    ap.add_argument("--scale-events", default=None, metavar="T:N[,T:N...]",
                    help="elastic membership schedule (paged engine, no "
                         "--disagg): at tick T scale to N replicas; a "
                         "'T:kill:R' entry injects a replica-R failure "
                         "instead (e.g. 8:1,12:kill:0,16:2)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="plan tick t+1 while tick t's steps run on device "
                         "(paged engine; --no-overlap restores the serial "
                         "loop, token-identical either way)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV-cache + chunked prefill (README §Serving)")
    ap.add_argument("--kv-dtype", choices=("fp32", "fp16", "int8"),
                    default="fp16",
                    help="paged-pool storage dtype; int8 quantizes every "
                         "state pool (self-KV, cross-KV, SSM slabs) with "
                         "per-page scales at ~half the fp16 bytes "
                         "(README §Quantized KV cache)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--n-pages", type=int, default=0,
                    help="page pool size (0 = full occupancy + scratch)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix sharing with copy-on-write pages "
                         "(implies --paged)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="speculative decoding: prompt-lookup self-drafts "
                         "of up to K tokens verified in one fused step "
                         "(attention-only archs; implies --paged; outputs "
                         "stay token-identical)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common system-prompt prefix of this "
                         "many tokens to every request")
    ap.add_argument("--frame-groups", type=int, default=1, metavar="K",
                    help="enc-dec archs: spread requests over K distinct "
                         "synthetic frame tensors (requests in a group "
                         "share one encode's cross-KV pages)")
    ap.add_argument("--policy", choices=("fcfs", "priority", "fair"),
                    default="fcfs", help="admission policy (serving.policies)")
    ap.add_argument("--preemption", action="store_true",
                    help="evict low-priority slots for urgent arrivals "
                         "(requires --policy priority and a paged engine)")
    ap.add_argument("--high-priority-every", type=int, default=0,
                    metavar="N", help="every Nth request gets priority 10 "
                                      "(0 = uniform priority)")
    ap.add_argument("--clients", type=int, default=1,
                    help="spread requests over N client ids (fair policy)")
    args = ap.parse_args(argv)
    if args.shared_prefix + args.prompt_len + args.max_new > args.seq_budget:
        ap.error("--shared-prefix + --prompt-len + --max-new must fit "
                 "--seq-budget")
    if args.preemption and args.policy not in ("priority", "fair"):
        ap.error("--preemption requires --policy priority or fair")
    if args.preemption and not (args.paged or args.prefix_cache
                                or args.dp > 1):
        ap.error("--preemption requires the paged engine (--paged)")
    if args.dp < 1:
        ap.error("--dp must be >= 1")
    if args.speculative < 0:
        ap.error("--speculative must be >= 0")
    disagg = None
    if args.disagg is not None:
        try:
            p, d = (int(x) for x in args.disagg.split(":"))
        except ValueError:
            ap.error("--disagg expects P:D (e.g. --disagg 1:1)")
        if p < 1 or d < 1 or p + d != args.dp:
            ap.error(f"--disagg {args.disagg} needs --dp {p + d} "
                     f"(P + D replicas, both >= 1)")
        disagg = (p, d)
    scale_events = []
    if args.scale_events:
        if not (args.paged or args.prefix_cache or args.dp > 1
                or args.speculative):
            ap.error("--scale-events requires the paged engine (--paged)")
        if disagg is not None:
            ap.error("--scale-events cannot combine with --disagg "
                     "(role sets are static)")
        for part in args.scale_events.split(","):
            bits = part.split(":")
            try:
                if len(bits) == 2:
                    scale_events.append((int(bits[0]), "scale",
                                         int(bits[1])))
                elif len(bits) == 3 and bits[1] == "kill":
                    scale_events.append((int(bits[0]), "kill",
                                         int(bits[2])))
                else:
                    raise ValueError(part)
            except ValueError:
                ap.error("--scale-events expects comma-separated T:N or "
                         "T:kill:R entries")
        scale_events.sort()
    args.disagg, args.scale_events = disagg, scale_events
    return args


def build_engine(args):
    """-> the ServingEngine the command line describes: config, sharding
    plan, mesh over the local devices, seeded parameters placed on that
    mesh, scheduler and sampler.  ``main`` and ``chip_smoke.py`` both
    build through here."""
    import jax
    from repro.configs import get_config, reduced
    from repro.configs.base import ShapeConfig
    from repro.core import model, steps
    from repro.core.partition import ShardingPlan
    from repro.launch.mesh import host_mesh
    from repro.serving import (FairScheduler, PriorityScheduler,
                               SamplerConfig, ServingEngine)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    kvd = {"fp32": "float32", "fp16": "bfloat16", "int8": "int8"}
    plan = ShardingPlan(tp=args.tp, kv_cache_dtype=kvd[args.kv_dtype],
                        ssm_cache_dtype=("int8" if args.kv_dtype == "int8"
                                         else ""))
    # shard replicas over real devices when they exist; otherwise they
    # co-locate on one data shard (n_replicas must cover the mesh evenly)
    mesh_dp = max((d for d in range(1, args.dp + 1)
                   if args.dp % d == 0 and
                   d * args.tp <= len(jax.devices())), default=1)
    mesh = host_mesh(tp=args.tp, dp=mesh_dp)
    params = model.init_params(cfg, plan, seed=args.seed, mesh=mesh)

    scheduler = None                 # engine default: FCFS
    if args.policy == "priority":
        scheduler = functools.partial(PriorityScheduler,
                                      preemption=args.preemption)
    elif args.policy == "fair":
        scheduler = functools.partial(FairScheduler,
                                      preemption=args.preemption)

    sampler = SamplerConfig(temperature=args.temperature, top_k=40)
    if args.paged or args.prefix_cache or args.dp > 1 or args.speculative:
        engine = ServingEngine.build_paged(
            cfg, plan, mesh, args.slots, args.seq_budget, params,
            page_size=args.page_size, n_pages=args.n_pages,
            prefill_chunk=args.prefill_chunk, sampler=sampler,
            prefix_cache=args.prefix_cache, scheduler=scheduler,
            rng_seed=args.seed, dp=args.dp, speculative=args.speculative,
            overlap=args.overlap, disagg=args.disagg)
        if args.scale_events:
            def membership_hook(e, _pending=list(args.scale_events)):
                while _pending and e.stats.ticks >= _pending[0][0]:
                    _, kind, val = _pending.pop(0)
                    if kind == "scale":
                        e.scale_to(val)
                    else:
                        e.kill_replica(val)
            engine.membership_hook = membership_hook
    else:
        dshape = ShapeConfig("serve", "decode", args.seq_budget, args.slots)
        pshape = ShapeConfig("serve1", "decode", args.seq_budget, 1)
        decode_fn, _, _ = steps.make_decode_step(cfg, plan, mesh, dshape)
        prefill_fn, _, _ = steps.make_prefill_step(cfg, plan, mesh, pshape)
        # donate the lane/engine cache: it is reassigned from the return at
        # every call site (cache arg trails the prompt; enc-dec adds frames)
        cache_arg = 3 if cfg.is_encdec else 2
        engine = ServingEngine(cfg, plan, mesh, args.slots, args.seq_budget,
                               params,
                               jax.jit(prefill_fn,
                                       donate_argnums=(cache_arg,)),
                               jax.jit(decode_fn, donate_argnums=(1,)),
                               sampler=sampler,
                               scheduler=scheduler, rng_seed=args.seed)
    return engine


def main(argv=None):
    args = parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Request

    enable_compile_cache()
    engine = build_engine(args)
    cfg, disagg = engine.cfg, args.disagg
    rng = np.random.RandomState(args.seed)
    shared = rng.randint(2, cfg.vocab_size,
                         args.shared_prefix).astype(np.int32)
    frame_groups = [rng.randn(cfg.enc_seq_len, cfg.d_model
                              ).astype(np.float32)
                    for _ in range(max(args.frame_groups, 1))] \
        if cfg.is_encdec else []
    reqs = []
    t0 = time.time()
    for rid in range(args.requests):
        prompt = rng.randint(2, cfg.vocab_size,
                             rng.randint(4, args.prompt_len + 1)
                             ).astype(np.int32)
        prompt = np.concatenate([shared, prompt]).astype(np.int32)
        hi = args.high_priority_every and rid % args.high_priority_every == 0
        req = Request(rid=rid, prompt=prompt, max_new_tokens=args.max_new,
                      priority=10 if hi else 0,
                      client_id=rid % max(args.clients, 1),
                      frames=(frame_groups[rid % len(frame_groups)]
                              if frame_groups else None))
        reqs.append(req)
        engine.submit(req)
    stats = engine.run()
    dt = time.time() - t0
    print(f"requests={args.requests} ticks={stats.ticks} "
          f"prefills={stats.prefills} tokens={stats.decoded_tokens} "
          f"preemptions={stats.preemptions}")
    if stats.ttft_s:
        print(f"throughput={stats.decoded_tokens / dt:.1f} tok/s "
              f"ttft_p50={np.median(stats.ttft_s) * 1e3:.1f}ms "
              f"ttft_p95={np.percentile(stats.ttft_s, 95) * 1e3:.1f}ms "
              f"tpot_p50={np.median(stats.tpot_s) * 1e3:.1f}ms")
    else:
        print("no tokens emitted")
    if engine.paged:
        per_tick = 1e3 / max(stats.ticks, 1)
        print(f"pipeline: overlap={'on' if engine.overlap else 'off'} "
              f"plan_ahead_ticks={stats.plan_ahead_ticks} "
              f"plan_invalidations={stats.plan_invalidations} "
              f"collect_wait={stats.collect_wait_s * 1e3:.1f}ms")
        print(f"host per tick: plan={stats.plan_s * per_tick:.2f}ms "
              f"wait={stats.collect_wait_s * per_tick:.2f}ms "
              f"sample={stats.sample_s * per_tick:.2f}ms "
              f"dispatch={stats.dispatch_s * per_tick:.2f}ms")
    if disagg is not None:
        print(f"disagg(P={disagg[0]} D={disagg[1]}): "
              f"handoffs={stats.handoffs} "
              f"pages_transferred={stats.pages_transferred}")
    if args.scale_events:
        print(f"elastic: scale_events={stats.scale_events} "
              f"crashes={stats.crashes} migrations={stats.migrations} "
              f"migrated_pages={stats.migrated_pages} "
              f"readmitted={stats.readmitted} dp_final={engine.R}")
    if args.high_priority_every:
        for label, cls in (("high", 10), ("low", 0)):
            ts = [stats.request_ttft[r.rid] for r in reqs
                  if r.priority == cls and r.rid in stats.request_ttft]
            if ts:
                print(f"ttft[{label}]: p50={np.median(ts) * 1e3:.1f}ms "
                      f"p99={np.percentile(ts, 99) * 1e3:.1f}ms "
                      f"n={len(ts)}")
    if args.prefix_cache:
        cached = sum(c.n_cached_pages for c in engine.prefix_caches if c)
        evictions = sum(c.evictions for c in engine.prefix_caches if c)
        print(f"prefix_cache: hit_rate={stats.prefix_hit_rate:.2f} "
              f"({stats.prefix_hits}/{stats.prefix_lookups} lookups) "
              f"prefill_tokens_skipped={stats.prefill_tokens_skipped} "
              f"cow_copies={stats.cow_copies} "
              f"cached_pages={cached} evictions={evictions}")
    if args.speculative:
        print(f"speculative(k={args.speculative}): "
              f"accepted_tokens_per_tick="
              f"{stats.accepted_tokens_per_tick:.2f} "
              f"draft_hit_rate={stats.draft_hit_rate:.2f} "
              f"({stats.spec_draft_hits}/{stats.spec_draft_lookups} "
              f"lookups) accepted={stats.spec_accepted}"
              f"/{stats.spec_drafted} drafted "
              f"spec_denied={stats.spec_denied}")
    if engine.cross_caches:
        print(f"cross_kv: hit_rate={stats.cross_hit_rate:.2f} "
              f"({stats.cross_hits}/{stats.cross_lookups} lookups) "
              f"encodes={stats.cross_encodes} "
              f"cached_entries="
              f"{sum(c.n_entries for c in engine.cross_caches)}")
    if engine.slab_allocators:
        print(f"ssm_slabs: per_replica={engine.n_slabs - 1} "
              f"allocated={sum(s.total_allocated for s in engine.slab_allocators)} "
              f"stash_restores={stats.slab_restores}")
    if args.dp > 1:
        print(f"router: affinity_routed={engine.router.affinity_routed}"
              f"/{args.requests}")
        for r, rs in enumerate(stats.replicas):
            alloc = engine.allocators[r]
            handoff = ""
            if disagg is not None:
                handoff = (f" role={rs.role} "
                           f"handoffs={rs.handoffs_out}out/"
                           f"{rs.handoffs_in}in "
                           f"pages_transferred={rs.pages_transferred_out}"
                           f"out/{rs.pages_transferred_in}in")
            print(f"replica[{r}]: routed={rs.routed} "
                  f"prefills={rs.prefills} tokens={rs.decoded_tokens} "
                  f"preemptions={rs.preemptions} "
                  f"prefix_hit_rate={rs.prefix_hit_rate:.2f} "
                  f"pages_allocated={alloc.total_allocated} "
                  f"pages_free={alloc.n_free}/"
                  f"{alloc.n_pages - alloc.n_reserved}" + handoff)
    slowest = sorted(stats.request_ttft.items(), key=lambda kv: -kv[1])[:3]
    print("ttft_per_request_worst3: " +
          " ".join(f"rid{r}={t * 1e3:.1f}ms" for r, t in slowest))
    return 0


if __name__ == "__main__":
    sys.exit(main())
