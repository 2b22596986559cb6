import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers + compiles the step (train_step for train shapes; prefill/decode
     steps for serving shapes) against ShapeDtypeStruct inputs (no
     allocation),
  3. records memory_analysis / cost_analysis / the HLO collective schedule,
  4. derives the three-term roofline (analytic FLOPs+bytes, CommLedger wire
     bytes) and appends everything to a JSON results file.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]
  python -m repro.launch.dryrun --all --subprocess   # one proc per cell

The dry run is CPU-only by construction: it forces 512 virtual host
devices and lowers against shapes, never a chip.  ``--subprocess`` starts
one child process per cell, which is safe only because no process here
holds a TPU; do not run it on a machine whose chip a parent holds.

Plan variants (hillclimbing): --moe-mode ep, --remat block, --seq-kv,
--kv-dtype int8, --activations seq.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from collections import Counter  # noqa: E402


def _build_plan(args, cfg, shape):
    from repro.core.partition import ShardingPlan
    dp_axes = ("pod", "data") if args.multi_pod else ("data",)
    seq_kv = shape.name == "long_500k" and cfg.family != "ssm"
    if args.seq_kv:
        seq_kv = True
    remat = args.remat
    if remat == "auto":   # production default: remat train shapes
        remat = "block" if shape.kind == "train" else "none"
    tp, cp_axes = 16, ()
    if args.cp:           # context parallelism over the model axis (tp=1)
        tp, cp_axes = 1, ("model",)
    return ShardingPlan(
        tp=tp, dp_axes=dp_axes, seq_shard_kv=seq_kv, cp_axes=cp_axes,
        cp_state_dtype=args.cp_state_dtype, zero1=args.zero1,
        moe_mode=args.moe_mode, remat=remat,
        kv_cache_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
        attn_scheme=args.attn_scheme, activations=args.activations)


def run_cell(arch: str, shape_name: str, multi_pod: bool, args):
    import jax
    from repro.configs import SHAPES, get_config, shape_supported
    from repro.core import analytics, collectives as cc, steps
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    if getattr(args, "ssm_chunk", 0):
        import dataclasses
        cfg = dataclasses.replace(cfg, ssm_chunk=args.ssm_chunk)
    shape = SHAPES[shape_name]
    ok, reason = shape_supported(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = _build_plan(args, cfg, shape)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape, strict=True))
    t0 = time.time()
    cc.LEDGER.start()

    if shape.kind == "train":
        if plan.zero1:
            step, specs = steps.make_train_step_zero1(
                cfg, plan, mesh, shape=shape, grad_accum=args.grad_accum)
            state = steps.abstract_train_state_zero1(cfg, plan, mesh)
        else:
            step, specs = steps.make_train_step(cfg, plan, mesh, shape=shape,
                                                grad_accum=args.grad_accum)
            state = steps.abstract_train_state(cfg, plan)
        batch, _ = steps.train_batch_template(cfg, shape, plan)
        with mesh:
            lowered = jax.jit(step, donate_argnums=(0,)).lower(state, batch)
    elif shape.kind == "prefill":
        fn, t, s = steps.make_prefill_step(cfg, plan, mesh, shape)
        from repro.core import model as m
        params = m.abstract_params(cfg, plan)
        with mesh:
            if cfg.is_encdec:
                lowered = jax.jit(fn).lower(params, t["frames"],
                                            t["dec_tokens"], t["cache"])
            elif cfg.frontend == "vision_patches":
                lowered = jax.jit(fn).lower(params, t["prompt"],
                                            t["image_embeds"], t["cache"])
            else:
                lowered = jax.jit(fn).lower(params, t["prompt"], t["cache"])
    else:  # decode
        fn, t, s = steps.make_decode_step(cfg, plan, mesh, shape)
        from repro.core import model as m
        params = m.abstract_params(cfg, plan)
        with mesh:
            lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                params, t["cache"], t["tokens1"], t["pos"])
    t_lower = time.time() - t0
    cc.LEDGER.stop()
    ledger_bytes = cc.LEDGER.total_bytes()
    comm_by_tag = cc.LEDGER.bytes_by_tag()
    block_syncs = cc.LEDGER.sync_count("block/")

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo_colls = dict(Counter(
        re.findall(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                   r"collective-permute)", compiled.as_text())))

    cost = analytics.step_cost(cfg, plan, shape, sizes)
    model_flops = analytics.model_flops_ideal(cfg, shape)
    n_chips = int(np.prod(mesh.devices.shape)) if (np := __import__("numpy")) \
        else 0
    roof = rl.build_roofline(arch, shape_name, mesh_name, cost, ledger_bytes,
                             comm_by_tag, model_flops, n_chips)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok",
        "plan": {"tp": plan.tp, "dp_axes": list(plan.dp_axes),
                 "seq_shard_kv": plan.seq_shard_kv, "cp_axes": list(plan.cp_axes),
                 "moe_mode": plan.moe_mode, "remat": plan.remat,
                 "kv_cache_dtype": plan.kv_cache_dtype,
                 "weight_dtype": plan.weight_dtype,
                 "attn_scheme": plan.attn_scheme},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes_per_device": mem.argument_size_in_bytes,
            "output_bytes_per_device": mem.output_size_in_bytes,
            "temp_bytes_per_device": mem.temp_size_in_bytes,
            "alias_bytes_per_device": mem.alias_size_in_bytes,
            "peak_est_bytes_per_device": (mem.argument_size_in_bytes +
                                          mem.output_size_in_bytes +
                                          mem.temp_size_in_bytes -
                                          mem.alias_size_in_bytes),
        },
        "cost_analysis": {k: float(v) for k, v in ca.items()
                          if k in ("flops", "bytes accessed")},
        "hlo_collectives": hlo_colls,
        "block_syncs_per_step": block_syncs,
        "roofline": roof.to_dict(),
    }
    return rec


def all_cells(multi_pod):
    from repro.configs import ASSIGNED, SHAPES
    for arch in ASSIGNED:
        for shape in SHAPES:
            yield arch, shape, multi_pod


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--subprocess", action="store_true",
                    help="isolate each cell in its own process "
                         "(CPU-only: cells lower on virtual host devices)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--moe-mode", default="tp", choices=["tp", "ep"])
    ap.add_argument("--remat", default="auto",
                    choices=["auto", "none", "block", "selective"])
    ap.add_argument("--seq-kv", action="store_true")
    ap.add_argument("--kv-dtype", default="bfloat16")
    ap.add_argument("--weight-dtype", default="")
    ap.add_argument("--attn-scheme", default="scan", choices=["scan", "split"])
    ap.add_argument("--cp", action="store_true",
                    help="context parallelism on the model axis (tp=1)")
    ap.add_argument("--activations", default="replicated")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--cp-state-dtype", default="float32")
    ap.add_argument("--zero1", action="store_true",
                    help="shard optimizer state over the data axis (ZeRO-1)")
    ap.add_argument("--ssm-chunk", type=int, default=0,
                    help="override the SSD chunk length")
    args = ap.parse_args()

    results = []
    if args.all:
        cells = []
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            cells += list(all_cells(mp))
        for arch, shape, mp in cells:
            if args.subprocess:
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--moe-mode", args.moe_mode, "--remat", args.remat,
                       "--kv-dtype", args.kv_dtype,
                       "--activations", args.activations]
                if mp:
                    cmd.append("--multi-pod")
                if args.seq_kv:
                    cmd.append("--seq-kv")
                r = subprocess.run(cmd, capture_output=True, text=True)
                line = [ln for ln in r.stdout.splitlines()
                        if ln.startswith("RESULT ")]
                if line:
                    rec = json.loads(line[-1][len("RESULT "):])
                else:
                    rec = {"arch": arch, "shape": shape,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error",
                           "error": (r.stderr or r.stdout)[-2000:]}
                results.append(rec)
                print(f"[{rec['status']:7s}] {arch} {shape} "
                      f"{'mp' if mp else 'sp'} "
                      f"{rec.get('compile_s', '')}")
            else:
                results.append(_run_and_print(arch, shape, mp, args))
    else:
        results.append(_run_and_print(args.arch, args.shape, args.multi_pod,
                                      args))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out} ({len(results)} records)")


def _run_and_print(arch, shape, mp, args):
    try:
        rec = run_cell(arch, shape, mp, args)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        import traceback
        rec = {"arch": arch, "shape": shape,
               "mesh": "2x16x16" if mp else "16x16", "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-1500:]}
    print("RESULT " + json.dumps(rec))
    return rec


if __name__ == "__main__":
    main()
