import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks device
# count on first init).  Everything below may import jax.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

A CPU-only model-scaffold tool: it forces 512 host devices at import
and spawns child processes, so it is never sent to the chip, and
nothing on the chip path (``chip_smoke.py``, the cluster front door,
``benchmarks/run.py``) imports it.

Proves the distribution config is coherent without hardware:

  * 16x16 single-pod mesh (256 chips) AND 2x16x16 multi-pod (512 chips)
  * every assigned architecture x its applicable input shapes
  * prints compiled.memory_analysis() (fits check) and cost_analysis()
    (roofline §) per cell, written as JSON under experiments/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every cell, subprocesses
  python -m repro.launch.dryrun --all --mesh multi
"""
import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

OUT_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             roofline: bool = True, variant: str = "",
             overrides=None, step_opts=None) -> dict:
    import jax
    from repro.configs import ARCHS, SHAPES, skip_reason
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import (extrapolate, model_flops,
                                       parse_collectives, RooflineTerms)
    from repro.launch.steps import build_step
    from repro.models import attention as attn_mod
    from repro.models import ssm as ssm_mod

    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    if overrides:
        moe_over = overrides.pop("moe", None)
        if moe_over and cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, **moe_over))
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
    # step-level perf knobs (the §Perf hillclimb turns these)
    step_kw = {}
    if step_opts:
        import jax.numpy as jnp
        from repro.optim.adamw import AdamWConfig
        if "cache_write" in step_opts:
            from repro.models import model as model_mod
            model_mod.CACHE_WRITE = step_opts["cache_write"]
        if step_opts.get("seq_parallel"):
            import repro.sharding.specs as _specs
            _orig = _specs.make_rules

            def _mk(mesh, c, **kw):
                r = _orig(mesh, c, **kw)
                r.seq_parallel = True
                return r
            _specs.make_rules = _mk
            import repro.launch.steps as _steps
            _steps.make_rules = _mk
        if shape.kind == "train":
            if "remat" in step_opts:
                step_kw["remat"] = step_opts["remat"]
            if "loss_chunk" in step_opts:
                step_kw["loss_chunk"] = step_opts["loss_chunk"]
            if "moment_dtype" in step_opts:
                step_kw["adamw"] = AdamWConfig(
                    moment_dtype=getattr(jnp, step_opts["moment_dtype"]))
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                 "variant": variant, "kind": shape.kind}
    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skip"
        rec["skip_reason"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.size

    # ---- 1. production lowering: full depth, scanned --------------------
    # monotonic: an NTP step mid-compile must not corrupt compile_s
    t0 = time.monotonic()
    bundle = build_step(cfg, mesh, shape, **step_kw)
    lowered = bundle.lower()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.monotonic() - t0, 1)
    ma = compiled.memory_analysis()
    rec["memory_per_device"] = {
        "arguments_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "peak_bytes": int(ma.peak_memory_in_bytes),
        "fits_16GiB_hbm": bool(
            ma.argument_size_in_bytes + ma.temp_size_in_bytes
            < 16 * 1024**3),
    }
    prod_cost = compiled.cost_analysis()
    rec["cost_analysis_raw"] = {
        k: float(prod_cost[k]) for k in ("flops", "bytes accessed")
        if k in prod_cost}
    prod_coll = parse_collectives(compiled.as_text(), multiply_while=True,
                                  default_trips=cfg.n_periods)
    rec["collectives_prod_bytes"] = {k: float(v) for k, v in
                                     prod_coll.per_kind_bytes.items()}
    rec["status"] = "ok"

    # ---- 2. roofline: reduced-depth unrolled delta method ----------------
    if roofline:
        attn_mod.UNROLL_SCANS = True
        ssm_mod.UNROLL_SCANS = True
        try:
            costs, colls = [], []
            for k in (1, 2):
                small = dataclasses.replace(cfg,
                                            n_layers=cfg.period * k)
                b = build_step(small, mesh, shape, scan_unroll=k,
                               **step_kw)
                c = b.lower().compile()
                costs.append(c.cost_analysis())
                colls.append(parse_collectives(
                    c.as_text(), multiply_while=True).total)
            flops, hbm, coll = extrapolate(costs[0], costs[1],
                                           colls[0], colls[1],
                                           cfg.n_periods)
            terms = RooflineTerms(flops=flops, hbm_bytes=hbm,
                                  coll_bytes=coll,
                                  model_flops=model_flops(cfg, shape))
            rec["roofline"] = terms.summary(chips)
            rec["roofline"]["coll_prod_crosscheck_bytes"] = prod_coll.total
        finally:
            attn_mod.UNROLL_SCANS = False
            ssm_mod.UNROLL_SCANS = False
    return rec


def cell_list(mesh_kind: str):
    from repro.configs import ARCHS, SHAPES
    for arch in sorted(ARCHS):
        for shape in SHAPES:
            yield arch, shape, mesh_kind


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch")
    p.add_argument("--shape")
    p.add_argument("--mesh", default="single", choices=["single", "multi"])
    p.add_argument("--all", action="store_true")
    p.add_argument("--no-roofline", action="store_true")
    p.add_argument("--variant", default="",
                   help="label recorded in the JSON (perf experiments)")
    p.add_argument("--override", default="",
                   help="JSON dict of ArchConfig field overrides")
    p.add_argument("--opts", default="",
                   help="JSON dict of step options: remat, loss_chunk, "
                        "moment_dtype (perf hillclimbing)")
    p.add_argument("--out", default=OUT_DIR)
    p.add_argument("--timeout", type=int, default=2400)
    args = p.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        failures = []
        for arch, shape, mesh in cell_list(args.mesh):
            tag = f"{arch}_{shape}_{mesh}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path):
                print(f"[skip existing] {tag}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh,
                   "--out", args.out]
            if args.no_roofline:
                cmd.append("--no-roofline")
            print(f"[run] {tag}", flush=True)
            try:
                r = subprocess.run(cmd, timeout=args.timeout)
                if r.returncode != 0:
                    failures.append(tag)
            except subprocess.TimeoutExpired:
                failures.append(tag + " (timeout)")
        print("FAILURES:", failures if failures else "none")
        sys.exit(1 if failures else 0)

    roofline = not args.no_roofline and args.mesh == "single"
    overrides = json.loads(args.override) if args.override else None
    step_opts = json.loads(args.opts) if args.opts else None
    try:
        rec = run_cell(args.arch, args.shape, args.mesh,
                       roofline=roofline, variant=args.variant,
                       overrides=overrides, step_opts=step_opts)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
               "status": "error", "error": traceback.format_exc()}
    suffix = f"_{args.variant}" if args.variant else ""
    tag = f"{args.arch}_{args.shape}_{args.mesh}{suffix}"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=2)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("error",)}, indent=2)[:2000])
    if rec["status"] == "error":
        print(rec["error"][-3000:], file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
