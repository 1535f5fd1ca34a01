"""Perf-loop profiler: list the largest collectives in a cell's compiled
HLO with op metadata (this is the 'profile' the §Perf hints describe —
lowered IR, not wall clock).

A CPU-only model-scaffold tool: it forces 512 host devices at import, so
it is never sent to the chip and nothing on the chip path imports it.

    PYTHONPATH=src python -m benchmarks.inspect_collectives --arch X --shape Y
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import argparse
import dataclasses
import json
import re


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--shape", required=True)
    p.add_argument("--mesh", default="single")
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--opts", default="")
    p.add_argument("--depth1", action="store_true",
                   help="lower 1 period unrolled (faster, per-layer view)")
    args = p.parse_args()

    from repro.configs import ARCHS, SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import _COLL_RE, _SHAPE_RE, _shape_bytes
    from repro.launch.steps import build_step

    cfg = ARCHS[args.arch]
    shape = SHAPES[args.shape]
    kw = json.loads(args.opts) if args.opts else {}
    if args.depth1:
        cfg = dataclasses.replace(cfg, n_layers=cfg.period)
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    compiled = build_step(cfg, mesh, shape, **kw).lower().compile()
    txt = compiled.as_text()

    rows = []
    for line in txt.splitlines():
        s = line.strip()
        m = _COLL_RE.search(s)
        if not m or "-done" in s.split("=")[-1][:40]:
            continue
        nbytes = _shape_bytes(s)
        meta = ""
        mm = re.search(r'op_name="([^"]+)"', s)
        if mm:
            meta = mm.group(1)[-110:]
        rows.append((nbytes, m.group(1), meta))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"{len(rows)} collectives (static, unmultiplied), "
          f"{total/2**30:.2f} GiB total")
    for nbytes, kind, meta in rows[:args.top]:
        print(f"{nbytes/2**20:10.1f} MiB  {kind:20s} {meta}")


if __name__ == "__main__":
    main()
