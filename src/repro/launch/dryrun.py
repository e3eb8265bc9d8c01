import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST stay the first statements of this module: jax
# locks the device count at first init, and the production meshes need
# 512 placeholder host devices.
#
# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
#       --shape train_4k --mesh single --out artifacts/dryrun
#
# Per cell this performs:
#   1. a full-depth SCAN-over-layers compile  -> proves the production
#      config lowers+compiles on the mesh; memory analysis.
#   2. two shallow UNROLLED compiles (1 and 2 body periods) -> exact
#      per-period flops/bytes/collective bytes (XLA cost analysis counts
#      while bodies once, so the scanned module cannot be used for
#      costs); linear extrapolation to full depth.
# --all sweeps the assigned matrix; long_500k cells for non-sub-quadratic
# archs are recorded as skipped (DESIGN.md §4). Multi-pod runs step 1
# only (the roofline table is single-pod by design).

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCHS, get_config
from repro.launch import roofline as rl
from repro.launch.mesh import TARGET_DEVICE_KIND, make_production_mesh
from repro.launch.steps import lowerable
from repro.models.config import SHAPES_BY_NAME, shapes_for
from repro.models.model_zoo import build_model


def with_depth(cfg, n_periods: int):
    n_layers = (len(cfg.head_pattern) + n_periods * len(cfg.body_pattern)
                + len(cfg.tail_pattern))
    return dataclasses.replace(cfg, n_periods=n_periods, n_layers=n_layers)


def _compile(cfg, shape, mesh, layout: str = "2d", donate: bool = False):
    model = build_model(cfg)
    fn, in_shardings, args = lowerable(model, shape, mesh, layout=layout)
    donate_argnums = (3,) if (donate and shape.kind == "decode") else ()
    t0 = time.time()
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_shardings,
                          donate_argnums=donate_argnums).lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    return compiled, round(t1 - t0, 2), round(t2 - t1, 2)


def _costs(compiled):
    ca = compiled.cost_analysis()
    coll = rl.collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll": coll,
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             overrides=None, tag: str = "", costs: bool = True,
             layout: str = "2d", donate: bool = False) -> dict:
    cfg = get_config(arch)
    if overrides:
        moe_over = (overrides or {}).pop("moe", None)
        cfg = dataclasses.replace(cfg, **overrides)
        if moe_over:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, **moe_over))
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))

    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "tag": tag, "status": "ok",
        "target_device_kind": TARGET_DEVICE_KIND,
        "layout": layout, "donate": donate,
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
    }
    try:
        # -- step 1: full-depth scan compile (production config) ---------
        compiled, lower_s, compile_s = _compile(
            dataclasses.replace(cfg, scan_layers=True), shape, mesh,
            layout, donate)
        record["lower_s"] = lower_s
        record["compile_s"] = compile_s
        record["memory_analysis"] = _mem_dict(compiled.memory_analysis())
        del compiled

        if costs:
            # -- step 2: shallow unrolled compiles for exact costs -------
            p1, p2 = 1, 2
            c1, *_ = _compile(
                with_depth(dataclasses.replace(cfg, scan_layers=False), p1),
                shape, mesh, layout, donate)
            k1 = _costs(c1)
            del c1
            c2, *_ = _compile(
                with_depth(dataclasses.replace(cfg, scan_layers=False), p2),
                shape, mesh, layout, donate)
            k2 = _costs(c2)
            del c2
            n = cfg.n_periods
            flops = k2["flops"] + (n - p2) * (k2["flops"] - k1["flops"])
            bytes_ = k2["bytes"] + (n - p2) * (k2["bytes"] - k1["bytes"])
            coll = {
                op: int(k2["coll"][op]
                        + (n - p2) * (k2["coll"][op] - k1["coll"][op]))
                for op in k2["coll"]
            }
            terms = rl.roofline_terms(flops, bytes_, sum(coll.values()),
                                      device_kind=TARGET_DEVICE_KIND)
            n_chips = 1
            for v in mesh.shape.values():
                n_chips *= v
            mflops = rl.model_flops(cfg, shape)
            record.update({
                "flops_per_device": flops,
                "bytes_per_device": bytes_,
                "collective_bytes_per_device": coll,
                "collective_bytes_total": sum(coll.values()),
                "roofline": terms,
                "model_flops_global": mflops,
                "model_flops_per_device": mflops / n_chips,
                "useful_flops_ratio": (mflops / n_chips / flops)
                if flops else None,
                "depth_probe": {"p1": k1, "p2": k2, "n_periods": n},
            })
    except Exception as e:  # noqa: BLE001 - record the failure verbatim
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc(limit=20)

    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = f"-{tag}" if tag else ""
    path = out_dir / f"{arch}--{shape_name}--{mesh_kind}{suffix}.json"
    path.write_text(json.dumps(record, indent=2))
    return record


def _mem_dict(mem) -> dict:
    if mem is None:
        return {}
    out = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes", "peak_memory_in_bytes"):
        if hasattr(mem, field):
            try:
                out[field] = int(getattr(mem, field))
            except (TypeError, ValueError):
                pass
    return out


def cell_matrix():
    cells = []
    for arch in ARCHS:
        cfg = get_config(arch)
        active = {s.name for s in shapes_for(cfg)}
        for sname in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
            cells.append((arch, sname, sname in active))
    return cells


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--layout", default="2d", choices=["2d", "dp"])
    ap.add_argument("--donate", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. remat=dots, "
                         "kv_quant=true, moe.impl=einsum)")
    args = ap.parse_args()
    out_dir = Path(args.out)

    overrides = {}
    for kv in args.set:
        key, val = kv.split("=", 1)
        if val.lower() in ("true", "false"):
            val = val.lower() == "true"
        elif val.isdigit():
            val = int(val)
        if key.startswith("moe."):
            overrides.setdefault("moe", {})[key[4:]] = val
        else:
            overrides[key] = val

    if args.all:
        for arch, sname, active in cell_matrix():
            for mesh_kind in ("single", "multi"):
                suffix = f"-{args.tag}" if args.tag else ""
                path = out_dir / f"{arch}--{sname}--{mesh_kind}{suffix}.json"
                if path.exists():
                    continue
                if not active:
                    out_dir.mkdir(parents=True, exist_ok=True)
                    path.write_text(json.dumps({
                        "arch": arch, "shape": sname, "mesh": mesh_kind,
                        "status": "skipped",
                        "reason": "full-attention arch: no sub-quadratic "
                                  "path for 500k decode (DESIGN.md §4)",
                    }, indent=2))
                    continue
                t0 = time.time()
                rec = run_cell(arch, sname, mesh_kind, out_dir,
                               costs=(mesh_kind == "single"),
                               overrides=dict(overrides) or None,
                               tag=args.tag, layout=args.layout,
                               donate=args.donate)
                print(f"{arch} {sname} {mesh_kind}: {rec['status']} "
                      f"({time.time() - t0:.0f}s)", flush=True)
        return

    rec = run_cell(args.arch, args.shape, args.mesh, out_dir, tag=args.tag,
                   overrides=overrides or None, layout=args.layout,
                   donate=args.donate)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback",)}, indent=2))
    if rec["status"] != "ok":
        print(rec.get("traceback", ""))
        raise SystemExit(1)


if __name__ == "__main__":
    main()
