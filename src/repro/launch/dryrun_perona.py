import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Dry-run of the PAPER'S OWN model on the production meshes: Perona
# fingerprint training at fleet scale. At 1000+ nodes the fingerprint DB
# is genuinely large (every node x 6 benchmark types x a rolling history
# of executions), so the Perona train step itself must shard: nodes are
# data-parallel over the full mesh; the 3-predecessor neighbor gathers
# stay chain-local and lower to collectives where chains cross shards.
#
#   PYTHONPATH=src python -m repro.launch.dryrun_perona --mesh multi

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.model import PeronaConfig, PeronaModel
from repro.launch import roofline as rl
from repro.launch.mesh import (TARGET_DEVICE_KIND, data_axes,
                               make_production_mesh)
from repro.optim.adamw import AdamW


# fleet-scale fingerprint batch: 2048 nodes x 6 types x 16-run history
FLEET_N = 2048 * 6 * 16  # 196,608 executions
FEATURE_DIM = 94  # 88 selected metrics + 6 type one-hot (§IV-C fit)
EDGE_DIM = 12


def abstract_batch(n: int):
    sds = jax.ShapeDtypeStruct
    return {
        "x": sds((n, FEATURE_DIM), jnp.float32),
        "type_id": sds((n,), jnp.int32),
        "anomaly": sds((n,), jnp.int32),
        "nbr": sds((n, 3), jnp.int32),
        "nbr_mask": sds((n, 3), jnp.bool_),
        "edge": sds((n, 3, EDGE_DIM), jnp.float32),
        "norm_gt": sds((n,), jnp.float32),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    d_ax = data_axes(mesh) + ("model",)  # pure DP over every axis
    cfg = PeronaConfig(feature_dim=FEATURE_DIM, edge_dim=EDGE_DIM)
    model = PeronaModel(cfg)
    opt = AdamW(lr=3e-3)

    aparams = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    astate = opt.abstract_state(aparams)
    batch = abstract_batch(FLEET_N)
    rep = NamedSharding(mesh, P())
    node_sh = NamedSharding(mesh, P(d_ax))

    def shard_of(leaf):
        return NamedSharding(mesh, P(d_ax, *([None] * (len(leaf.shape) - 1))))

    bshard = jax.tree_util.tree_map(shard_of, batch)
    pshard = jax.tree_util.tree_map(lambda _: rep, aparams)
    oshard = jax.tree_util.tree_map(lambda _: rep, astate)

    def train_step(params, state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss, has_aux=True)(params, batch, jax.random.PRNGKey(0))
        params, state, om = opt.update(grads, state, params)
        return params, state, loss

    record = {"arch": "perona-fingerprint", "shape": f"fleet_{FLEET_N}",
              "mesh": args.mesh, "status": "ok",
              "target_device_kind": TARGET_DEVICE_KIND}
    try:
        t0 = time.time()
        with mesh:
            lowered = jax.jit(
                train_step,
                in_shardings=(pshard, oshard, bshard)).lower(
                    aparams, astate, batch)
            compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = rl.collective_bytes(compiled.as_text())
        flops = float(ca.get("flops", 0.0))
        record.update({
            "compile_s": round(time.time() - t0, 2),
            "flops_per_device": flops,
            "bytes_per_device": float(ca.get("bytes accessed", 0.0)),
            "collective_bytes_per_device": coll,
            "roofline": rl.roofline_terms(
                flops, float(ca.get("bytes accessed", 0.0)),
                sum(coll.values()), device_kind=TARGET_DEVICE_KIND),
        })
    except Exception as e:  # noqa: BLE001
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc(limit=20)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"perona-fingerprint--fleet--{args.mesh}.json"
    path.write_text(json.dumps(record, indent=2))
    print(json.dumps({k: v for k, v in record.items()
                      if k != "traceback"}, indent=2))
    if record["status"] != "ok":
        print(record.get("traceback", ""))
        raise SystemExit(1)


if __name__ == "__main__":
    main()
