import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# ^ MUST precede every other import (same contract as dryrun.py).

"""Dry-run of the PAPER'S OWN technique at production scale.

Lowers the distributed range-query step (zone-prune + box-scan refine,
shard_map'd over the data axis) against the paper's catalog geometry:
90,429,772 rows x d' subset dims, sharded over the 16x16 pod — and the
full-scan baseline the scan models must run. Produces the same JSON
artifacts as dryrun.py so benchmarks/roofline.py §Search can price both
paths per the v5e roofline.

Variants (--variant):
  index_query   zone-prune + gather-free masked refine (the engine step)
  full_scan     box_scan over the whole shard (DT/RF inference)

Usage:
  python -m repro.launch.search_dryrun --variant index_query
  python -m repro.launch.search_dryrun --all
"""
import argparse
import gzip
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.dryrun import collective_stats, memory_dict
from repro.launch.hlo_analysis import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh

ART_DIR = Path(__file__).resolve().parents[3] / "experiments" / "artifacts" / "dryrun"

# the paper's catalog (§3): 90,429,772 patches
PAPER_ROWS = 90_429_772


def search_step_specs(*, n_rows: int, d_sub: int, block: int, n_boxes: int):
    nb = -(-n_rows // block)
    # pad block count to the data axis (256 shards on 16x16... mesh data=16)
    rows = jax.ShapeDtypeStruct((nb, block, d_sub), jnp.float32)
    zlo = jax.ShapeDtypeStruct((nb, d_sub), jnp.float32)
    zhi = jax.ShapeDtypeStruct((nb, d_sub), jnp.float32)
    blo = jax.ShapeDtypeStruct((n_boxes, d_sub), jnp.float32)
    bhi = jax.ShapeDtypeStruct((n_boxes, d_sub), jnp.float32)
    return rows, zlo, zhi, blo, bhi


def make_index_query_step(mesh, block: int, capacity: int):
    """The engine's sharded query step — the capacity-bounded PRUNED
    formulation. The local per-shard program is imported from
    core/index.pruned_local_step (NOT re-implemented here), so the HLO
    this dry-run lowers at paper scale is byte-for-byte the production
    step distributed_query_pruned shard_maps."""
    from jax.sharding import PartitionSpec as P

    from repro.core.index import pruned_local_step

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data", "model"))
    spec = P(dp)
    return jax.shard_map(pruned_local_step(block, capacity), mesh=mesh,
                         in_specs=(spec, spec, spec, P(), P()),
                         out_specs=spec, check_vma=False)


def make_full_scan_step(mesh, block: int):
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ref as kref

    def local(rows, blo, bhi):
        flat = rows.reshape(-1, rows.shape[-1])
        return kref.box_scan_ref(flat, blo, bhi)

    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data", "model"))
    spec = P(dp)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, P(), P()),
                         out_specs=spec, check_vma=False)


def run_variant(variant: str, *, n_rows: int = PAPER_ROWS, d_sub: int = 6,
                block: int = 1024, n_boxes: int = 32, multi_pod: bool = False,
                selectivity: float = 0.02, save: bool = True,
                dtype=jnp.float32, tag: str = "") -> dict:
    mesh_name = "pod2_2x16x16" if multi_pod else "pod1_16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_shards = mesh.devices.size
    # round blocks up to a shard multiple
    nb = -(-n_rows // block)
    nb = -(-nb // n_shards) * n_shards
    # surviving-block capacity per shard (measured prune fractions on the
    # synthetic catalog are 85-99%; 2% is a conservative default)
    capacity = max(8, int(nb // n_shards * selectivity))
    result = {"arch": f"search-{variant}{tag}",
              "shape": f"rows{n_rows}_d{d_sub}_b{block}_q{n_boxes}",
              "mesh": mesh_name, "ok": False,
              "devices": int(n_shards), "capacity_blocks": capacity}
    t0 = time.time()
    try:
        rows = jax.ShapeDtypeStruct((nb, block, d_sub), dtype)
        zlo = jax.ShapeDtypeStruct((nb, d_sub), dtype)
        zhi = jax.ShapeDtypeStruct((nb, d_sub), dtype)
        blo = jax.ShapeDtypeStruct((n_boxes, d_sub), jnp.float32)
        bhi = jax.ShapeDtypeStruct((n_boxes, d_sub), jnp.float32)
        if variant == "index_query":
            fn = make_index_query_step(mesh, block, capacity)
            args = (rows, zlo, zhi, blo, bhi)
        else:
            fn = make_full_scan_step(mesh, block)
            args = (rows, blo, bhi)
        lowered = jax.jit(fn).lower(*args)
        compiled = lowered.compile()
        hlo = compiled.as_text()
        deep = hlo_analyze(hlo)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        # Analytic kernel model: zone_prune + box_scan are OUR Pallas
        # kernels (kernels/*.py) with exactly known HBM traffic — the
        # interpret-mode HLO materialises [N, B, D] compare tensors the
        # real kernels keep in VMEM, so for the search step the analytic
        # numbers are the roofline inputs (EXPERIMENTS.md §Search).
        bpe = jnp.dtype(dtype).itemsize
        nb_loc = nb // n_shards
        if variant == "index_query":
            model_bytes = (2 * nb_loc * d_sub * bpe            # zone maps
                           + capacity * block * d_sub * bpe    # gather+scan
                           + capacity * block * 4)             # counts out
            model_flops = (3.0 * nb_loc * n_boxes * d_sub      # prune cmps
                           + 3.0 * capacity * block * n_boxes * d_sub)
        else:
            model_bytes = nb_loc * block * d_sub * bpe + nb_loc * block * 4
            model_flops = 3.0 * nb_loc * block * n_boxes * d_sub
        result.update(
            ok=True,
            compile_s=round(time.time() - t0, 1),
            memory=memory_dict(mem),
            xla_flops_per_device=float(cost.get("flops", -1)),
            flops_per_device=deep["total_flops"],
            dot_flops_per_device=deep["dot_flops"],
            hbm_bytes_per_device=deep["hbm_bytes"],
            hbm_bytes_upper_per_device=deep["hbm_bytes_upper"],
            collective_bytes_per_device=deep["collective_bytes"],
            collectives=deep["collectives"],
            rows_per_device=n_rows / n_shards,
            shard_bytes=nb_loc * block * d_sub * bpe,
            kernel_model_bytes_per_device=float(model_bytes),
            kernel_model_flops_per_device=float(model_flops),
        )
        ART_DIR.mkdir(parents=True, exist_ok=True)
        with gzip.open(ART_DIR / f"search-{variant}{tag}_{mesh_name}.hlo.txt.gz",
                       "wt") as f:
            f.write(hlo)
    except Exception as e:  # noqa: BLE001
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
    if save:
        ART_DIR.mkdir(parents=True, exist_ok=True)
        (ART_DIR / f"search-{variant}{tag}_{mesh_name}.json").write_text(
            json.dumps(result, indent=1))
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default=None,
                    choices=["index_query", "full_scan"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--block", type=int, default=1024)
    ap.add_argument("--boxes", type=int, default=32)
    ap.add_argument("--d-sub", type=int, default=6)
    ap.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--selectivity", type=float, default=0.02)
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    variants = (["index_query", "full_scan"] if args.all
                else [args.variant or "index_query"])
    rc = 0
    for v in variants:
        # the scan models (DT/RF) constrain arbitrary dims: they must scan
        # the FULL 384-d feature matrix with full-width boxes (paper §4.1);
        # the index path reads one d'=6 subset index + surviving blocks.
        kw = (dict(d_sub=384, n_boxes=128) if v == "full_scan"
              else dict(d_sub=args.d_sub, n_boxes=args.boxes))
        r = run_variant(v, multi_pod=args.multi_pod, block=args.block,
                        dtype=jnp.dtype(args.dtype),
                        selectivity=args.selectivity, tag=args.tag, **kw)
        if r["ok"]:
            print(f"[ok] search/{v} {r['mesh']} "
                  f"hbm/dev={r['hbm_bytes_per_device'] / 2**30:.3f} GiB "
                  f"flops/dev={r['flops_per_device']:.3e} "
                  f"coll/dev={r['collective_bytes_per_device'] / 2**20:.1f} MiB")
        else:
            rc = 1
            print(f"[FAIL] search/{v}: {r['error']}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
