#!/usr/bin/env python3
"""The control for ``correct``: the plain reference put in the program's
place, with the catalog rows it tests membership on rounded to bfloat16
(the precision below the float32 the configurations state).

    python3 bench/control.py --workload <name> --sent 500 --seeds 11 12 13

For each seed it makes the cell's catalog and the window's label sets as
a run of the cell would (same generator, same draws), takes the same
sample of answers a run that sent ``--sent`` requests checks, and compares the bfloat16 reference's
answers with the float32 reference's, printing the numbers a run compares
(``answers_wrong``, ``answers_missing``) one JSON line per seed. The
benchmark's own runs never run this; it shows that the comparison fails
the step a later change might be tempted to take.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench.catalog import make_catalog  # noqa: E402
from bench.reference import Reference, compare  # noqa: E402
from bench.traffic.labels import LabelSets  # noqa: E402


def window_bodies(mix: dict, cluster: np.ndarray, seed: int,
                  seconds: float):
    """The label sets a run's window may send (its own stream of the
    seed), in the order sent."""
    from bench.run import WINDOW_LABELS, draw_bodies, stream

    labels = LabelSets(cluster, mix, stream(seed, WINDOW_LABELS))
    return draw_bodies(labels, mix, seconds)


def control(cfg: dict, mix: dict, seed: int, seconds: float,
            mirror_dtype=None, sent: int = None) -> dict:
    import ml_dtypes

    from bench.run import sample

    cat = cfg["catalog"]
    x, cluster = make_catalog(seed, cat["rows"], cat["dim"],
                              cat["n_clusters"], cat["spread"], cat["noise"])
    bodies = window_bodies(mix, cluster, seed, seconds)[:sent]
    records = [{"body": b} for b in bodies]
    pick = sample(records, int(mix["check_sample"]), seed)
    eng = cfg["engine"]
    kw = dict(n_subsets=eng["n_subsets"], subset_dim=eng["subset_dim"],
              subset_seed=eng["seed"])
    ref = Reference(x, **kw)
    low = Reference(x, mirror_dtype=mirror_dtype or ml_dtypes.bfloat16,
                    **kw)
    t0 = time.perf_counter()
    want = [ref.answer(bodies[i]) for i in pick]
    got = [low.answer(bodies[i]) for i in pick]
    out = compare(got, want)
    out.update(seed=seed, sampled=len(pick),
               seconds=time.perf_counter() - t0)
    return out


def main(argv=None) -> int:
    from bench.run import enable_compile_cache, load_benchmark, resolve

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--sent", type=int, default=None,
                    help="requests a run's window sent (default: as many "
                    "as it may send)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    bench = load_benchmark()
    _, _, cfg, mix = resolve(bench, args.workload)
    seconds = args.seconds or float(bench["run_seconds"])
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload,
                          **control(cfg, mix, seed, seconds,
                                     sent=args.sent)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
