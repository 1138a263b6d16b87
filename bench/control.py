#!/usr/bin/env python3
"""The control of the correctness check: the plain reference put in the
program's place, one precision step down, must come out not correct.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --queries N

For each seed it makes the configuration's corpus and the run's first
``N`` window queries exactly as ``bench/run.py`` does, answers them with the brute-force
reference computed with bf16_3x dots (three bf16 passes, the TPU's
``HIGH``) where the configuration states float32 at ``HIGHEST``, and
judges those answers with the run's own check and limits.  It prints each
number compared beside its limit, one line per seed, and exits 1 if any
seed's control came out correct.  The benchmark's own runs never run it.
``--rehearse`` uses the configuration's rehearsal size on the CPU.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax          # noqa: E402
import numpy as np  # noqa: E402

from benchlib import check, data, reference, spec  # noqa: E402

CONTROL_PRECISION = "bf16_3x"


def control_checks(cfg: dict, traffic: dict, seed: int, queries: int,
                   seconds: float, devices=None) -> dict:
    """The judge's verdict on the control's answers for one seed, the
    reference's shards spread over ``devices`` as in a run."""
    n = int(cfg["climber"]["series_len"])
    bs = int(cfg["serving"]["batch_size"])
    k = int(cfg["serving"]["k"])
    shards = data.make_corpus(cfg["corpus"], n, int(cfg["num_series"]))
    corpus = shards[0] if len(shards) == 1 else np.concatenate(shards)
    del shards
    _, window_q = data.run_queries(cfg, traffic, corpus, seconds, seed)
    issued = window_q[:queries]
    pick = data.exhaustive_pick(seed, np.arange(len(issued)), bs)
    scan = {"shards": int(cfg["corpus"]["shards"]), "devices": devices}
    d2, ids = reference.exact_topk(corpus, issued, k,
                                   precision=CONTROL_PRECISION, **scan)
    dist, gid = np.sqrt(d2).astype(np.float32), ids.astype(np.int32)
    return check.judge(corpus, queries=issued, dist=dist, gid=gid,
                       unanswered=0, exh_queries=issued[pick],
                       exh_dist=dist[pick], exh_gid=gid[pick],
                       limits=cfg["limits"], **scan)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--queries", type=int, required=True,
                    help="window queries to answer (as many as a run "
                         "answers)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    bm = spec.load_benchmark()
    cell = spec.cell(bm, args.workload)
    cfg = spec.load_config(bm, cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    if args.rehearse:
        cfg, traffic = spec.rehearsal(cfg, traffic)
    seconds = float(bm["run_seconds"])
    devices = jax.devices()[:int(cell["chips"])]
    passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        verdict = control_checks(cfg, traffic, seed, args.queries, seconds,
                                 devices)
        passed += verdict["correct"]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "precision": CONTROL_PRECISION,
                          "correct": verdict["correct"],
                          "dist_rms": verdict["dist_rms"],
                          "checks": verdict["checks"]}), flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
