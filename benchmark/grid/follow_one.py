#!/usr/bin/env python3
"""ONE follow of a cell's plain reference at one precision, a process of
its own.

    python3 benchmark/grid/follow_one.py --workload <cell> --seed N \
        --precision f32|bf16|fp8 [--against FILE] [--out FILE] [--rehearse]

``calibrate.py`` makes the float32 reference, the fp8 control and the
bf16 witness of a seed in one process; at nemotron-3-nano-30b-a3b a follow
holds 21 GB (``reference.follow`` does not donate: PERF.md section 7) and
three met the host's 40 GiB. This makes one and writes its losses and
per-leaf norms to ``--out`` (default
``chiprun_out/follow_<cell>_<precision>_<seed>.json``).
With ``--against`` (the float32 follow of the same seed, from an earlier
process) it also prints ``reference.readings`` of this follow against that
one and what ``reference.compare`` makes of them under the cell's limits:
the fp8 control has to come out not correct, the bf16 witness correct.
Not run by the benchmark's own runs.
"""
import argparse
import json
import os
import sys
import time

import run          # noqa: E402  (captures descriptor 1 like a run does)
import reference    # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--precision", choices=("f32", "bf16", "fp8"),
                    required=True)
    ap.add_argument("--against", help="the float32 follow of the same seed")
    ap.add_argument("--out", help="where to write this follow")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _, spec, _ = run.open_cell(args.workload, args.rehearse)
    cfg, traffic, model = spec["cfg"], spec["traffic"], spec["model"]
    t0 = time.perf_counter()
    out = reference.follow(
        model.loss_sum(cfg, reference.make_dot(args.precision)),
        reference.make_weights(model.param_spec(cfg), args.seed),
        model.batches(cfg, traffic, args.seed),
        (traffic["optimizer"]["name"], traffic["optimizer"]),
        steps=traffic["followed_steps"],
        block_rows=traffic["reference_block_rows"])
    path = args.out or os.path.join(
        run.ROOT, "chiprun_out",
        f"follow_{args.workload}_{args.precision}_{args.seed}.json")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f)
    run.log(f"{args.precision} seed {args.seed}: {len(out['loss'])} steps "
            f"followed in {time.perf_counter() - t0:.1f}s, loss "
            f"{out['loss']}")
    if args.against:
        with open(args.against) as f:
            ref = json.load(f)
        correct, compared = reference.compare(out, ref, spec["limits"])
        run.log(json.dumps({
            "seed": args.seed, "precision": args.precision,
            "readings": reference.readings(out, ref),
            "correct": correct, "compared": compared}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
