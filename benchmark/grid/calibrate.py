#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 benchmark/grid/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--controls 3] [--faults 3] [--out FILE] [--rehearse]

One process, for each seed: the program's first steps through
``run.Program.follow`` (the timed path at the timed sizes, no window: a
training cell's readings need none), then with its state freed the plain
float32 reference; for the first ``--controls`` seeds the reference put in
the program's place with its matrix products in fp8 (the control: the
precision below the bf16 the configurations state) and in bf16 (the stated
precision: a second witness, and the sound side of the control's test on
the CPU, where the program's own bf16 sums round as they do not on the
chip); and for the first ``--faults`` seeds twice more: over half the rows
with the mean taken over them, and with a learning rate of 0 (a step that
returns its state unchanged). Every reading is a number
``reference.readings`` gives; ``raw`` keeps each side's losses and
per-leaf norms, so that another number can be tried without the chip.
Not run by the benchmark's own runs. Writes
``chiprun_out/calibrate_<cell>.json`` and prints a summary on stderr.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", help="where to write the readings (default "
                    "chiprun_out/calibrate_<cell>.json)")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    _, spec, devices = run.open_cell(args.workload, args.rehearse)
    cfg, traffic, model = spec["cfg"], spec["traffic"], spec["model"]
    import mxnet_tpu  # noqa: F401
    steps, block = traffic["followed_steps"], traffic["reference_block_rows"]
    optimizer = (traffic["optimizer"]["name"], traffic["optimizer"])
    rows = []
    for n, seed in enumerate(seeds):
        t0 = time.perf_counter()
        prog = run.Program(spec, seed)
        got = prog.follow(steps)
        prog.barrier()
        state = prog.state()
        prog.free()
        del prog

        def follow(precision, half=False, frozen=False):
            batch = traffic["batch"]
            opt = optimizer if not frozen else (
                optimizer[0], dict(optimizer[1], learning_rate=0.0))
            return reference.follow(
                model.loss_sum(cfg, reference.make_dot(precision)),
                reference.make_weights(model.param_spec(cfg), seed),
                model.batches(cfg, traffic, seed), opt, steps=steps,
                block_rows=min(block, batch // 2) if half else block,
                rows=batch // 2 if half else None)

        ref = follow("f32")
        raw = {"reference": ref, "program": got}
        if n < args.controls:
            raw["control_fp8"] = follow("fp8")
            raw["plain_bf16"] = follow("bf16")
        if n < args.faults:
            raw["fault_half_batch"] = follow("f32", half=True)
            raw["fault_state_unchanged"] = follow("f32", frozen=True)
        row = {"seed": seed, "state": state, "raw": raw}
        row.update({kind: reference.readings(side, ref)
                    for kind, side in raw.items() if kind != "reference"})
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        run.log(json.dumps({k: v for k, v in row.items() if k != "raw"}))
    summary = {}
    for kind in ("program", "plain_bf16", "control_fp8",
                 "fault_half_batch", "fault_state_unchanged"):
        have = [r[kind] for r in rows if kind in r]
        if have:
            summary[kind] = {
                name: {"min": min(h[name][0] for h in have),
                       "max": max(h[name][0] for h in have)}
                for name in have[0]}
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "rehearse": args.rehearse, "summary": summary, "rows": rows}
    path = args.out or os.path.join(run.ROOT, "chiprun_out",
                                    f"calibrate_{args.workload}.json")
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    run.log(json.dumps(summary, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
