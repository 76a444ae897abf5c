#!/usr/bin/env python
"""Measure kvstore aggregate push/pull bandwidth on model-shaped arrays.

Reference analog: tools/bandwidth/measure.py — same experiment (push a
network's gradient set through a kvstore, pull it back, report GB/s and
the error vs a serial reduction), re-targeted at this framework's
kvstore types ('local', 'tpu', 'dist*') instead of GPU device lists,
in a single process.
"""
import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np


def parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description="command for benchmark kvstore bandwidth")
    parser.add_argument("--network", type=str, default="resnet18_v1",
                        help="gluon model_zoo.vision model whose "
                        "parameter shapes are pushed")
    parser.add_argument("--kv-store", type=str, default="tpu",
                        help="the kvstore type: local | tpu | dist_sync")
    parser.add_argument("--num-batches", type=int, default=5)
    parser.add_argument("--disp-batches", type=int, default=1)
    parser.add_argument("--test-results", type=int, default=1,
                        help="whether to check reduction correctness")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--optimizer", type=str, default="None",
                        help="optimizer applied inside the kvstore; "
                        "None means plain reduce")
    parser.add_argument("--gc-type", type=str, default="none",
                        help="gradient compression: none | 2bit | 1bit")
    args = parser.parse_args(argv)
    logging.info(args)
    return args


def get_shapes(network, num_classes):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    net = getattr(vision, network)(classes=num_classes)
    net.initialize()
    net(mx.nd.array(np.zeros((1, 3, 32, 32), "float32")))
    return [tuple(p.shape) for p in net.collect_params().values()
            if p._data is not None and p.grad_req != "null"]


def error(result, expected):
    num = sum(float(np.abs(r.asnumpy() - e).sum()) for r, e in
              zip(result, expected))
    den = sum(float(np.abs(e).sum()) for e in expected)
    return num / max(den, 1e-12)


def run(args):
    import mxnet_tpu as mx

    kv = mx.kvstore.create(args.kv_store)
    if args.gc_type != "none":
        kv.set_gradient_compression({"type": args.gc_type})
    if args.optimizer not in (None, "None"):
        kv.set_optimizer(mx.optimizer.create(args.optimizer))

    shapes = get_shapes(args.network, args.num_classes)
    size = sum(int(np.prod(s)) for s in shapes)
    rng = np.random.RandomState(0)
    grads = [mx.nd.array(rng.uniform(-1, 1, s).astype("float32"))
             for s in shapes]
    outs = [mx.nd.zeros(s) for s in shapes]
    keys = list(range(len(shapes)))
    for k, g in zip(keys, grads):
        kv.init(k, mx.nd.zeros(g.shape))

    # bytes moved per batch: one push + one pull of every array
    nbytes = 2 * 4 * size
    times = []
    for b in range(args.num_batches):
        t0 = time.perf_counter()
        for k, g, o in zip(keys, grads, outs):
            kv.push(k, g)
            kv.pull(k, out=o)
        outs[-1].asnumpy()  # host sync
        dt = time.perf_counter() - t0
        times.append(dt)
        if (b + 1) % args.disp_batches == 0:
            logging.info("batch %d: %.3f s, %.2f GB/s",
                         b, dt, nbytes / dt / 1e9)

    if args.test_results and args.optimizer in (None, "None") and \
            args.gc_type == "none":
        expected = [g.asnumpy() * kv.num_workers for g in grads]
        err = error(outs, expected)
        logging.info("reduction error: %.2e", err)
        assert err < 1e-5, f"kvstore reduction mismatch: {err}"

    best = min(times)
    result = {"network": args.network, "kv_store": args.kv_store,
              "params_mb": round(size * 4 / 1e6, 1),
              "best_sec_per_batch": round(best, 4),
              "gbps": round(nbytes / best / 1e9, 2)}
    logging.info("result: %s", result)
    return result


def main(argv=None):
    logging.basicConfig(level=logging.INFO)
    run(parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
