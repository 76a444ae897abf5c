#!/usr/bin/env python
"""Per-operator micro-benchmark (reference benchmark/opperf/: runs every
registered op with synthetic shapes and reports per-op latency).

Sweeps a representative slice of the nd op surface — MXU ops (dot, FC,
conv), reductions, normalizations, elementwise, shape ops — at small and
large synthetic shapes. For each (op, shape): median wall microseconds
over ``--iters`` timed calls (after warmup, each ended by a host fetch)
plus achieved GFLOP/s from an analytic FLOP count where one is
meaningful.

Prints one JSON line per measurement and a trailing summary line. A CPU
reference output is committed at benchmark/opbench.reference.json for
regression eyeballing (absolute numbers are machine-dependent; the
structure and op coverage are the contract).

Run: python benchmark/opbench.py [--iters 30] [--ops dot,conv,...]
"""
import argparse
import json
import os
import sys
import time

import numpy as onp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _cases():
    """(name, build() -> (fn, flops)) — fn is a nullary closure over
    prebuilt device arrays; flops=None for ops without a natural count."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rng = onp.random.RandomState(0)

    def arr(*shape):
        return nd.array(rng.randn(*shape).astype("float32"))

    cases = []

    def add(name, fn, flops=None):
        cases.append((name, fn, flops))

    for n in (256, 1024):
        a, b = arr(n, n), arr(n, n)
        add(f"dot_{n}x{n}", lambda a=a, b=b: nd.dot(a, b), 2 * n ** 3)
    x = arr(64, 512)
    w = arr(512, 512)
    bias = arr(512)
    add("fully_connected_64x512",
        lambda x=x, w=w, b=bias: nd.FullyConnected(x, w, b, num_hidden=512),
        2 * 64 * 512 * 512)
    for hw, c in ((32, 32), (64, 64)):
        xc = arr(8, c, hw, hw)
        wc = arr(c, c, 3, 3)
        flops = 2 * 8 * c * c * 9 * hw * hw
        add(f"conv3x3_{c}c_{hw}px",
            lambda xc=xc, wc=wc: nd.Convolution(
                xc, wc, kernel=(3, 3), pad=(1, 1), num_filter=wc.shape[0]),
            flops)
    xp = arr(8, 32, 64, 64)
    add("maxpool2x2", lambda xp=xp: nd.Pooling(xp, kernel=(2, 2),
                                               stride=(2, 2),
                                               pool_type="max"))
    g, beta = arr(64), arr(64)
    mm, mv = arr(64), nd.array(onp.abs(rng.randn(64)).astype("float32"))
    xb = arr(32, 64, 16, 16)
    add("batchnorm_infer",
        lambda xb=xb, g=g, b=beta, m=mm, v=mv: nd.BatchNorm(
            xb, g, b, m, v, use_global_stats=True),
        4 * xb.size)
    xl = arr(64, 512)
    add("layernorm", lambda xl=xl, g2=arr(512), b2=arr(512):
        nd.LayerNorm(xl, g2, b2), 8 * 64 * 512)
    for n in (1 << 16, 1 << 22):
        xe = arr(n)
        add(f"relu_{n}", lambda xe=xe: nd.relu(xe), n)
        add(f"exp_{n}", lambda xe=xe: nd.exp(xe), n)
    xa, xb2 = arr(1 << 20), arr(1 << 20)
    add("broadcast_add_1M", lambda a=xa, b=xb2: a + b, 1 << 20)
    xs = arr(128, 1000)
    add("softmax_128x1000", lambda xs=xs: nd.softmax(xs), 5 * 128 * 1000)
    xr = arr(1 << 20)
    add("sum_1M", lambda xr=xr: nd.sum(xr), 1 << 20)
    xt = arr(512, 512)
    add("transpose_512", lambda xt=xt: nd.transpose(xt))
    add("concat_2x1M", lambda a=xa, b=xb2: nd.concat(a, b, dim=0))
    xk = arr(1024, 128)
    add("topk_1024x128", lambda xk=xk: nd.topk(xk, k=8, axis=-1))
    xso = arr(4096, 64)
    add("sort_4096x64", lambda xso=xso: nd.sort(xso, axis=-1))
    add("embedding_64x128",
        lambda idx=nd.array(rng.randint(0, 1000, (64, 128))
                            .astype("int32")), w=arr(1000, 64):
        nd.Embedding(idx, w, input_dim=1000, output_dim=64))
    # second tier: deconv, batched matmul, activations, shape/index ops
    xd = arr(8, 32, 16, 16)
    wd = arr(32, 16, 2, 2)
    # kernel 2 stride 2: each INPUT pixel contributes k*k taps; counting
    # by inputs avoids over-counting the stride-partitioned output
    add("deconv2x2_stride2",
        lambda xd=xd, wd=wd: nd.Deconvolution(
            xd, wd, kernel=(2, 2), stride=(2, 2), num_filter=16),
        2 * 8 * 32 * 16 * 4 * 16 * 16)
    ba, bb = arr(64, 128, 64), arr(64, 64, 128)
    add("batch_dot_64x128x64",
        lambda a=ba, b=bb: nd.batch_dot(a, b), 2 * 64 * 128 * 64 * 128)
    xg = arr(64, 1024)
    for act in ("sigmoid", "tanh", "gelu"):
        add(f"{act}_64x1024",
            lambda xg=xg, act=act: getattr(nd, act)(xg), 64 * 1024)
    add("log_softmax_128x1000",
        lambda xs=xs: nd.log_softmax(xs), 5 * 128 * 1000)
    add("avgpool2x2", lambda xp=xp: nd.Pooling(
        xp, kernel=(2, 2), stride=(2, 2), pool_type="avg"))
    add("global_avg_pool", lambda xp=xp: nd.Pooling(
        xp, global_pool=True, pool_type="avg"))
    xt2 = arr(1 << 18)
    add("cumsum_256k", lambda x=xt2: nd.cumsum(x))
    cond = xa > 0  # prebuilt: the timed fn measures where alone
    add("where_1M", lambda c=cond, a=xa, b=xb2: nd.where(c, a, b),
        1 << 20)
    add("take_rows", lambda w=arr(4096, 256),
        idx=nd.array(rng.randint(0, 4096, 1024).astype("int32")):
        nd.take(w, idx))
    add("tile_2x", lambda x=arr(512, 128): nd.tile(x, reps=(2, 2)))
    add("pad_edge", lambda x=arr(8, 16, 32, 32): nd.pad(
        x, mode="edge", pad_width=(0, 0, 0, 0, 2, 2, 2, 2)))
    add("one_hot_32k", lambda idx=nd.array(
        rng.randint(0, 512, 32768).astype("int32")):
        nd.one_hot(idx, depth=512))
    T, N, C, H = 32, 16, 64, 128
    from mxnet_tpu.ops.rnn import rnn_packed_param_size
    npk = rnn_packed_param_size("lstm", C, H, 1, False)
    xr2 = arr(T, N, C)
    pv = arr(npk)
    add("lstm_T32_N16_H128",
        lambda x=xr2, p=pv: nd.RNN(x, p, state_size=H, mode="lstm"),
        2 * T * N * 4 * H * (C + H))
    return cases


def _flush(out):
    x = out[0] if isinstance(out, (list, tuple)) else out
    x.asnumpy()  # host fetch: waits for the device


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--ops", type=str, default="",
                    help="comma-separated substring filter")
    args = ap.parse_args()
    import jax
    backend = jax.default_backend()
    wanted = [s for s in args.ops.split(",") if s]

    results = []
    for name, fn, flops in _cases():
        if wanted and not any(w in name for w in wanted):
            continue
        for _ in range(args.warmup):
            _flush(fn())
        times = []
        for _ in range(args.iters):
            t0 = time.perf_counter()
            _flush(fn())
            times.append(time.perf_counter() - t0)
        med = float(onp.median(times))
        rec = {"op": name, "usec": round(med * 1e6, 1),
               "gflops": round(flops / med / 1e9, 2) if flops else None}
        results.append(rec)
        print(json.dumps(rec), flush=True)
    print(json.dumps({"summary": True, "backend": backend,
                      "ops_measured": len(results),
                      "total_usec": round(sum(r["usec"]
                                              for r in results), 1)}))


if __name__ == "__main__":
    main()
