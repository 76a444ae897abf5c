"""Minimal 2-process smoke worker: protects jax.distributed CPU bring-up
(the dependency every dist kvstore feature rides) inside the QUICK gate —
tiny arrays, two collectives, done. The full feature matrix lives in
dist_kvstore_worker.py (slow suite).

Capability note: some jaxlib builds cannot RUN multi-process collectives
on the CPU backend at all ("Multiprocess computations aren't implemented
on the CPU backend").  That is a backend capability, not a framework
regression — launch + jax.distributed.initialize + kvstore construction
(the things a jax/jaxlib bump actually breaks) still execute here, and
the worker records ``{"capability": "no-cpu-multiprocess"}`` so the test
can skip the collective assertions with a documented reason instead of
failing the quick gate."""
import json
import os
import sys

os.environ["XLA_FLAGS"] = " ".join(
    f for f in os.environ.get("XLA_FLAGS", "").split()
    if not f.startswith("--xla_force_host_platform_device_count"))
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

import numpy as onp  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402
from mxnet_tpu.parallel import dist  # noqa: E402


def _write(outdir, rank, payload):
    with open(os.path.join(outdir, f"smoke{rank}.json"), "w") as f:
        json.dump(payload, f)


def main(outdir):
    dist.initialize()
    rank = jax.process_index()
    kv = mx.kvstore.create("dist_sync")
    g = nd.array(onp.full((3,), float(rank + 1), "float32"))
    try:
        kv.pushpull("g", g)
        g.wait_to_read()
    except Exception as e:
        if "aren't implemented on the CPU backend" in str(e):
            # init + store construction proven; the backend simply has
            # no CPU multi-process collective runtime
            _write(outdir, rank, {"rank": rank,
                                  "capability": "no-cpu-multiprocess",
                                  "error": str(e)[:300]})
            return
        raise
    a = nd.array(onp.full((2,), float(rank + 1), "float32"))
    b = nd.array(onp.full((5,), 2.0 * (rank + 1), "float32"))
    kv.pushpull_list([0, 1], [a, b])
    out = {"rank": rank, "sum": g.asnumpy().tolist(),
           "fused": [a.asnumpy().tolist(), b.asnumpy().tolist()],
           "stats": dict(kv.stats)}
    _write(outdir, rank, out)


if __name__ == "__main__":
    main(sys.argv[1])
