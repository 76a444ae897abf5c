#!/usr/bin/env python
"""Multi-process / multi-host job launcher (reference: tools/launch.py —
dmlc-tracker submitting N workers + servers + scheduler over
local/ssh/mpi/sge/yarn).

TPU-native redesign: there are no parameter servers — every process is an
SPMD worker in one global mesh (`jax.distributed`). The launcher keeps the
reference CLI (`-n` workers, `--launcher local|ssh`) and env-var contract
(DMLC_NUM_WORKER / DMLC_WORKER_ID / DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT,
consumed by mxnet_tpu.parallel.dist.initialize), so reference launch
scripts port unchanged:

    JAX_PLATFORMS=cpu python tools/launch.py -n 4 --launcher local \
        python train.py

One process per host owns that host's chips (all four of a v5e host,
through a mesh), so ``--launcher local`` starts several workers only when
they are pinned to the CPU; across hosts use ``--launcher ssh``, one
worker per host.
"""
from __future__ import annotations

import argparse
import os
import shlex
import signal
import subprocess
import sys


def _may_own_accelerator(env) -> bool:
    """True when the child could hold the accelerator client: JAX
    reaches for the chip unless its platform is pinned to the CPU."""
    return env.get("JAX_PLATFORMS", "").lower() != "cpu"


def _graceful_stop(procs, grace=None) -> None:
    """Dead-rank cleanup: SIGTERM -> grace window -> SIGKILL, so no
    worker outlives the launcher (a straggler would keep holding its
    chip, or a collective waiting on the dead rank)."""
    import time
    if grace is None:
        grace = float(os.environ.get("MXNET_LAUNCH_KILL_GRACE", "10"))
    for p in procs:
        if p.poll() is None:
            p.terminate()
    deadline = time.time() + grace
    while time.time() < deadline:
        if all(p.poll() is not None for p in procs):
            return
        time.sleep(0.1)
    for p in procs:
        if p.poll() is None:
            p.kill()


def launch_local(n: int, cmd, port: int) -> int:
    """Spawn n local worker processes sharing a coordinator (the analog of
    the reference's `--launcher local` multi-process rig used by
    tests/nightly/dist_sync_kvstore.py).

    A chip belongs to one process at a time, and every local child gets
    the same environment — n accelerator-owning children would all reach
    for the same chips and fail or hang. So more than one is refused:
    ONE process drives all of a host's chips through a mesh
    (``parallel.make_mesh``); the local rig is for CPU-pinned workers."""
    if n > 1 and _may_own_accelerator(os.environ):
        raise SystemExit(
            f"launch: refusing to start {n} local workers that may each "
            "claim this host's accelerator (a chip belongs to one "
            "process). Drive the host's chips from ONE process through "
            "a mesh (mxnet_tpu.parallel.make_mesh), or pin the local "
            "rig to the CPU with JAX_PLATFORMS=cpu.")
    procs = []
    for i in range(n):
        env = dict(os.environ)
        env.update({
            "DMLC_NUM_WORKER": str(n),
            "DMLC_WORKER_ID": str(i),
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
        })
        procs.append(subprocess.Popen(cmd, env=env))

    def _kill(*_):
        _graceful_stop(procs)
        sys.exit(1)

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    return _wait_all(procs)


def launch_ssh(n: int, cmd, hostfile: str, port: int) -> int:
    """One worker per host line in ``hostfile`` (reference ssh launcher)."""
    with open(hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < n:
        raise SystemExit(f"hostfile has {len(hosts)} hosts, need {n}")
    coord = hosts[0]
    procs = []
    for i in range(n):
        envs = " ".join([
            f"DMLC_NUM_WORKER={n}", f"DMLC_WORKER_ID={i}",
            "DMLC_ROLE=worker", f"DMLC_PS_ROOT_URI={coord}",
            f"DMLC_PS_ROOT_PORT={port}",
        ])
        remote = f"cd {shlex.quote(os.getcwd())} && {envs} " + \
            " ".join(shlex.quote(c) for c in cmd)
        # -t allocates a PTY so killing the ssh client sends SIGHUP to the
        # remote command instead of orphaning it on every host
        procs.append(subprocess.Popen(["ssh", "-tt", "-o",
                                       "StrictHostKeyChecking=no",
                                       hosts[i], remote]))

    def _kill(*_):
        _graceful_stop(procs)
        sys.exit(1)

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    return _wait_all(procs)


def _wait_all(procs) -> int:
    """Wait on all workers; when one fails, gracefully stop the siblings
    (they may be blocked in a collective waiting for the dead rank
    forever)."""
    import time
    rc = 0
    alive = list(procs)
    while alive:
        for p in list(alive):
            r = p.poll()
            if r is None:
                continue
            alive.remove(p)
            if r != 0:
                rc = rc or r
                _graceful_stop(procs)
        time.sleep(0.05)
    return rc


def main():
    ap = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("-p", "--port", type=int, default=9091)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.launcher == "local":
        rc = launch_local(args.num_workers, args.command, args.port)
    else:
        if not args.hostfile:
            ap.error("--launcher ssh requires --hostfile")
        rc = launch_ssh(args.num_workers, args.command, args.hostfile,
                        args.port)
    sys.exit(rc)


if __name__ == "__main__":
    main()
