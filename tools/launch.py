#!/usr/bin/env python
"""Multi-process launcher (tools/launch.py + dmlc-core tracker analog).

The reference spawns scheduler + workers + servers over ssh/mpi/yarn and
wires them with DMLC_* env. TPU-native launch is serverless: every
process is a worker; one coordinator address is broadcast and
jax.distributed.initialize performs the rendezvous (the scheduler role).

    python tools/launch.py -n 4 --launcher local python train.py ...

sets, per process: MXNET_TPU_COORDINATOR, MXNET_TPU_NUM_PROCS,
MXNET_TPU_PROC_ID (DMLC_* names are also set for script compat), then
execs the command. 'local' runs all workers on this host (the analog of
dmlc local launcher used by the reference's nightly dist tests); 'ssh'
reads a hostfile.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference compat; servers do not "
                         "exist on the TPU backend (serverless allreduce)")
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("--port", type=int, default=9360)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    coord = f"127.0.0.1:{args.port}"
    procs = []

    if args.launcher == "ssh":
        if not args.hostfile:
            ap.error("--hostfile required for ssh launcher")
        hosts = [h.strip() for h in open(args.hostfile) if h.strip()]
        coord = f"{hosts[0]}:{args.port}"
        for rank in range(args.num_workers):
            host = hosts[rank % len(hosts)]
            env = " ".join(
                f"{k}={v}" for k, v in _env(coord, args.num_workers, rank,
                                            rank // len(hosts)).items())
            cmd = ["ssh", "-o", "StrictHostKeyChecking=no", host,
                   f"cd {os.getcwd()} && {env} {' '.join(args.command)}"]
            procs.append(subprocess.Popen(cmd))
    else:
        # This launcher imports no jax (standard library only), so it
        # never holds a chip itself. A chip belongs to ONE process:
        # workers sharing a TPU host must each be given their own
        # (e.g. TPU_VISIBLE_DEVICES per rank in the caller's env) or
        # run on the CPU backend, as the dist tests do.
        for rank in range(args.num_workers):
            env = dict(os.environ)
            # local launcher: every worker shares this host
            env.update(_env(coord, args.num_workers, rank, rank))
            procs.append(subprocess.Popen(args.command, env=env))

    def _term(*_):
        for p in procs:
            p.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _term)
    signal.signal(signal.SIGTERM, _term)

    rc = 0
    for p in procs:
        rc |= p.wait()
    sys.exit(rc)


def _env(coord, n, rank, local_rank=0):
    return {
        "MXNET_TPU_COORDINATOR": coord,
        "MXNET_TPU_NUM_PROCS": str(n),
        "MXNET_TPU_PROC_ID": str(rank),
        "MXNET_TPU_LOCAL_RANK": str(local_rank),
        # reference-compatible names so old scripts keep working
        "DMLC_PS_ROOT_URI": coord.split(":")[0],
        "DMLC_PS_ROOT_PORT": coord.split(":")[1],
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_ROLE": "worker",
    }


if __name__ == "__main__":
    main()
