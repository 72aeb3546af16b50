"""Per-node process spawner.

Counterpart of the reference's ``launcher/launch.py`` (per-local-rank Popen
with RANK/LOCAL_RANK/WORLD_SIZE env, signal handling + process-tree kill
:115).  A TPU host runs ONE process that owns all its chips: a chip belongs
to one process at a time and nothing here binds a process to a chip, so
``slots=N > 1`` in the hostfile is for CPU simulation.  This parent only
spawns; it never asks jax for a device, so it holds no chip while its
children run.  Rendezvous env is JAX's: DS_COORDINATOR/NUM_PROCESSES/PROCESS_ID,
consumed by ``deepspeed_tpu.comm.init_distributed`` →
``jax.distributed.initialize``.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import signal
import subprocess
import sys
from collections import OrderedDict

from ..utils.logging import logger


def parse_args(args=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--world_info", type=str, required=True)
    parser.add_argument("--node_rank", type=int, default=-1)
    parser.add_argument("--node_rank_env", type=str, default="",
                        help="env var carrying the node rank (MPI/SLURM "
                             "launchers: OMPI_COMM_WORLD_RANK, SLURM_PROCID)")
    parser.add_argument("--master_addr", type=str, required=True)
    parser.add_argument("--master_port", type=int, default=29500)
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args)


def main(args=None) -> int:
    args = parse_args(args)
    if args.node_rank < 0:
        if not args.node_rank_env or args.node_rank_env not in os.environ:
            raise SystemExit(
                "launch.py needs --node_rank or --node_rank_env naming a "
                "set env var (MPI/SLURM rank variable)")
        args.node_rank = int(os.environ[args.node_rank_env])
    world_info = OrderedDict(json.loads(
        base64.urlsafe_b64decode(args.world_info.encode())))
    hosts = list(world_info)
    slots = list(world_info.values())
    num_processes = sum(slots)
    first_rank = sum(slots[:args.node_rank])
    local_slots = slots[args.node_rank]

    procs = []
    for local_rank in range(local_slots):
        env = os.environ.copy()
        rank = first_rank + local_rank
        env.update({
            "DS_COORDINATOR": f"{args.master_addr}:{args.master_port}",
            "DS_NUM_PROCESSES": str(num_processes),
            "DS_PROCESS_ID": str(rank),
            # reference-compatible names some user scripts read
            "RANK": str(rank),
            "LOCAL_RANK": str(local_rank),
            "WORLD_SIZE": str(num_processes),
            "MASTER_ADDR": args.master_addr,
            "MASTER_PORT": str(args.master_port),
        })
        cmd = [sys.executable, "-u", args.user_script] + args.user_args
        logger.info(f"rank {rank} (local {local_rank}): {' '.join(cmd)}")
        procs.append(subprocess.Popen(cmd, env=env))

    # signal handling: forward + kill the whole tree (reference :115)
    def _terminate(signum, frame):
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                # dslint: disable=signal-handler-purity — the launcher IS the teardown path: it must reap the child tree before exiting, and it exits right after (nothing left to deadlock)
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        sys.exit(128 + signum)

    signal.signal(signal.SIGINT, _terminate)
    signal.signal(signal.SIGTERM, _terminate)

    rc = 0
    for p in procs:
        p.wait()
        if p.returncode != 0:
            rc = p.returncode
            # one rank died: tear the rest down like the reference does
            for q in procs:
                if q.poll() is None:
                    q.terminate()
    return rc


if __name__ == "__main__":
    sys.exit(main())
