"""Distributed job launcher (parity: tools/launch.py — dmlc_tracker in the
reference; here the roles map to jax.distributed processes).

The reference forks scheduler+server+worker processes wired by DMLC_* env
vars over ssh/mpi/yarn.  TPU-native distributed training has no parameter
servers — every process is a worker attached to its TPU hosts and the
collectives ride ICI/DCN — so the launcher's job shrinks to: start N
processes with the jax.distributed coordinator env (local mode), or print
the per-host commands (ssh mode).  DMLC_NUM_WORKER/DMLC_WORKER_ID are also
set so kvstore='dist_*' code reading the reference's env protocol works.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def launch_local(args, command):
    """Run n workers as local processes (the reference's `--launcher local`
    CI pattern, SURVEY.md §4.6)."""
    procs = []
    coordinator = "localhost:%d" % args.port
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update({
            "JAX_COORDINATOR_ADDRESS": coordinator,
            "JAX_NUM_PROCESSES": str(args.num_workers),
            "JAX_PROCESS_ID": str(rank),
            "DMLC_ROLE": "worker",
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_WORKER_ID": str(rank),
        })
        if args.num_workers > 1:
            # a chip belongs to ONE process: N workers on one host are
            # the cpu harness of a multi-host job, never N claimants of
            # the local chip (a lone worker keeps the default backend)
            env["JAX_PLATFORMS"] = "cpu"
        procs.append(subprocess.Popen(command, shell=True, env=env))

    def _kill(signum, frame):
        for p in procs:
            p.terminate()
        sys.exit(1)

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


def launch_ssh(args, command):
    """Print/execute per-host commands over ssh."""
    hosts = []
    with open(args.hostfile) as f:
        for line in f:
            host = line.strip()
            if host:
                hosts.append(host)
    assert len(hosts) >= args.num_workers, "not enough hosts"
    coordinator = "%s:%d" % (hosts[0], args.port)
    procs = []
    for rank in range(args.num_workers):
        env = ("JAX_COORDINATOR_ADDRESS=%s JAX_NUM_PROCESSES=%d "
               "JAX_PROCESS_ID=%d DMLC_ROLE=worker DMLC_NUM_WORKER=%d "
               "DMLC_WORKER_ID=%d" % (coordinator, args.num_workers, rank,
                                      args.num_workers, rank))
        remote = "ssh -o StrictHostKeyChecking=no %s 'cd %s && %s %s'" % (
            hosts[rank], os.getcwd(), env, command)
        if args.dry_run:
            print(remote)
        else:
            procs.append(subprocess.Popen(remote, shell=True))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    return rc


if __name__ == "__main__":
    parser = argparse.ArgumentParser(
        description="Launch a distributed training job")
    parser.add_argument("-n", "--num-workers", required=True, type=int)
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"])
    parser.add_argument("-H", "--hostfile", type=str,
                        help="hostfile for ssh launcher")
    parser.add_argument("--port", type=int, default=9357)
    parser.add_argument("--dry-run", action="store_true")
    parser.add_argument("command", nargs="+")
    args = parser.parse_args()
    cmd = " ".join(args.command)
    if args.launcher == "local":
        sys.exit(launch_local(args, cmd))
    sys.exit(launch_ssh(args, cmd))
