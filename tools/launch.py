#!/usr/bin/env python
"""Multi-process launcher (reference: `tools/launch.py:10-38`, which drives
dmlc_tracker to set DMLC_ROLE/DMLC_PS_ROOT_URI and exec the user script on
every node).

TPU-native: there are no server/scheduler roles — every process is a worker
that joins the jax multi-process runtime. This launcher sets the rendezvous
env (COORDINATOR_ADDRESS, NUM_PROCESSES, PROCESS_ID) and execs the command
N times:

- `--launcher local` (default): N processes on this machine, used by the
  distributed kvstore tests (the analogue of the reference's
  `tests/nightly/dist_sync_kvstore.py` local runs). The ranks must be
  pinned to the CPU by name (`JAX_PLATFORMS=cpu`): a TPU chip belongs to
  one process, and N local ranks would each claim every chip of the host.
  On a TPU host the layout is ONE process over all its chips.
- `--launcher ssh -H hostfile`: one process per host over ssh (each TPU
  host in a pod slice runs the same program; jax discovers the global
  topology at initialize()).

Fail-fast: if any worker exits non-zero, the remaining workers are killed
(the reference tracker kills the process group on first failure).

Usage: python tools/launch.py -n 2 [--port 9123] python train.py ...
"""
from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
import time


def _kill_group(p, sig):
    try:
        os.killpg(os.getpgid(p.pid), sig)
    except (ProcessLookupError, PermissionError, OSError):
        p.kill() if sig == 9 else p.terminate()


def _wait_fail_fast(procs):
    """Wait for all procs; on first non-zero exit, kill the remaining
    process groups (SIGTERM, then SIGKILL after a grace period — workers
    blocked in a native rendezvous ignore SIGTERM)."""
    import signal

    rc = 0
    pending = list(procs)
    deadline = None
    while pending:
        for p in list(pending):
            code = p.poll()
            if code is None:
                continue
            pending.remove(p)
            if code != 0 and rc == 0:
                rc = code
                deadline = time.monotonic() + 10.0
                for q in pending:
                    _kill_group(q, signal.SIGTERM)
        if deadline is not None and time.monotonic() > deadline:
            for q in pending:
                _kill_group(q, signal.SIGKILL)
            deadline = float("inf")
        time.sleep(0.05)
    return rc


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=["local", "ssh"], default="local")
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("--port", type=int, default=9123)
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE to pass through")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")

    extra = dict(kv.split("=", 1) for kv in args.env)

    if args.launcher == "local":
        platforms = dict(os.environ, **extra).get("JAX_PLATFORMS", "")
        if platforms.split(",")[0] != "cpu":
            sys.exit(
                "launch.py --launcher local: the ranks are not pinned to the "
                f"CPU (JAX_PLATFORMS={platforms!r}). A TPU chip belongs to "
                "one process, and every local rank would claim every chip "
                "of this host. Set JAX_PLATFORMS=cpu (or --env "
                "JAX_PLATFORMS=cpu) for tests and rehearsals; on a TPU host "
                "run ONE process over all its chips (parallel.DataParallel "
                "over a Mesh, serve.serve_mesh), and one process per host "
                "with --launcher ssh.")
        coordinator = f"127.0.0.1:{args.port}"
        procs = []
        for rank in range(args.num_workers):
            env = dict(os.environ, **extra)
            env.update(COORDINATOR_ADDRESS=coordinator,
                       NUM_PROCESSES=str(args.num_workers),
                       PROCESS_ID=str(rank),
                       # all local-launcher ranks share this host
                       MXNET_LOCAL_RANK=str(rank))
            procs.append(subprocess.Popen(args.command, env=env,
                                          start_new_session=True))
        sys.exit(_wait_fail_fast(procs))

    if args.hostfile is None:
        ap.error("--launcher ssh requires -H/--hostfile")
    hosts = [h.strip() for h in open(args.hostfile)
             if h.strip() and not h.startswith("#")]
    if len(hosts) < args.num_workers:
        sys.exit(f"hostfile has {len(hosts)} hosts < -n {args.num_workers}")
    coordinator = f"{hosts[0]}:{args.port}"
    procs = []
    for rank in range(args.num_workers):
        envs = " ".join(
            [f"COORDINATOR_ADDRESS={shlex.quote(coordinator)}",
             f"NUM_PROCESSES={args.num_workers}", f"PROCESS_ID={rank}",
             # rank within the host: a hostfile may repeat a host to
             # place several ranks on it
             f"MXNET_LOCAL_RANK={hosts[:rank].count(hosts[rank])}"]
            + [f"{k}={shlex.quote(v)}" for k, v in extra.items()])
        cmd = " ".join(shlex.quote(c) for c in args.command)
        procs.append(subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", hosts[rank],
             f"cd {shlex.quote(os.getcwd())} && {envs} {cmd}"],
            start_new_session=True))
    sys.exit(_wait_fail_fast(procs))


if __name__ == "__main__":
    main()
