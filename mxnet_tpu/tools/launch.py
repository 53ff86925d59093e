#!/usr/bin/env python
"""Multi-host launcher (ref: tools/launch.py + dmlc-core tracker).

The reference launches parameter-server jobs (scheduler + servers +
workers) over ssh/mpi/local with DMLC_* env wiring. The TPU equivalent
launches one worker process per host that calls
``jax.distributed.initialize`` — the JAX coordination service plays the
scheduler; GSPMD over DCN replaces ps-lite (SURVEY §5.8).

  # 4 local processes faking a 4-host job (the reference's `--launcher
  # local` test mode, used by tests/nightly/dist_sync_kvstore.py). A CPU
  # rehearsal: a chip belongs to one process and local workers are given
  # none of their own, so on a host with chips several workers that may
  # use the TPU are refused — one process drives all of a host's chips:
  JAX_PLATFORMS=cpu python tools/launch.py -n 4 --launcher local \
      python train.py

  # ssh to hosts in a hostfile:
  python tools/launch.py -n 2 -H hosts --launcher ssh python train.py

Env protocol handed to each worker (read by mxnet_tpu.kvstore 'dist_*'):
  MXTPU_COORD_ADDR  host:port of process 0 (jax coordinator)
  MXTPU_NUM_PROC    world size
  MXTPU_PROC_ID     rank
The legacy DMLC_* names are also set for script compatibility.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys


def worker_env(rank, n, coord_addr):
    env = dict(os.environ)
    env.update({
        "MXTPU_COORD_ADDR": coord_addr,
        "MXTPU_NUM_PROC": str(n),
        "MXTPU_PROC_ID": str(rank),
        # legacy names (ref: dmlc tracker env wiring)
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_ROLE": "worker",
        "DMLC_PS_ROOT_URI": coord_addr.split(":")[0],
        "DMLC_PS_ROOT_PORT": coord_addr.split(":")[1],
    })
    return env


def launch_local(args, command):
    """Spawn the job; heartbeat-monitor the workers and auto-restart the
    whole job on failure up to --max-restarts (SURVEY §5.3's TPU plan:
    'checkpoint + relaunch; add heartbeat + auto-resume in the launcher'
    — the training script resumes from its own latest checkpoint, like
    the reference's recovery story)."""
    import time

    from ..diagnostics import guard
    coord = f"127.0.0.1:{args.port}"
    # one process for each chip: local workers are given no device of
    # their own, so on a host with chips more than one that may use the
    # TPU is refused (a CPU rehearsal pins JAX_PLATFORMS=cpu)
    guard.check_chip_children([None] * args.num_workers,
                              "launch.py --launcher local")
    attempts = 0
    # bounded by the restart budget: the body returns 1 past
    # --max-restarts, so the condition is the loop's honest contract
    while attempts <= args.max_restarts:
        procs = [subprocess.Popen(
            command, env=dict(worker_env(r, args.num_workers, coord),
                              MXTPU_RESTART=str(attempts)))
            for r in range(args.num_workers)]

        def _terminate(signum, frame):
            for p in procs:
                p.terminate()
            sys.exit(1)
        signal.signal(signal.SIGINT, _terminate)
        signal.signal(signal.SIGTERM, _terminate)

        # heartbeat loop: poll liveness; one dead worker fails the job
        # (dist_sync semantics — the reference's dist_sync also cannot
        # survive a lost worker; recovery = relaunch from checkpoint).
        # Bounded by child liveness, not a while-True spin (G13): the
        # loop ends when every worker has exited or the first fails.
        failed = False
        codes = [None] * len(procs)
        while any(c is None for c in codes) and not failed:
            time.sleep(args.heartbeat_interval)
            codes = [p.poll() for p in procs]
            failed = any(c is not None and c != 0 for c in codes)
        if not failed:
            return 0
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            p.wait()
        attempts += 1
        if attempts > args.max_restarts:
            print(f"launch: job failed after {attempts - 1} restarts",
                  file=sys.stderr)
            return 1
        print(f"launch: worker died; restarting job "
              f"(attempt {attempts}/{args.max_restarts}, scripts resume "
              f"from their checkpoints; MXTPU_RESTART={attempts})",
              file=sys.stderr)
    return 1         # --max-restarts < 0: nothing was ever launched


def launch_ssh(args, command):
    if not args.hostfile:
        raise SystemExit("--launcher ssh requires -H/--hostfile")
    with open(args.hostfile) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < args.num_workers:
        raise SystemExit(f"hostfile has {len(hosts)} hosts < "
                         f"-n {args.num_workers}")
    coord = f"{hosts[0]}:{args.port}"
    procs = []
    for rank in range(args.num_workers):
        env = worker_env(rank, args.num_workers, coord)
        import shlex
        env_str = " ".join(
            f"{k}={shlex.quote(str(v))}" for k, v in env.items()
            if k.startswith(("MXTPU_", "DMLC_")))
        remote = f"cd {shlex.quote(os.getcwd())} && {env_str} " + \
            " ".join(shlex.quote(c) for c in command)
        procs.append(subprocess.Popen(["ssh", "-o",
                                       "StrictHostKeyChecking=no",
                                       hosts[rank], remote]))
    rc = 0
    for p in procs:
        rc = p.wait() or rc
    return rc


def main():
    parser = argparse.ArgumentParser(
        description="Launch a multi-host mxnet_tpu job "
                    "(ref: tools/launch.py)")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("-H", "--hostfile", type=str, default=None)
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"])
    parser.add_argument("-p", "--port", type=int, default=9099)
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="auto-restart the job this many times when a "
                             "worker dies (local launcher); scripts resume "
                             "from their own checkpoints")
    parser.add_argument("--heartbeat-interval", type=float, default=0.5,
                        help="worker liveness poll interval, seconds")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if not args.command:
        raise SystemExit("no command given")
    if args.launcher == "local":
        rc = launch_local(args, args.command)
    else:
        rc = launch_ssh(args, args.command)
    sys.exit(rc)


if __name__ == "__main__":
    main()
