#!/usr/bin/env python
"""Multi-process / multi-host launcher (reference parity: tools/launch.py
+ dmlc_tracker).

Spawns N copies of a training command with the coordinator/rank
environment wired for `mxnet_tpu.kvstore.init_distributed`, streams each
worker's output with a rank prefix, and propagates failures (first
non-zero exit kills the rest).

Usage:
    python tools/launch.py -n 2 python examples/train_mnist.py \
        --kv-store dist --smoke
    python tools/launch.py -n 4 -H hostfile --launcher ssh python train.py

Exported env (both spellings, so either bootstrap path works):
    MXTPU_COORDINATOR=host:port   MXTPU_NUM_WORKERS=N   MXTPU_WORKER_ID=i
    DMLC_PS_ROOT_URI=host  DMLC_PS_ROOT_PORT=port
    DMLC_NUM_WORKER=N      DMLC_WORKER_ID=i   DMLC_ROLE=worker
    MXTPU_RESTART_COUNT=k          (incarnation; bumped by --max-restarts)

``--max-restarts N`` makes the launcher elastic: a crashed worker is
respawned in place (same rank, incarnation incremented) instead of
tearing the job down, until its per-rank budget runs out — the process
half of the fleet recovery drill (tools/fleet_drill.py).

TPU-first design note: upstream's launcher starts a ps-lite tracker plus
scheduler/server/worker roles. Here there are only WORKERS — the XLA
distributed runtime does rendezvous at MXTPU_COORDINATOR (rank 0 binds
it) and the gradient reductions are XLA collectives over ICI/DCN, so no
tracker process exists to launch.
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys
import threading


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(base, coord_host, coord_port, n, rank):
    env = dict(base)
    env.update({
        "MXTPU_COORDINATOR": f"{coord_host}:{coord_port}",
        "MXTPU_NUM_WORKERS": str(n),
        "MXTPU_WORKER_ID": str(rank),
        "DMLC_PS_ROOT_URI": coord_host,
        "DMLC_PS_ROOT_PORT": str(coord_port),
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_ROLE": "worker",
    })
    return env


def _stream(prefix, pipe, out):
    for line in iter(pipe.readline, b""):
        out.write(f"{prefix}{line.decode(errors='replace')}")
        out.flush()
    pipe.close()


def _read_hostfile(path, n):
    with open(path) as f:
        hosts = [ln.strip().split()[0] for ln in f
                 if ln.strip() and not ln.startswith("#")]
    if not hosts:
        raise SystemExit(f"hostfile {path} is empty")
    # round-robin over hosts, upstream-style
    return [hosts[i % len(hosts)] for i in range(n)]


def launch(n, command, launcher="local", hostfile=None, env=None,
           max_restarts=0):
    """Spawn the workers; returns the first non-zero exit code (0 if all
    succeed). Importable for tests.

    ``max_restarts`` makes the launcher ELASTIC: a worker that dies with
    a non-zero exit (including a SIGKILL) is respawned in place — same
    command, same rank/coordinator env, ``MXTPU_RESTART_COUNT``
    incremented so the reborn process knows its incarnation (the fleet
    supervisor reads it — fault/fleet.py). Only a worker that exhausts
    its per-rank restart budget propagates failure and tears the job
    down; the surviving workers meanwhile keep running, detect the
    dead peer by heartbeat staleness, and agree on a rollback step, so
    the respawned incarnation rejoins at the agreed checkpoint instead
    of the whole gang restarting (docs/RELIABILITY.md "Fleet
    recovery")."""
    base_env = dict(os.environ if env is None else env)
    port = _free_port()
    hosts = _read_hostfile(hostfile, n) if hostfile else ["127.0.0.1"] * n
    coord_host = hosts[0] if launcher == "ssh" else "127.0.0.1"

    procs = [None] * n
    threads = []
    restarts = [0] * n

    def _spawn(rank):
        wenv = _worker_env(base_env, coord_host, port, n, rank)
        wenv["MXTPU_RESTART_COUNT"] = str(restarts[rank])
        if launcher == "ssh" and hosts[rank] not in ("127.0.0.1",
                                                     "localhost"):
            exports = " ".join(
                f"{k}={shlex.quote(v)}" for k, v in wenv.items()
                if k.startswith(("MXTPU_", "DMLC_", "JAX_", "XLA_",
                                 "PYTHONPATH")))
            remote = f"cd {shlex.quote(os.getcwd())} && {exports} " \
                + " ".join(shlex.quote(c) for c in command)
            p = subprocess.Popen(["ssh", "-o", "BatchMode=yes",
                                  hosts[rank], remote],
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT)
        else:
            p = subprocess.Popen(command, env=wenv,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT)
        procs[rank] = p
        t = threading.Thread(target=_stream, args=(f"[worker {rank}] ",
                                                   p.stdout, sys.stdout),
                             daemon=True)
        t.start()
        threads.append(t)

    for rank in range(n):
        _spawn(rank)

    rc = 0
    try:
        # poll until every worker exits cleanly; a non-zero exit is
        # respawned while its restart budget lasts, and propagates
        # (killing the rest) once it is exhausted
        import time
        pending = set(range(n))
        while pending:
            for i in list(pending):
                r = procs[i].poll()
                if r is None:
                    continue
                if r != 0 and restarts[i] < max_restarts:
                    restarts[i] += 1
                    print(f"[launch] worker {i} exited rc={r}; "
                          f"respawning (restart {restarts[i]}/"
                          f"{max_restarts})", file=sys.stderr)
                    _spawn(i)
                    continue
                pending.discard(i)
                if r != 0 and rc == 0:
                    rc = r
                    print(f"[launch] worker {i} exited rc={r}; "
                          "terminating the rest", file=sys.stderr)
                    for j in pending:
                        procs[j].terminate()
            time.sleep(0.2)
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
        for t in threads:
            t.join(timeout=5)
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job",
        usage="launch.py -n N [-H hostfile] [--launcher local|ssh] "
              "command ...")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-H", "--hostfile", default=None)
    ap.add_argument("--launcher", choices=("local", "ssh"), default="local")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="respawn a crashed worker in place up to N times "
                         "(MXTPU_RESTART_COUNT incremented) before its "
                         "failure propagates")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    if args.launcher == "ssh" and not args.hostfile:
        ap.error("--launcher ssh needs -H hostfile")
    return launch(args.num_workers, args.command, launcher=args.launcher,
                  hostfile=args.hostfile, max_restarts=args.max_restarts)


if __name__ == "__main__":
    sys.exit(main())
