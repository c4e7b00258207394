#!/usr/bin/env python
"""Single-host multi-process launcher — the [U:tools/launch.py] local-mode
analog ([U:3rdparty/dmlc-core/tracker/dmlc_tracker/local.py]).

Spawns N worker processes on this host with the DMLC_* environment the
reference's tracker sets; the framework's KVStoreDist maps that onto
``jax.distributed.initialize`` (worker 0's in-process coordinator plays the
scheduler role; there is no server tier — workers are SPMD peers).

Usage:
    python tools/launch_local.py -n 2 python my_training_script.py [args...]

Differences from the reference, by design (SURVEY.md §3.4): no -s/--num-servers
(accepted, ignored, for script compat — the PS tier is subsumed by XLA
collectives), and workers are forced onto the CPU backend.  A chip belongs
to one process at a time and ONE process drives all chips of a host
(``SPMDTrainer`` over ``make_mesh()``), so N local workers can never share
the host's accelerator; ``--env JAX_PLATFORMS=...`` overrides for a pod
runtime that gives each worker its own chips.
"""
import argparse
import os
import socket
import subprocess
import sys


def reserve_port():
    """Bind a free port and KEEP the socket open (SO_REUSEADDR) until the
    workers have spawned — closing before spawn is a TOCTOU race where
    another process claims the port first."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    return s, s.getsockname()[1]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference-script compat; ignored (no PS tier)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for the workers")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no worker command given")

    holder, port = reserve_port()
    # separate ephemeral port for the async parameter server: the old
    # convention (coordinator port + 1000) collides with whatever else
    # landed on that port — the flake behind the async dist-test failures
    ps_holder, ps_port = reserve_port()
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update(
            DMLC_ROLE="worker",
            DMLC_PS_ROOT_URI="127.0.0.1",
            DMLC_PS_ROOT_PORT=str(port),
            DMLC_NUM_WORKER=str(args.num_workers),
            DMLC_NUM_SERVER=str(args.num_servers),
            DMLC_WORKER_ID=str(rank),
        )
        env.setdefault("MXNET_ASYNC_PS_PORT", str(ps_port))
        env["JAX_PLATFORMS"] = "cpu"
        for kv in args.env:
            k, _, v = kv.partition("=")
            env[k] = v
        procs.append(subprocess.Popen(args.command, env=env))

    holder.close()  # workers spawned; the coordinator (worker 0) binds next
    ps_holder.close()

    # poll instead of sequential waits: when one worker dies, its SPMD
    # peers block forever inside collectives — kill them immediately
    import time

    rc = 0
    live = list(procs)
    while live:
        for p in list(live):
            code = p.poll()
            if code is None:
                continue
            live.remove(p)
            if code != 0:
                rc = rc or code
                for q in procs:
                    if q.poll() is None:
                        q.kill()
        time.sleep(0.1)
    sys.exit(rc)


if __name__ == "__main__":
    main()
