#!/usr/bin/env python
"""Elastic run supervisor for the dist_sync/SPMD path (ISSUE 16).

``tools/launch_local.py`` with a recovery loop: spawns N ranks with the
DMLC_* environment, monitors liveness (process exit AND a heartbeat lease
over a lightweight control socket — workers opt in via
``incubator_mxnet_tpu.parallel.elastic.init()``), and when any rank dies
or goes silent it kills the survivors, reserves a FRESH coordinator port
(the old ``jax.distributed`` cohort is unrecoverable — re-forming the job
re-runs ``mesh.init_distributed`` with a new coordinator in every
relaunched rank), and restarts the command under a bounded restart budget
with exponential backoff.  Workers resume from their latest COMMITTED
``RunCheckpoint`` snapshot — the supervisor restarts processes; exact
resume is the workers' two-phase snapshot contract.

Usage:
    python tools/supervise.py -n 2 [--max-restarts 3] python train.py ...

Ranks are forced onto the CPU backend, like ``launch_local.py``'s: a chip
belongs to one process at a time and ONE process drives all chips of a
host, so N local ranks can never share the host's accelerator (``--env
JAX_PLATFORMS=...`` overrides for a runtime that gives each rank its own).

Per generation ``g`` the workers additionally see:

* ``MXNET_ELASTIC_SOCKET``  — this supervisor's control address
* ``MXNET_ELASTIC_RESTART`` — ``g`` (0 on the first launch), so fault
  gating (``gen=``) and the restart metrics gauge see the generation
* ``MXNET_ELASTIC_DOWNTIME_S`` — cumulative supervisor-observed downtime
  (previous generation's end → this spawn, including backoff) which
  ``parallel.elastic.init()`` folds into the goodput ledger's downtime
  bucket (ISSUE 20)

Reports exactly ONE ``ELASTIC_RESTART {json}`` line per re-formation
(and one ``ELASTIC_GIVEUP`` line if the budget runs out) — chaos tests
count these lines.  With ``--manifest PATH`` (or
``MXNET_ELASTIC_MANIFEST``) the same story is kept machine-readable: a
JSON run manifest (schema-versioned; per-generation start/end
timestamps, exit causes, downtime seconds, restart totals) atomically
rewritten at every transition, so tooling reads the run's fault history
from ONE file instead of scraping log lines.

Env defaults: ``MXNET_ELASTIC_MAX_RESTARTS`` (3),
``MXNET_ELASTIC_BACKOFF_S`` (1.0, doubled per restart, capped at 30),
``MXNET_ELASTIC_LEASE_S`` (15 — a rank that heartbeated once and then
goes silent this long is declared dead even if its process lingers,
e.g. wedged inside a collective with no watchdog).
"""
import argparse
import json
import os
import pickle
import signal
import socket
import struct
import subprocess
import sys
import threading
import time

_LEN = struct.Struct("!I")


def reserve_port():
    """Bind a free port and KEEP the socket open until the workers have
    spawned (same TOCTOU discipline as tools/launch_local.py)."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    return s, s.getsockname()[1]


def _recv_exact(conn, n):
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("peer closed")
        buf += chunk
    return buf


class ControlServer(threading.Thread):
    """Accepts worker connections; tracks the last heartbeat per rank
    (the lease table) and logs one-shot events.  One-way wire: workers
    send length-prefixed pickled tuples, nothing is replied."""

    def __init__(self):
        super().__init__(name="elastic-control", daemon=True)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(("127.0.0.1", 0))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._lock = threading.Lock()
        self._beats = {}   # rank -> time.monotonic() of last heartbeat
        self._gen = 0

    def run(self):
        while True:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(target=self._reader, args=(conn,),
                             daemon=True).start()

    def _reader(self, conn):
        gen = self._gen  # connections from a dead generation are ignored
        try:
            while True:
                (length,) = _LEN.unpack(_recv_exact(conn, _LEN.size))
                msg = pickle.loads(_recv_exact(conn, length))
                if not isinstance(msg, tuple) or not msg:
                    continue
                if msg[0] == "hb" and gen == self._gen:
                    with self._lock:
                        self._beats[int(msg[1])] = time.monotonic()
                elif msg[0] == "event":
                    _, rank, kind, payload = msg
                    print(f"[supervise] rank {rank} event {kind}: "
                          f"{json.dumps(payload, default=str)}",
                          file=sys.stderr, flush=True)
        except (ConnectionError, OSError, pickle.UnpicklingError, EOFError,
                struct.error, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def new_generation(self):
        with self._lock:
            self._gen += 1
            self._beats.clear()

    def expired(self, lease_s):
        """Ranks whose lease lapsed — only ranks that heartbeated at
        least once are on lease (plain scripts never beat)."""
        now = time.monotonic()
        with self._lock:
            return [r for r, t in self._beats.items() if now - t > lease_s]


def spawn_ranks(args, ctrl_port, gen, downtime_s=0.0):
    holder, port = reserve_port()
    ps_holder, ps_port = reserve_port()
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update(
            DMLC_ROLE="worker",
            DMLC_PS_ROOT_URI="127.0.0.1",
            DMLC_PS_ROOT_PORT=str(port),
            DMLC_NUM_WORKER=str(args.num_workers),
            DMLC_NUM_SERVER="0",
            DMLC_WORKER_ID=str(rank),
            MXNET_ELASTIC_SOCKET=f"127.0.0.1:{ctrl_port}",
            MXNET_ELASTIC_RESTART=str(gen),
            MXNET_ELASTIC_DOWNTIME_S=f"{downtime_s:.3f}",
        )
        env["MXNET_ASYNC_PS_PORT"] = str(ps_port)
        env["JAX_PLATFORMS"] = "cpu"
        for kv in args.env:
            k, _, v = kv.partition("=")
            env[k] = v
        procs.append(subprocess.Popen(args.command, env=env))
    holder.close()
    ps_holder.close()
    return procs


def kill_all(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass


def write_manifest(path, manifest):
    """Atomically (tmp + rename) rewrite the run manifest — a crashed
    supervisor leaves the last complete transition, never a torn file."""
    if not path:
        return
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
        os.replace(tmp, path)
    except OSError as e:
        print(f"[supervise] manifest write failed: {e}",
              file=sys.stderr, flush=True)


def run_generation(args, ctrl, gen, downtime_s=0.0):
    """Run one cohort to completion.  Returns ``(rc, failure)`` —
    ``(0, None)`` when every rank exits cleanly."""
    ctrl.new_generation()
    procs = spawn_ranks(args, ctrl.port, gen, downtime_s)
    try:
        while True:
            live = [p for p in procs if p.poll() is None]
            failed = [(r, p.returncode) for r, p in enumerate(procs)
                      if p.poll() is not None and p.returncode != 0]
            if failed:
                rank, code = failed[0]
                kill_all(procs)
                return code, {"reason": "rank_exit", "rank": rank,
                              "exit_code": code}
            if not live:
                return 0, None
            stale = ctrl.expired(args.lease_s)
            if stale:
                kill_all(procs)
                return 1, {"reason": "lease_expired", "rank": stale[0],
                           "lease_s": args.lease_s}
            time.sleep(0.1)
    except (KeyboardInterrupt, SystemExit):
        kill_all(procs)
        raise


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for the workers")
    ap.add_argument("--max-restarts", type=int,
                    default=int(os.environ.get(
                        "MXNET_ELASTIC_MAX_RESTARTS", "3")))
    ap.add_argument("--backoff", type=float,
                    default=float(os.environ.get(
                        "MXNET_ELASTIC_BACKOFF_S", "1.0")))
    ap.add_argument("--lease-s", type=float,
                    default=float(os.environ.get(
                        "MXNET_ELASTIC_LEASE_S", "15")))
    ap.add_argument("--manifest",
                    default=os.environ.get("MXNET_ELASTIC_MANIFEST") or None,
                    help="path for the machine-readable JSON run manifest"
                         " (generations, exit causes, downtime)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no worker command given")

    ctrl = ControlServer()
    ctrl.start()

    def on_term(signum, frame):
        raise SystemExit(128 + signum)

    signal.signal(signal.SIGTERM, on_term)

    gen = 0
    total_downtime = 0.0
    manifest = {
        "schema": 1,
        "started_unix": time.time(),
        "num_workers": args.num_workers,
        "command": list(args.command),
        "generations": [],
        "restarts": 0,
        "total_downtime_s": 0.0,
        "final": None,
        "ended_unix": None,
    }
    write_manifest(args.manifest, manifest)
    while True:
        gen_start = time.time()
        rc, failure = run_generation(args, ctrl, gen, total_downtime)
        gen_end = time.time()
        gen_rec = {"generation": gen, "start_unix": gen_start,
                   "end_unix": gen_end,
                   "exit_cause": failure or {"reason": "clean"},
                   "downtime_s": 0.0}
        manifest["generations"].append(gen_rec)
        if rc == 0:
            if gen:
                print(f"[supervise] run complete after {gen} restart(s)",
                      file=sys.stderr, flush=True)
            manifest.update(final="complete", ended_unix=time.time())
            write_manifest(args.manifest, manifest)
            return 0
        report = dict(failure or {}, event="elastic_restart", generation=gen,
                      restarts_left=args.max_restarts - gen)
        if gen >= args.max_restarts:
            report["event"] = "elastic_giveup"
            print("ELASTIC_GIVEUP " + json.dumps(report),
                  file=sys.stderr, flush=True)
            manifest.update(final="giveup", ended_unix=time.time())
            write_manifest(args.manifest, manifest)
            return rc if rc > 0 else 1
        # exactly ONE restart report line per re-formation (chaos tests
        # count these)
        print("ELASTIC_RESTART " + json.dumps(report),
              file=sys.stderr, flush=True)
        try:
            from incubator_mxnet_tpu import profiler as _profiler
            _profiler.incr("elastic_restart")
        except Exception:
            pass
        time.sleep(min(args.backoff * (2 ** gen), 30.0))
        # supervisor-observed downtime for THIS re-formation: generation
        # end (death detected + survivors killed) → the instant the next
        # cohort spawns.  The cumulative figure rides
        # MXNET_ELASTIC_DOWNTIME_S into the relaunched workers' ledgers.
        gen_rec["downtime_s"] = round(time.time() - gen_end, 3)
        total_downtime = round(total_downtime + gen_rec["downtime_s"], 3)
        manifest["restarts"] = gen + 1
        manifest["total_downtime_s"] = total_downtime
        write_manifest(args.manifest, manifest)
        gen += 1


if __name__ == "__main__":
    sys.exit(main())
