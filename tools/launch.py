"""Multi-worker job launcher — the dmlc `local` tracker analogue.

ref: tools/launch.py (dmlc-core tracker): the reference starts
scheduler/server/worker processes with DMLC_* env and ssh/mpi/local
trackers.  Here there are no server/scheduler roles — the jax
coordination service (hosted by worker 0) replaces them (see
base.ensure_jax_distributed) — so launching N workers on this host is:

    python tools/launch.py -n 2 -- python tests/nightly/dist_sync_kvstore.py
    python tools/launch.py -n 2 --devices-per-worker 4 -- \
        python tests/nightly/dist_sharded_trainer.py

Each worker gets DMLC_NUM_WORKER / DMLC_WORKER_ID / DMLC_PS_ROOT_URI /
DMLC_PS_ROOT_PORT; `--devices-per-worker` additionally forces an
N-device virtual CPU platform per worker (multi-chip simulation).
Do NOT start several workers on one TPU host: a chip belongs to one
process, every worker would claim all the local chips, and all but the
first fail or hang.  One process drives all the chips of a host
(`ShardedTrainer` over `make_mesh`, as chip_smoke.py's multichip phase
does); on TPU this launcher is for one worker PER HOST (--base-rank).
Output is streamed with a `[rank]` prefix; the first failing worker
kills the rest (fail-fast, like the reference's local tracker).
Multi-HOST launches set DMLC_PS_ROOT_URI to worker 0's address and run
this once per host with --base-rank (ssh/mpi orchestration is out of
scope, as the reference delegates it to the cluster tool).
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import threading


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _stream(proc, rank, out):
    for line in proc.stdout:
        out.write("[%d] %s" % (rank, line))
        out.flush()


def launch(num_workers, command, devices_per_worker=0, base_rank=0,
           total_workers=None, coordinator=None, timeout=None,
           out=sys.stdout):
    """Start `command` num_workers times with distributed env; returns
    the first nonzero exit code (0 if all succeeded, 124 on timeout).

    total_workers: world size when launching across hosts (defaults to
    num_workers — the single-host case); every worker must see the SAME
    value or jax.distributed init rejects the out-of-range ranks.
    timeout: overall wall-clock bound in seconds (None = unbounded)."""
    import time as _time
    coordinator = coordinator or "127.0.0.1:%d" % _free_port()
    uri, port = coordinator.rsplit(":", 1)
    total = total_workers or num_workers
    procs = []
    threads = []
    try:
        for i in range(num_workers):
            rank = base_rank + i
            env = dict(os.environ)
            env.update({
                "DMLC_NUM_WORKER": str(total),
                "DMLC_WORKER_ID": str(rank),
                "DMLC_PS_ROOT_URI": uri,
                "DMLC_PS_ROOT_PORT": port,
            })
            if devices_per_worker:
                flags = env.get("XLA_FLAGS", "")
                env["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=%d"
                    % devices_per_worker).strip()
            p = subprocess.Popen(command, env=env,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append(p)
            t = threading.Thread(target=_stream, args=(p, rank, out),
                                 daemon=True)
            t.start()
            threads.append(t)
        # poll ALL workers: a late-rank crash must fail-fast even while
        # earlier ranks block at a coordination barrier
        deadline = None if timeout is None else _time.time() + timeout
        rc = 0
        while True:
            codes = [p.poll() for p in procs]
            failed = [c for c in codes if c not in (None, 0)]
            if failed and rc == 0:
                rc = failed[0]
                for q in procs:
                    if q.poll() is None:
                        q.kill()
            if all(c is not None for c in codes):
                break
            if deadline is not None and _time.time() > deadline:
                rc = rc or 124
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                break
            _time.sleep(0.2)
        for t in threads:
            t.join(timeout=5)
        return rc
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="launch N distributed workers on this host "
                    "(ref: tools/launch.py local tracker)")
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--devices-per-worker", type=int, default=0,
                    help="force an N-device virtual CPU platform per "
                         "worker (multi-chip simulation; omit on real "
                         "TPU hosts)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of worker 0's coordination service "
                         "(default: a free localhost port)")
    ap.add_argument("--base-rank", type=int, default=0,
                    help="first rank on this host (multi-host launches)")
    ap.add_argument("--total-workers", type=int, default=None,
                    help="world size across ALL hosts (default: -n; "
                         "required for multi-host launches)")
    ap.add_argument("--timeout", type=float, default=None,
                    help="overall wall-clock bound in seconds")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="worker command (prefix with --)")
    args = ap.parse_args(argv)
    cmd = args.command
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no worker command given")
    return launch(args.num_workers, cmd,
                  devices_per_worker=args.devices_per_worker,
                  base_rank=args.base_rank,
                  total_workers=args.total_workers,
                  coordinator=args.coordinator, timeout=args.timeout)


if __name__ == "__main__":
    sys.exit(main())
