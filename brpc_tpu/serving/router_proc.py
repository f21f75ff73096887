"""Subprocess cluster-router entry point (ISSUE 16).

The durable-session story only means something across a PROCESS death:
chaos scenario 14 killed replica engines, but the router itself — the
thing holding every session — was always the test process.  This
module is the missing half: a ``ClusterRouter`` runnable as its own
OS process over remote-only replicas, adopting (or creating) a session
WAL, so a harness can ``SIGKILL`` it mid-generation and spin up a
successor over the same WAL file:

    python -m brpc_tpu.serving.router_proc '{"wal": ..., "replicas":
        [...], ...}'

The child prints ``ROUTER_PORT <port>`` on stdout once serving, then
blocks until stdin closes (the parent's handle going away doubles as
the shutdown signal, so an orphaned router never outlives its
harness).  :func:`spawn_router` wraps the Popen + port handshake for
the press tool and tests.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Optional, Sequence


def run_router(cfg: dict):
    """Build and serve a router from a config dict (see main()); blocks
    until stdin closes.  Factored out of main() so a test can drive the
    same path in-process."""
    import brpc_tpu as brpc
    from brpc_tpu.serving.router import (ClusterRouter, SessionTable,
                                         register_router)

    wal_path = cfg.get("wal")
    sessions: Optional[SessionTable] = None
    if wal_path and os.path.exists(wal_path):
        sessions = SessionTable.recover(
            wal_path, keep_finished=int(cfg.get("keep_finished", 512)))
    router = ClusterRouter(
        list(cfg["replicas"]),
        sessions=sessions,
        wal=(wal_path if sessions is None else None),
        max_sessions=int(cfg.get("max_sessions", 256)),
        check_interval_s=float(cfg.get("check_interval_s", 0.05)),
        replicate_sessions=bool(cfg.get("replicate_sessions", True)),
        replication_factor=int(cfg.get("replication_factor", 2)),
        page_tokens=int(cfg.get("page_tokens", 8)),
        progress_timeout_s=float(cfg.get("progress_timeout_s", 30.0)),
        name=str(cfg.get("name", "router_proc")),
        timeout_ms=int(cfg.get("timeout_ms", 20_000)))
    srv = brpc.Server()
    register_router(srv, router)
    srv.start(cfg.get("host", "127.0.0.1"), int(cfg.get("port", 0)))
    return router, srv


def main(argv: Sequence[str]) -> int:
    cfg = json.loads(argv[1]) if len(argv) > 1 else {}
    router, srv = run_router(cfg)
    print(f"ROUTER_PORT {srv.port}", flush=True)
    try:
        # block until the parent closes our stdin (or kills us — the
        # whole point of this process is being killable)
        while sys.stdin.readline():
            pass
    except KeyboardInterrupt:
        pass
    router.close(timeout_s=2.0)
    srv.stop()
    srv.join()
    return 0


def spawn_router(wal_path: str, replica_addrs: Sequence[str], *,
                 timeout_s: float = 20.0, **cfg):
    """Launch a router subprocess over `wal_path` + remote replicas;
    returns ``(proc, addr)`` once the child reports its port.  Kill it
    with ``proc.kill()`` (SIGKILL — no goodbye, that's the test) and
    spawn a successor over the same ``wal_path`` to adopt the fleet."""
    cfg = dict(cfg)
    cfg["wal"] = str(wal_path)
    cfg["replicas"] = [str(a) for a in replica_addrs]
    # one process per chip: the router routes and never holds a device,
    # so its child is forced off the accelerator even when the parent
    # selected one — a child that opened the parent's chip would fail
    # or hang
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.Popen(
        [sys.executable, "-m", "brpc_tpu.serving.router_proc",
         json.dumps(cfg)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, env=env, cwd=repo_root, text=True)
    deadline = time.monotonic() + timeout_s
    line = ""
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("ROUTER_PORT "):
            port = int(line.split()[1])
            return proc, f"127.0.0.1:{port}"
        if not line and proc.poll() is not None:
            break
    proc.kill()
    raise RuntimeError(
        f"router subprocess never reported a port (last line: {line!r})")


if __name__ == "__main__":
    sys.exit(main(sys.argv))
