"""Replica process: restore → warm → listen → drain on SIGTERM.

``python -m heat_tpu.serve.net.replica --checkpoint CKPT [--mesh N]
[--host H] [--port P]`` is the unit :class:`~.pool.ReplicaPool` spawns
N times. Lifecycle:

1. (optional) force an ``N``-device virtual CPU mesh *before* the
   backend initializes — the same dance as the bench harness ``--mesh``;
2. :meth:`heat_tpu.serve.Server.restore` the endpoint checkpoint (the
   crash-recovery path: a replica is rebuilt from the CRC-verified
   resilience checkpoint, never refit — restored answers are
   bit-identical);
3. ``warmup()`` the whole batch ladder. Replicas share JAX's persistent
   compilation cache (``program_cache.enable_persistent_cache``: the
   inherited ``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``),
   so this deserializes what a sibling or an earlier run compiled
   instead of compiling, and a shared ``HEAT_TPU_TUNE_DB`` warm-starts
   the knob overlay with zero measured trials (PR 3 / PR 11 — "a second
   process starts compiled *and* tuned", now load-bearing for
   horizontal scale);
4. start the :class:`~.transport.HttpFront` (which arms the
   steady-state CompileWatcher ``/stats`` exposes) and print ONE
   machine-readable **ready line** on stdout::

       {"ready": true, "port": <bound>, "pid": ..., "warmup": {...}}

5. block until **SIGTERM/SIGINT**, then shut down gracefully: shed new
   requests 503/``draining`` (the router retries siblings), finish
   every queued + in-flight batch, ``telemetry.flush()`` (the final
   counter/watermark snapshot reaches the sink — a killed in-process
   server used to drop it), and ``exit 0``. The pool's drain-then-kill
   removal is exactly one SIGTERM + wait.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

__all__ = ["main"]


def _build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m heat_tpu.serve.net.replica",
        description="One serving replica: restore a serve checkpoint, warm "
                    "the ladder, serve HTTP until SIGTERM (docs/SERVING.md).",
    )
    p.add_argument("--checkpoint", required=True,
                   help="serve checkpoint directory (Server.save) holding "
                        "the endpoint set this replica serves")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=None,
                   help="listen port (default: HEAT_TPU_SERVE_NET_PORT; "
                        "0 binds an ephemeral port, printed in the ready "
                        "line)")
    p.add_argument("--mesh", type=int, default=0,
                   help="force an n-device virtual CPU mesh before backend "
                        "init (0 = use the attached platform as-is)")
    p.add_argument("--request-timeout", type=float, default=30.0,
                   help="per-request future wait before HTTP 504")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="max seconds the SIGTERM drain waits for queued + "
                        "in-flight work before closing anyway")
    return p


def main(argv=None) -> int:
    args = _build_parser().parse_args(argv)
    if args.mesh:
        from heat_tpu.utils.backend_probe import force_virtual_cpu_mesh

        force_virtual_cpu_mesh(args.mesh)
    # imported here, after the mesh decision — backend init is lazy, and
    # restore() below is the first device touch
    from heat_tpu import telemetry
    from heat_tpu.core import program_cache
    from heat_tpu.serve import Server

    from .transport import HttpFront

    program_cache.enable_persistent_cache()
    server = Server.restore(args.checkpoint)
    warm = server.warmup()
    front = HttpFront(
        server, host=args.host, port=args.port,
        request_timeout=args.request_timeout,
    )
    front.warmup_report = warm
    front.start()

    stop = threading.Event()

    def _on_signal(signum, frame):  # noqa: ARG001 — signal contract
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    from heat_tpu.serve import tracing

    print(json.dumps({
        "ready": True,
        "url": front.url,
        "port": front.port,
        "pid": os.getpid(),
        "endpoints": sorted(server.endpoints()),
        "warmup": warm,
        # observability posture (ISSUE 17): whether this replica records
        # adopted trace contexts — the pool/CI can verify a fleet's
        # tracing configuration from the ready lines alone
        "tracing": tracing.active(),
    }), flush=True)

    stop.wait()
    # graceful shutdown (ISSUE 12 satellite): drain the queue, flush the
    # final telemetry snapshot, exit 0 — nothing in flight is dropped,
    # and the sink carries the replica's last counters/watermarks
    drained = front.drain(args.drain_timeout)
    telemetry.flush("sigterm_drain")
    print(json.dumps({"exit": True, "drained": drained,
                      "pid": os.getpid()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
