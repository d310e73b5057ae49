#!/usr/bin/env python
"""Horizontally-scaled serving benchmark: multi-process loadgen against a
replica pool behind the least-loaded router (ISSUE 12).

No reference analog (the reference framework has no serving tier). The
runner fits small estimators ONCE, checkpoints them, and then every
replica process is *born* from that checkpoint — warming from the shared
persistent XLA compile cache (and tuning DB when armed), the property
that makes horizontal scale-out cheap. Phases, each one JSONL line:

* ``{"pool": ...}`` — per-replica spawn/warm-up reports (ready wall,
  warm-up compile counts/seconds — replica 2..N should deserialize, not
  compile, when the shared cache is already hot);
* ``{"digest_probe": ...}`` — the router-vs-direct bit-identity oracle:
  the same seeded request set driven through an in-process Server and
  through the router over HTTP must produce IDENTICAL response digests
  (wire round-trip is bitwise; exact-mode answers are
  batch-composition-independent);
* ``{"scaling": [...]}`` — the headline: the SAME open-loop Poisson
  schedule at the SAME offered rate against 1, 2, ... N replicas (equal
  per-replica admission budgets via env knobs). Completed QPS at one
  replica is the single-process ceiling; N replicas should lift it
  near-linearly while p99 falls out of the queueing regime. Every row
  carries each replica's ``steady_backend_compiles`` (must be 0 — the
  remote zero-compile oracle).

  **Pacing regime.** Each replica's capacity is deliberately bounded by
  its recorded per-replica budget: the micro-batch gather window
  (``--wait-ms``) plus the router's per-replica in-flight budget
  (``--max-inflight``, default 1 outstanding batch). One replica
  therefore serializes on its own window+dispatch+wire cycle, and N
  replicas run N such pipelines concurrently — the scale factor
  measures the horizontal architecture (router, transport, shared-cache
  warm start), not host-core contention, which is what makes the number
  reproducible on small shared CI hosts. Raising the budgets shifts the
  bottleneck back to CPU, where scaling is capped by physical cores
  (both configs are honest; the summary records which one ran);
* ``{"chaos": ...}`` — kill one replica mid-load (SIGKILL): the router
  evicts it, siblings absorb the traffic, and ONLY the killed replica's
  in-flight requests fail; a freshly spawned replacement joins via
  ``Router.add_target`` and the post-kill probe answers bit-identically
  to the direct single-dispatch reference;
* final summary — ``on_chip`` + ``cpu_fallback`` honesty: replica
  processes ALWAYS run virtual CPU meshes (an attached accelerator
  cannot be shared across processes), so this bench is a CPU number by
  construction and says so in-band.

``--artifact PATH`` appends the emitted lines (the committed
``artifacts/bench_serving_net_r12.jsonl``). The CI serving-net gate
(scripts/run_ci.sh) runs ``--replicas-list 2 --chaos`` small and asserts
the digest/recovery/zero-compile verdicts.
"""

import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from benchmarks._harness import base_parser, bootstrap

CPU_FALLBACK_REASON = (
    "replica processes run on virtual cpu meshes (an attached accelerator "
    "cannot be shared across replica processes)"
)


def add_args(p):
    p.add_argument("--replicas-list", default="1,2,4",
                   help="comma-separated replica counts to sweep at equal "
                        "offered load")
    p.add_argument("--requests", type=int, default=1200,
                   help="requests per scaling phase")
    p.add_argument("--rate", type=float, default=1200.0,
                   help="offered Poisson arrival rate, requests/second "
                        "(the SAME for every replica count)")
    p.add_argument("--streams", type=int, default=4,
                   help="concurrent loadgen submitter threads")
    p.add_argument("--endpoints", default="cdist,dense",
                   help="comma-separated endpoint subset "
                        "(kmeans,lasso,gnb,dense,knn,rbf,cdist)")
    p.add_argument("--replica-mesh", type=int, default=4,
                   help="virtual CPU mesh size of every replica process")
    p.add_argument("--max-batch", type=int, default=4,
                   help="per-replica micro-batch ladder top (the bounded "
                        "per-replica batch budget)")
    p.add_argument("--queue-max", type=int, default=64,
                   help="per-replica admission queue bound (bounds the "
                        "queueing tail; excess load sheds 503)")
    p.add_argument("--wait-ms", type=float, default=2.0,
                   help="per-replica micro-batch gather window")
    p.add_argument("--workers", type=int, default=16,
                   help="router client worker threads (the router's max "
                        "total in-flight)")
    p.add_argument("--max-inflight", type=int, default=1,
                   help="router per-replica in-flight budget (the client "
                        "half of the per-replica admission discipline; "
                        "0 = unlimited). With the budget at 1, a replica "
                        "serves strictly one request at a time, so the "
                        "single-replica arm measures the serialized "
                        "per-request wall (gather window + dispatch + "
                        "wire) and N replicas run N such pipelines "
                        "concurrently")
    p.add_argument("--max-rows", type=int, default=1,
                   help="max rows per request payload")
    p.add_argument("--digest-requests", type=int, default=120,
                   help="requests in the router-vs-direct digest probe")
    p.add_argument("--digest-rate", type=float, default=150.0,
                   help="offered rate of the digest probe (below "
                        "saturation: zero sheds on both sides)")
    p.add_argument("--chaos", action="store_true",
                   help="run the kill-one-replica phase")
    p.add_argument("--chaos-rate", type=float, default=None,
                   help="offered rate during chaos (default: rate/2 — the "
                        "surviving replicas must absorb it)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workdir", default=None,
                   help="checkpoint + log directory (default: a fresh "
                        "temp dir; the compile cache is JAX's persistent "
                        "one, shared by every replica)")
    p.add_argument("--artifact", default=None,
                   help="append the emitted JSONL lines to this file")


def _emit(lines, obj):
    print(json.dumps(obj), flush=True)
    lines.append(obj)


def _pool_env(args):
    env = {
        "HEAT_TPU_SERVE_MAX_BATCH": str(args.max_batch),
        "HEAT_TPU_SERVE_MAX_WAIT_MS": str(args.wait_ms),
        "HEAT_TPU_SERVE_QUEUE_MAX": str(args.queue_max),
    }
    # the tuning DB rides along when the parent run is armed
    # (docs/AUTOTUNE.md): replicas start tuned
    # heatlint: disable=HL005 -- pass-through of the parent's already-set
    # env into the replica subprocess env dict, not a knob read
    for var in ("HEAT_TPU_TUNE_DB", "HEAT_TPU_AUTOTUNE",
                "HEAT_TPU_TELEMETRY"):
        if os.environ.get(var):
            env[var] = os.environ[var]
    return env


def _spawn(args, ckpt, n, log_dir):
    from heat_tpu.serve.net import ReplicaPool, Router

    t0 = time.perf_counter()
    pool = ReplicaPool(
        ckpt, n, mesh=args.replica_mesh, env=_pool_env(args),
        log_dir=log_dir,
    ).start()
    router = Router(
        pool, workers=args.workers,
        max_inflight=args.max_inflight or None,
    )
    return pool, router, round(time.perf_counter() - t0, 3)


def _replica_net(pool):
    """Per-replica ``net`` stats blocks (steady compiles, http tallies)."""
    out = []
    for h in pool.replicas:
        if h.state != "up" or not h.alive():
            out.append({"replica": h.index, "state": h.state})
            continue
        try:
            st = pool.stats(h.index)
        except Exception as e:  # noqa: BLE001 — a dead replica is data
            out.append({"replica": h.index, "state": "unreachable",
                        "error": repr(e)})
            continue
        net = st.get("net", {})
        out.append({
            "replica": h.index,
            "steady_backend_compiles": net.get("steady_backend_compiles"),
            "http_requests": net.get("http_requests"),
            "warmup": h.ready.get("warmup") if h.ready else None,
            "shed": st.get("shed"),
            "pending": st.get("pending"),
        })
    return out


def _reference_answers(ht, eps, seed):
    """Direct single-dispatch reference per endpoint (fresh jit, like the
    PR 8 post_ok oracle) — the chaos recovery probe compares routed
    answers against these, bitwise."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed + 17)
    out = {}
    for name, ep in sorted(eps.items()):
        probe = rng.standard_normal((2, ep.features)).astype(ep.dtype)
        # heatlint: disable=HL001 -- fresh independent jit is the oracle:
        # compiled outside the server's cached program to prove bit-equality
        ref = np.asarray(jax.jit(ep.build())(jnp.asarray(probe), *ep.params))
        out[name] = (probe, ref)
    return out


def _probe_router(router, refs, timeout=30.0):
    """post_ok: every endpoint's routed answer must match the direct
    reference bit-for-bit."""
    ok = True
    for name, (probe, ref) in refs.items():
        try:
            got = router.predict(name, probe, timeout=timeout)
        except Exception:  # noqa: BLE001 — a dead tier is the finding
            return False
        if np.asarray(got).tobytes() != ref.tobytes():
            ok = False
    return ok


def main():
    p = base_parser("heat_tpu horizontally-scaled serving benchmark "
                    "(replica pool + router, multi-process loadgen)")
    add_args(p)
    args = p.parse_args()
    ht = bootstrap(args)
    import jax

    from benchmarks.serving import loadgen
    from benchmarks.serving.heat_tpu import build_endpoints
    from heat_tpu import telemetry

    devs = jax.devices()
    lines = []
    replicas_list = sorted(
        {int(v) for v in args.replicas_list.split(",") if v.strip()}
    )
    names = [s.strip() for s in args.endpoints.split(",") if s.strip()]

    workdir = args.workdir or tempfile.mkdtemp(prefix="heat_tpu_srvnet_")
    os.makedirs(workdir, exist_ok=True)
    log_dir = os.path.join(workdir, "logs")
    os.makedirs(log_dir, exist_ok=True)
    ckpt = os.path.join(workdir, "endpoints.ckpt")

    # -- fit once, checkpoint, reference answers ------------------------------
    eps = build_endpoints(ht, args, [n for n in names if n != "cdist"])
    if "cdist" in names:
        rng = np.random.default_rng(args.seed)
        eps["cdist"] = ht.serve.cdist_query(
            rng.standard_normal((256, args.features)).astype(np.float32)
        )
    server = ht.serve.Server()
    for name, ep in eps.items():
        server.register(name, ep)
    server.save(ckpt)
    server.close()
    refs = _reference_answers(ht, eps, args.seed)

    features = {n: eps[n].features for n in eps}
    dtypes = {n: eps[n].dtype for n in eps}
    reqs = loadgen.make_requests(
        features, args.requests, args.seed,
        max_rows=args.max_rows, dtypes=dtypes,
    )
    digest_reqs = loadgen.make_requests(
        features, args.digest_requests, args.seed + 1,
        max_rows=args.max_rows, dtypes=dtypes,
    )

    # -- direct (in-process) digest reference ---------------------------------
    direct = ht.serve.Server.restore(ckpt)
    direct.warmup()
    direct_probe = loadgen.run_open_loop(
        direct, digest_reqs, args.digest_rate, seed=args.seed,
        streams=args.streams,
    )
    direct.close()

    # -- scaling sweep: equal offered load, growing replica count -------------
    scaling = []
    digest_probe = None
    for n in replicas_list:
        pool, router, spawn_wall = _spawn(
            args, ckpt, n, os.path.join(log_dir, f"r{n}")
        )
        try:
            if digest_probe is None:
                routed_probe = loadgen.run_open_loop(
                    router, digest_reqs, args.digest_rate, seed=args.seed,
                    streams=args.streams,
                )
                digest_probe = {
                    "requests": args.digest_requests,
                    "direct_digest": direct_probe["digest"],
                    "routed_digest": routed_probe["digest"],
                    "match": routed_probe["digest"] == direct_probe["digest"],
                    "direct_clean": direct_probe["failed"] == 0
                    and direct_probe["shed"] == 0,
                    "routed_clean": routed_probe["failed"] == 0
                    and routed_probe["shed"] == 0,
                }
                _emit(lines, {"digest_probe": digest_probe})
            report = loadgen.run_open_loop(
                router, reqs, args.rate, seed=args.seed,
                streams=args.streams,
            )
            net = _replica_net(pool)
            row = {
                "replicas": n,
                "spawn_wall_seconds": spawn_wall,
                "achieved_qps": report["achieved_qps"],
                "completed": report["completed"],
                "failed": report["failed"],
                "shed": report["shed"],
                "p50_s": report["latency"].get("p50_s"),
                "p99_s": report["latency"].get("p99_s"),
                "steady_backend_compiles": [
                    r.get("steady_backend_compiles") for r in net
                ],
                "per_replica": net,
                "router": router.stats()["router"],
            }
            scaling.append(row)
            _emit(lines, {"scaling_row": row})
        finally:
            router.close()
            pool.close()
    _emit(lines, {"scaling": scaling})

    # -- chaos: kill one replica mid-load -------------------------------------
    chaos = None
    if args.chaos:
        n = max(replicas_list)
        rate = args.chaos_rate or args.rate / 2
        pool, router, _ = _spawn(
            args, ckpt, n, os.path.join(log_dir, "chaos")
        )
        try:
            result = {}

            def _load():
                result["report"] = loadgen.run_open_loop(
                    router, reqs, rate, seed=args.seed,
                    streams=args.streams,
                )

            t = threading.Thread(target=_load, daemon=True)
            t.start()
            # kill roughly mid-schedule
            time.sleep(0.4 * args.requests / rate)
            victim = pool.replicas[n - 1].index
            victim_inflight = router.stats()["replicas"].get(
                pool.handle(victim).url, {}
            ).get("inflight", 0)
            pool.kill(victim)
            t_kill = time.perf_counter()
            t.join(timeout=180)
            report = result.get("report") or {}
            # recovery: a fresh replacement replica joins the rotation
            repl = pool.spawn()
            router.add_target(repl.url)
            post_ok = _probe_router(router, refs)
            chaos = {
                "replicas": n,
                "offered_rate": rate,
                "killed_replica": victim,
                "inflight_at_kill": victim_inflight,
                "completed": report.get("completed"),
                "failed": report.get("failed"),
                "shed": report.get("shed"),
                "p99_s": (report.get("latency") or {}).get("p99_s"),
                "router": router.stats()["router"],
                "max_inflight_bound": args.workers,
                "failed_within_inflight_bound":
                    (report.get("failed") or 0) <= args.workers,
                "replacement_replica": repl.index,
                "replacement_join_seconds":
                    round(time.perf_counter() - t_kill, 3),
                "post_ok": post_ok,
            }
            _emit(lines, {"chaos": chaos})
        finally:
            router.close()
            pool.close()

    # -- summary (bench-honesty contract) -------------------------------------
    by_n = {row["replicas"]: row for row in scaling}
    base = by_n.get(replicas_list[0], {})
    top = by_n.get(replicas_list[-1], {})
    summary = {
        "bench": "serving_net",
        "requests": args.requests,
        "offered_rate": args.rate,
        "endpoints": sorted(eps),
        "replica_mesh": args.replica_mesh,
        "per_replica_budget": {
            "max_batch": args.max_batch,
            "queue_max": args.queue_max,
            "wait_ms": args.wait_ms,
            "router_max_inflight": args.max_inflight or None,
        },
        "qps_by_replicas": {
            str(r["replicas"]): r["achieved_qps"] for r in scaling
        },
        "p99_by_replicas": {
            str(r["replicas"]): r["p99_s"] for r in scaling
        },
        "scale_factor": (
            round(top["achieved_qps"] / base["achieved_qps"], 2)
            if base.get("achieved_qps") else None
        ),
        "digest_probe": digest_probe,
        "chaos": chaos,
        "steady_backend_compiles_ok": all(
            c == 0
            for r in scaling for c in r["steady_backend_compiles"]
            if c is not None
        ),
        "on_chip": False,
        "cpu_fallback": CPU_FALLBACK_REASON,
        "devices": {"count": len(devs), "kind": devs[0].device_kind},
    }
    if telemetry.enabled():
        summary.update(telemetry.report.bench_fields())
    _emit(lines, summary)

    if args.artifact:
        with open(args.artifact, "a") as f:
            for obj in lines:
                f.write(json.dumps(obj) + "\n")


if __name__ == "__main__":
    main()
