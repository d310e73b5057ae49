#!/usr/bin/env python
"""Autoscaling control-plane benchmark: SLO-driven replica scaling,
priority-aware admission, and hedged tail-latency retries (ISSUE 20).

No reference analog (the reference framework's MPI world is static).
The runner fits/checkpoints two endpoints ONCE (``kmeans`` — the
latency-sensitive tenant — and ``cdist`` — the bulk tenant), then every
phase spawns replica processes born from that checkpoint, warming from
the shared persistent compile cache. Phases, each one JSONL line:

* ``{"autoscale_row": ...}`` per offered-load profile (step / spike /
  diurnal inhomogeneous-Poisson schedules from
  :mod:`benchmarks.autoscale.profiles`) — the headline: an
  :class:`~heat_tpu.serve.net.AutoscaleController` holds the declared
  p99 SLO while **replica-seconds** (the controller's live-footprint
  integral) price at least 2x better than static max provisioning
  (``max_replicas`` running the whole wall). Each row records the
  scale-up/scale-down trail, the drain-down-to-min verdict, and every
  replica's ``steady_backend_compiles`` (must be 0 — scale-ups
  warm-start from the shared cache, never retrace);
* ``{"two_tenant": ...}`` — overload fairness: bulk ``cdist`` offered
  well past capacity next to a modest latency ``kmeans`` stream, under
  weighted-fair admission (``latency=8, bulk=1``) and a bounded router
  queue. The verdicts: the latency tenant's p99 holds its SLO AND the
  bulk tenant still gets at least its weighted-fair share of routed
  requests (priority is isolation, not starvation);
* ``{"hedge": ...}`` — tail trimming: one straggler replica (injected
  latency faults via ``HEAT_TPU_FAULTS``) next to a clean one, the same
  schedule driven with hedging off then on. The verdicts: hedged p99
  beats the baseline, and the hedge fraction stays at or under the
  configured hard cap (first-wins semantics are pinned by unit test);
* ``{"chaos": ...}`` — self-healing: a replica SIGKILLed mid-load
  (raw ``proc.kill()``, so only the controller's liveness probe can
  notice) is replaced within a bounded number of ticks with zero
  failed requests (``retry_in_flight=True``) and zero steady-state
  compiles on the respawned replica;
* final summary — ``on_chip`` + ``cpu_fallback`` honesty (replica
  processes always run virtual CPU meshes).

``--artifact PATH`` appends the emitted lines (the committed
``artifacts/bench_autoscale_r20.jsonl``). The CI autoscale gate
(scripts/run_ci.sh) runs ``--profiles step --chaos`` small and asserts
the scale-up/drain-down/zero-failed/bounded-replacement verdicts.
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
from benchmarks.autoscale import profiles
from benchmarks.serving import loadgen
from benchmarks.serving.net import CPU_FALLBACK_REASON, _replica_net


def add_args(p):
    p.set_defaults(n=4000, features=32)
    p.add_argument("--profiles", default="step,spike,diurnal",
                   help="comma-separated offered-load profiles to run "
                        "(empty string skips the autoscale phase)")
    p.add_argument("--duration", type=float, default=30.0,
                   help="seconds per profile schedule")
    p.add_argument("--peak-rate", type=float, default=150.0,
                   help="peak offered rate, requests/second (profiles "
                        "scale this by their shape)")
    p.add_argument("--min-replicas", type=int, default=1)
    p.add_argument("--max-replicas", type=int, default=4,
                   help="controller ceiling — ALSO the static provisioning "
                        "the replica-seconds ratio prices against")
    p.add_argument("--slo-p99", type=float, default=3.0,
                   help="declared p99 SLO (seconds) on the cdist endpoint")
    p.add_argument("--tick-s", type=float, default=0.25,
                   help="controller tick interval")
    p.add_argument("--up-cooldown-s", type=float, default=1.0)
    p.add_argument("--down-cooldown-s", type=float, default=2.0)
    p.add_argument("--backlog-high", type=float, default=4.0)
    p.add_argument("--backlog-ticks", type=int, default=2)
    p.add_argument("--idle-low", type=float, default=0.5)
    p.add_argument("--idle-ticks", type=int, default=4)
    p.add_argument("--drain-wait", type=float, default=25.0,
                   help="post-load seconds to wait for drain-down to min")
    p.add_argument("--streams", type=int, default=4,
                   help="concurrent loadgen submitter threads")
    p.add_argument("--workers", type=int, default=16,
                   help="router client worker threads")
    p.add_argument("--max-inflight", type=int, default=4,
                   help="router per-replica in-flight budget (0 = "
                        "unlimited) — with the gather window below this "
                        "bounds per-replica throughput, the committed "
                        "pacing regime (see benchmarks/serving/net.py)")
    p.add_argument("--wait-ms", type=float, default=25.0,
                   help="per-replica micro-batch gather window")
    p.add_argument("--queue-max", type=int, default=512,
                   help="per-replica admission queue bound")
    p.add_argument("--replica-mesh", type=int, default=2,
                   help="virtual CPU mesh size of every replica process")
    # two-tenant overload phase
    p.add_argument("--two-tenant", action="store_true",
                   help="run the weighted-fair two-tenant overload phase")
    p.add_argument("--tenant-replicas", type=int, default=2)
    p.add_argument("--tenant-duration", type=float, default=12.0)
    p.add_argument("--latency-rate", type=float, default=30.0,
                   help="offered rate of the latency-sensitive kmeans "
                        "tenant")
    p.add_argument("--bulk-rate", type=float, default=400.0,
                   help="offered rate of the bulk cdist tenant (past "
                        "capacity — the overload)")
    p.add_argument("--latency-weight", type=float, default=8.0,
                   help="weighted-fair weight of the latency class "
                        "(bulk weighs 1)")
    p.add_argument("--priority-queue-max", type=int, default=64,
                   help="bounded router admission queue for the phase")
    # hedge phase
    p.add_argument("--hedge", action="store_true",
                   help="run the hedged-retry straggler phase")
    p.add_argument("--hedge-duration", type=float, default=15.0)
    p.add_argument("--hedge-rate", type=float, default=20.0)
    p.add_argument("--hedge-delay-ms", type=float, default=75.0,
                   help="fixed hedge delay (the artifact pins the regime; "
                        "production defaults derive it from p95)")
    p.add_argument("--hedge-cap", type=float, default=0.35,
                   help="hedge-fraction hard cap for the phase")
    p.add_argument("--straggle-delay", type=float, default=0.3,
                   help="injected latency-fault delay on the straggler")
    p.add_argument("--straggle-p", type=float, default=0.5,
                   help="injected latency-fault probability")
    # chaos phase
    p.add_argument("--chaos", action="store_true",
                   help="run the SIGKILL-replacement phase")
    p.add_argument("--chaos-replicas", type=int, default=2)
    p.add_argument("--chaos-duration", type=float, default=12.0)
    p.add_argument("--chaos-rate", type=float, default=20.0)
    p.add_argument("--replace-tick-bound", type=int, default=3,
                   help="max controller ticks allowed between the kill "
                        "and the replacement (the bounded-ticks verdict)")
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
        "HEAT_TPU_SERVE_MAX_BATCH": "4",
        "HEAT_TPU_SERVE_MAX_WAIT_MS": str(args.wait_ms),
        "HEAT_TPU_SERVE_QUEUE_MAX": str(args.queue_max),
    }
    # heatlint: disable=HL005 -- pass-through of the parent's already-set
    # env into the replica subprocess env dict, not a knob read
    for var in ("HEAT_TPU_TUNE_DB", "HEAT_TPU_AUTOTUNE",
                "HEAT_TPU_TELEMETRY"):
        if os.environ.get(var):
            env[var] = os.environ[var]
    return env


def _drive(router, requests, offsets, *, streams=4, timeout=120.0):
    """Open-loop drive of ``requests`` at precomputed arrival
    ``offsets`` (seconds from start) — the inhomogeneous-schedule twin
    of ``loadgen.run_open_loop`` (which generates its own fixed-rate
    schedule). Latency percentiles live in the ROUTER's per-endpoint
    stats; this returns the completion/shed/failure accounting."""
    from heat_tpu.serve import ServerOverloadedError

    n = len(requests)
    futures = [None] * n
    shed_errors = [None] * n
    submit_errors = [None] * n
    t0 = time.perf_counter()

    def submitter(stream):
        for i in range(stream, n, streams):
            name, payload = requests[i]
            delay = t0 + offsets[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            try:
                futures[i] = router.submit(name, payload)
            except ServerOverloadedError as e:
                shed_errors[i] = repr(e)
            except Exception as e:  # noqa: BLE001 — failed, never silent
                submit_errors[i] = repr(e)

    threads = [
        threading.Thread(target=submitter, args=(s,), daemon=True)
        for s in range(streams)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    shed = failed = 0
    errors = []
    deadline = time.monotonic() + timeout
    for i, (name, _payload) in enumerate(requests):
        if futures[i] is None:
            if submit_errors[i] is not None:
                failed += 1
                errors.append(f"request {i} ({name}): {submit_errors[i]}")
            else:
                shed += 1
            continue
        try:
            futures[i].result(max(0.001, deadline - time.monotonic()))
        except ServerOverloadedError:
            shed += 1
        except Exception as e:  # noqa: BLE001 — a failed request is data
            failed += 1
            errors.append(f"request {i} ({name}): {e!r}")
    wall = time.perf_counter() - t0
    ok = n - shed - failed
    return {
        "requests": n,
        "completed": ok,
        "failed": failed,
        "shed": shed,
        "errors": errors[:8],
        "wall_seconds": round(wall, 4),
        "achieved_qps": round(ok / wall, 2) if wall > 0 else 0.0,
    }


def _live(pool):
    return sum(
        1 for h in pool.replicas if h.state == "up" and h.alive()
    )


def _p99(router, endpoint):
    lat = router.stats()["endpoints"].get(endpoint, {}).get("latency", {})
    return lat.get("p99_s")


def _controller(args, pool, router, **over):
    from heat_tpu.serve.net import AutoscaleController

    kw = dict(
        min_replicas=args.min_replicas, max_replicas=args.max_replicas,
        backlog_high=args.backlog_high, backlog_ticks=args.backlog_ticks,
        idle_low=args.idle_low, idle_ticks=args.idle_ticks,
        up_cooldown_s=args.up_cooldown_s,
        down_cooldown_s=args.down_cooldown_s,
        tick_interval_s=args.tick_s,
        slo_check_every=4,
    )
    kw.update(over)
    return AutoscaleController(pool, router, **kw)


def _profile_phase(args, ckpt, log_dir, profile):
    from heat_tpu.serve.net import ReplicaPool, Router
    from heat_tpu.telemetry.cluster import SLO

    offsets = profiles.schedule(
        profile, args.duration, args.peak_rate, seed=args.seed
    )
    reqs = loadgen.make_requests(
        {"cdist": args.features}, len(offsets), args.seed + 3, max_rows=1
    )
    t0 = time.perf_counter()
    pool = ReplicaPool(
        ckpt, args.min_replicas, mesh=args.replica_mesh,
        env=_pool_env(args),
        log_dir=os.path.join(log_dir, f"as_{profile}"),
    ).start()
    router = Router(
        pool, workers=args.workers,
        max_inflight=args.max_inflight or None, retry_in_flight=True,
        slos=[SLO("cdist", p99_s=args.slo_p99)],
    )
    ctrl = _controller(args, pool, router).start()
    try:
        report = _drive(router, reqs, offsets, streams=args.streams)
        drain_deadline = time.monotonic() + args.drain_wait
        while time.monotonic() < drain_deadline:
            if _live(pool) <= args.min_replicas:
                break
            time.sleep(args.tick_s)
        ctrl.stop()
        wall = time.perf_counter() - t0
        cstats = ctrl.stats()
        p99 = _p99(router, "cdist")
        static = args.max_replicas * wall
        ratio = (
            round(static / cstats["replica_seconds"], 2)
            if cstats["replica_seconds"] else None
        )
        net = _replica_net(pool)
        return {
            "profile": profile,
            "offered": {"peak_rate": args.peak_rate,
                        "duration_s": args.duration,
                        "requests": len(reqs)},
            **{k: report[k] for k in ("completed", "failed", "shed",
                                      "achieved_qps")},
            "p99_s": p99,
            "slo_p99_s": args.slo_p99,
            "p99_within_slo": p99 is not None and p99 <= args.slo_p99,
            "controller": cstats,
            "max_replicas_seen": max(
                (r["obs"]["replicas"] for r in ctrl.history), default=0
            ),
            "drained_to_min": _live(pool) <= args.min_replicas,
            "replica_seconds": cstats["replica_seconds"],
            "static_replica_seconds": round(static, 3),
            "replica_seconds_ratio": ratio,
            "steady_backend_compiles": [
                r.get("steady_backend_compiles") for r in net
            ],
            "wall_seconds": round(wall, 3),
        }
    finally:
        ctrl.stop()
        router.close()
        pool.close()


def _two_tenant_phase(args, ckpt, log_dir, features):
    from heat_tpu.serve.net import ReplicaPool, Router

    n_lat = max(1, int(args.tenant_duration * args.latency_rate))
    n_bulk = max(1, int(args.tenant_duration * args.bulk_rate))
    reqs_lat = loadgen.make_requests(
        {"kmeans": features["kmeans"]}, n_lat, args.seed + 5, max_rows=1
    )
    reqs_bulk = loadgen.make_requests(
        {"cdist": features["cdist"]}, n_bulk, args.seed + 6, max_rows=1
    )
    off_lat = loadgen.poisson_schedule(n_lat, args.latency_rate,
                                       args.seed + 7)
    off_bulk = loadgen.poisson_schedule(n_bulk, args.bulk_rate,
                                        args.seed + 8)
    pool = ReplicaPool(
        ckpt, args.tenant_replicas, mesh=args.replica_mesh,
        env=_pool_env(args),
        log_dir=os.path.join(log_dir, "two_tenant"),
    ).start()
    router = Router(
        pool, workers=args.workers,
        max_inflight=args.max_inflight or None,
        priorities={"latency": args.latency_weight, "bulk": 1.0},
        endpoint_priorities={"kmeans": "latency", "cdist": "bulk"},
        priority_queue_max=args.priority_queue_max,
    )
    try:
        results = {}

        def _tenant(key, reqs, offs):
            results[key] = _drive(router, reqs, offs,
                                  streams=max(2, args.streams // 2))

        ts = [
            threading.Thread(target=_tenant,
                             args=("latency", reqs_lat, off_lat),
                             daemon=True),
            threading.Thread(target=_tenant,
                             args=("bulk", reqs_bulk, off_bulk),
                             daemon=True),
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        st = router.stats()
        classes = st["priority"]["classes"]
        routed_l = classes.get("latency", {}).get("routed", 0)
        routed_b = classes.get("bulk", {}).get("routed", 0)
        fair_share = 1.0 / (1.0 + args.latency_weight)
        bulk_share = routed_b / max(1, routed_b + routed_l)
        p99_lat = _p99(router, "kmeans")
        return {
            "replicas": args.tenant_replicas,
            "weights": {"latency": args.latency_weight, "bulk": 1.0},
            "priority_queue_max": args.priority_queue_max,
            "offered": {"latency_rate": args.latency_rate,
                        "bulk_rate": args.bulk_rate,
                        "duration_s": args.tenant_duration},
            "latency_tenant": {**results["latency"], "p99_s": p99_lat},
            "bulk_tenant": {**results["bulk"],
                            "p99_s": _p99(router, "cdist")},
            "routed": {"latency": routed_l, "bulk": routed_b},
            "priority_sheds": st["router"]["priority_sheds"],
            "bulk_fair_share": round(fair_share, 4),
            "bulk_routed_share": round(bulk_share, 4),
            "bulk_gets_fair_share": bulk_share >= fair_share,
            "latency_slo_p99_s": args.slo_p99,
            "latency_p99_within_slo":
                p99_lat is not None and p99_lat <= args.slo_p99,
            "latency_failed": results["latency"]["failed"],
        }
    finally:
        router.close()
        pool.close()


def _hedge_phase(args, ckpt, log_dir):
    from heat_tpu.serve.net import ReplicaPool, Router

    env = _pool_env(args)
    pool = ReplicaPool(
        ckpt, 1, mesh=args.replica_mesh, env=env,
        log_dir=os.path.join(log_dir, "hedge"),
    ).start()
    try:
        # the straggler: same checkpoint, latency faults injected into
        # its serve-side execution (resilience fault grammar, ISSUE 17)
        pool.env_overrides = dict(env, HEAT_TPU_FAULTS=(
            f"serve.*:kind=latency:delay={args.straggle_delay}"
            f":p={args.straggle_p}"
        ))
        pool.spawn()
        n = max(1, int(args.hedge_duration * args.hedge_rate))
        reqs = loadgen.make_requests(
            {"cdist": args.features}, n, args.seed + 9, max_rows=1
        )
        offs = loadgen.poisson_schedule(n, args.hedge_rate, args.seed + 10)
        rows = {}
        for mode, kw in (
            ("baseline", dict(hedge=False)),
            ("hedged", dict(hedge=True,
                            hedge_delay_ms=args.hedge_delay_ms,
                            hedge_max_fraction=args.hedge_cap)),
        ):
            router = Router(pool.urls(), workers=args.workers, **kw)
            try:
                rep = _drive(router, reqs, offs, streams=args.streams)
                st = router.stats()["router"]
                rows[mode] = {
                    **{k: rep[k] for k in ("completed", "failed", "shed")},
                    "p99_s": _p99(router, "cdist"),
                    "hedges": st["hedges"],
                    "hedge_wins": st["hedge_wins"],
                    "requests_routed": st["requests"],
                }
            finally:
                router.close()
        base_p99 = rows["baseline"]["p99_s"]
        hedged_p99 = rows["hedged"]["p99_s"]
        fraction = (
            rows["hedged"]["hedges"]
            / max(1, rows["hedged"]["requests_routed"])
        )
        return {
            "straggler_fault": {"delay_s": args.straggle_delay,
                                "p": args.straggle_p},
            "hedge_delay_ms": args.hedge_delay_ms,
            "hedge_cap": args.hedge_cap,
            "baseline": rows["baseline"],
            "hedged": rows["hedged"],
            "hedge_fraction": round(fraction, 4),
            "fraction_within_cap": fraction <= args.hedge_cap,
            "p99_improved":
                base_p99 is not None and hedged_p99 is not None
                and hedged_p99 < base_p99,
        }
    finally:
        pool.close()


def _chaos_phase(args, ckpt, log_dir):
    from heat_tpu.serve.net import ReplicaPool, Router

    pool = ReplicaPool(
        ckpt, args.chaos_replicas, mesh=args.replica_mesh,
        env=_pool_env(args),
        log_dir=os.path.join(log_dir, "chaos"),
    ).start()
    router = Router(
        pool, workers=args.workers,
        max_inflight=args.max_inflight or None, retry_in_flight=True,
    )
    ctrl = _controller(
        args, pool, router,
        min_replicas=args.chaos_replicas,
        max_replicas=args.chaos_replicas + 1,
    ).start()
    try:
        n = max(1, int(args.chaos_duration * args.chaos_rate))
        reqs = loadgen.make_requests(
            {"cdist": args.features}, n, args.seed + 11, max_rows=1
        )
        offs = loadgen.poisson_schedule(n, args.chaos_rate, args.seed + 12)
        result = {}

        def _load():
            result["report"] = _drive(router, reqs, offs,
                                      streams=args.streams)

        t = threading.Thread(target=_load, daemon=True)
        t.start()
        time.sleep(0.4 * args.chaos_duration)
        victim = next(
            h for h in reversed(pool.replicas)
            if h.state == "up" and h.alive()
        )
        ticks_at_kill = ctrl.ticks
        # RAW SIGKILL — pool state stays "up", so ONLY the controller's
        # liveness probe can notice and repair (the self-healing claim)
        victim.proc.kill()
        t_kill = time.perf_counter()
        t.join(timeout=180)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if ctrl.counts["replacements"] >= 1:
                break
            time.sleep(args.tick_s)
        ctrl.stop()
        replace_rows = [r for r in ctrl.history if r["action"] == "replace"]
        ticks_to_replace = (
            replace_rows[0]["tick"] - ticks_at_kill if replace_rows
            else None
        )
        report = result.get("report") or {}
        net = _replica_net(pool)
        live_net = [r for r in net if "steady_backend_compiles" in r]
        replacement = live_net[-1] if live_net else {}
        return {
            "replicas": args.chaos_replicas,
            "offered_rate": args.chaos_rate,
            "killed_replica": victim.index,
            **{k: report.get(k) for k in ("requests", "completed",
                                          "failed", "shed")},
            "replaced": bool(replace_rows),
            "ticks_to_replace": ticks_to_replace,
            "replace_tick_bound": args.replace_tick_bound,
            "replaced_within_bound":
                ticks_to_replace is not None
                and ticks_to_replace <= args.replace_tick_bound,
            "replacement_wall_seconds": round(
                time.perf_counter() - t_kill, 3
            ),
            "replacement": replacement,
            "replacement_steady_compiles":
                replacement.get("steady_backend_compiles"),
            "zero_failed": (report.get("failed") or 0) == 0,
            "controller": ctrl.stats(),
        }
    finally:
        ctrl.stop()
        router.close()
        pool.close()


def main():
    p = base_parser("heat_tpu autoscaling control-plane benchmark "
                    "(controller loadgen, two-tenant fairness, hedged "
                    "retries, chaos replacement)")
    add_args(p)
    args = p.parse_args()
    ht = bootstrap(args)
    import jax

    from benchmarks.serving.heat_tpu import build_endpoints
    from heat_tpu import telemetry

    devs = jax.devices()
    lines = []
    workdir = args.workdir or tempfile.mkdtemp(prefix="heat_tpu_autoscale_")
    os.makedirs(workdir, exist_ok=True)
    log_dir = os.path.join(workdir, "logs")
    os.makedirs(log_dir, exist_ok=True)
    ckpt = os.path.join(workdir, "endpoints.ckpt")

    # fit once, checkpoint: kmeans = the latency tenant, cdist = bulk
    eps = build_endpoints(ht, args, ["kmeans"])
    rng = np.random.default_rng(args.seed)
    eps["cdist"] = ht.serve.cdist_query(
        rng.standard_normal((256, args.features)).astype(np.float32)
    )
    server = ht.serve.Server()
    for name, ep in eps.items():
        server.register(name, ep)
    server.save(ckpt)
    server.close()
    features = {n: eps[n].features for n in eps}

    profile_rows = []
    for profile in [s.strip() for s in args.profiles.split(",") if s.strip()]:
        row = _profile_phase(args, ckpt, log_dir, profile)
        profile_rows.append(row)
        _emit(lines, {"autoscale_row": row})

    two_tenant = None
    if args.two_tenant:
        two_tenant = _two_tenant_phase(args, ckpt, log_dir,
                                       features)
        _emit(lines, {"two_tenant": two_tenant})

    hedge = None
    if args.hedge:
        hedge = _hedge_phase(args, ckpt, log_dir)
        _emit(lines, {"hedge": hedge})

    chaos = None
    if args.chaos:
        chaos = _chaos_phase(args, ckpt, log_dir)
        _emit(lines, {"chaos": chaos})

    summary = {
        "bench": "autoscale",
        "profiles": {
            r["profile"]: {
                "p99_within_slo": r["p99_within_slo"],
                "replica_seconds_ratio": r["replica_seconds_ratio"],
                "failed": r["failed"],
                "drained_to_min": r["drained_to_min"],
                "scale_ups": r["controller"]["scale_ups"],
                "scale_downs": r["controller"]["scale_downs"],
            }
            for r in profile_rows
        },
        "replica_seconds_ratio_min": min(
            (r["replica_seconds_ratio"] for r in profile_rows
             if r["replica_seconds_ratio"] is not None),
            default=None,
        ),
        "bounds": {"min_replicas": args.min_replicas,
                   "max_replicas": args.max_replicas},
        "two_tenant": two_tenant,
        "hedge": hedge,
        "chaos": chaos,
        "steady_backend_compiles_ok": all(
            c == 0
            for r in profile_rows for c in r["steady_backend_compiles"]
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
