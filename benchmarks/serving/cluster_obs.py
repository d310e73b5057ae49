#!/usr/bin/env python
"""Cluster observability driver: the ISSUE 17 end-to-end demo and CI
gate (2-replica pool + router under open-loop load).

Three phases, one JSONL line each, plus a final ``{"bench":
"cluster_obs"}`` summary the run_ci.sh checker asserts on:

* ``{"phase_off": ...}`` — tracing OFF (``HEAT_TPU_TRACE_REQUESTS=0``
  fleet-wide): the reference digest, plus every replica's ``/metrics``
  tracing counters (must be 0/0 — the off posture does no per-hop work)
  and the fleet-merge totals (merged per-endpoint requests must equal
  the loadgen completions exactly);
* ``{"phase_on": ...}`` — tracing ON at sample rate 1.0: the SAME seeded
  schedule must produce a BIT-IDENTICAL digest (tracing never touches
  payloads); every sampled request's trace id must appear on the full
  hop chain ``router.queue → router.post → serve.queue → serve.coalesce
  → serve.pad → serve.execute → serve.reply`` across the router's own
  events plus the scraped replica ``/trace`` events; the merged Perfetto
  export must carry one pid track per process (each with its explicit
  ``clock_sync`` record); and an in-process control run pins the
  merge-plumbing exactness — ``summarize_cluster`` over one scrape
  reproduces the server's own per-endpoint p99 bit-for-bit, while the
  pool's merged (server-side) p99 must sit within one histogram bucket
  width of the router's client-observed p99;
* ``{"phase_slo": ...}`` — the resilience injector adds
  ``--fault-delay`` seconds of latency to every replica-side program
  execution while the router declares a ``--slo-p99`` objective the
  delayed fleet cannot meet: the windowed burn rate must exceed the
  threshold and ``Router.check_slos()`` must emit ``slo_burn`` events
  (the paired ``serve_net.slo_burns`` counter proves it).

``--artifact PATH`` appends the emitted lines. Replicas always run
virtual CPU meshes (an accelerator cannot be shared across processes),
so every number here is a CPU number by construction.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

from benchmarks._harness import base_parser, bootstrap


def add_args(p):
    p.add_argument("--requests", type=int, default=80,
                   help="requests per load phase (the same seeded "
                        "schedule for off and on)")
    p.add_argument("--rate", type=float, default=120.0,
                   help="offered Poisson arrival rate, requests/second")
    p.add_argument("--streams", type=int, default=2,
                   help="concurrent loadgen submitter threads")
    p.add_argument("--endpoints", default="cdist,dense",
                   help="comma-separated endpoint subset")
    p.add_argument("--replicas", type=int, default=2)
    p.add_argument("--replica-mesh", type=int, default=4,
                   help="virtual CPU mesh size of every replica process")
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--wait-ms", type=float, default=2.0)
    p.add_argument("--queue-max", type=int, default=256)
    p.add_argument("--slo-requests", type=int, default=24,
                   help="requests in the SLO burn phase")
    p.add_argument("--slo-rate", type=float, default=30.0)
    p.add_argument("--slo-p99", type=float, default=0.05,
                   help="the deliberately-unmeetable p99 objective of "
                        "the burn phase")
    p.add_argument("--fault-delay", type=float, default=0.25,
                   help="injected per-execution latency (seconds) that "
                        "drives the SLO breach")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workdir", default=None)
    p.add_argument("--artifact", default=None,
                   help="append the emitted JSONL lines to this file")


def _emit(lines, obj):
    print(json.dumps(obj), flush=True)
    lines.append(obj)


def _pool_env(args, extra=None):
    env = {
        "HEAT_TPU_SERVE_MAX_BATCH": str(args.max_batch),
        "HEAT_TPU_SERVE_MAX_WAIT_MS": str(args.wait_ms),
        "HEAT_TPU_SERVE_QUEUE_MAX": str(args.queue_max),
        "HEAT_TPU_TELEMETRY": "1",
    }
    env.update(extra or {})
    return env


def _tracing_counters(scrapes):
    """Per-url ``(sampled, spans)`` out of ``/metrics`` scrapes."""
    out = {}
    for url, payload in scrapes.items():
        c = (payload or {}).get("counters", {}) or {}
        out[url] = {
            "sampled": int(c.get("tracing.sampled", 0)),
            "spans": int(c.get("tracing.spans", 0)),
        }
    return out


def _hop_completeness(router_events, scraped_traces):
    """For every ingress-sampled trace id, which of the seven canonical
    hops carry it (membership via the batch ``trace_ids`` lists too).
    Returns (ids, complete_ids, per-hop span counts)."""
    from heat_tpu.serve import tracing

    events = list(router_events)
    for payload in scraped_traces.values():
        events.extend((payload or {}).get("events", []) or [])
    spans = [e for e in events if e.get("kind") == "trace_span"]
    ids = sorted({
        e["trace_id"] for e in spans
        if e.get("ingress") and e.get("name") == "router.queue"
    })
    by_hop = {name: set() for name in tracing.HOPS}
    counts = {name: 0 for name in tracing.HOPS}
    for e in spans:
        name = e.get("name")
        if name in by_hop:
            counts[name] += 1
            by_hop[name].update(tracing.span_trace_ids(e))
    complete = [
        t for t in ids if all(t in by_hop[h] for h in tracing.HOPS)
    ]
    return ids, complete, counts


def main():
    p = base_parser("heat_tpu cluster observability driver (merged "
                    "tracing + fleet metrics + SLO burn; the ISSUE 17 "
                    "CI gate)")
    add_args(p)
    args = p.parse_args()
    ht = bootstrap(args)

    from benchmarks.serving import loadgen
    from benchmarks.serving.heat_tpu import build_endpoints
    from heat_tpu import telemetry
    from heat_tpu.serve import metrics as serve_metrics
    from heat_tpu.serve.net import ReplicaPool, Router
    from heat_tpu.telemetry.cluster import SLO, summarize_cluster

    lines = []
    names = [s.strip() for s in args.endpoints.split(",") if s.strip()]

    workdir = args.workdir or tempfile.mkdtemp(prefix="heat_tpu_clobs_")
    os.makedirs(workdir, exist_ok=True)
    ckpt = os.path.join(workdir, "endpoints.ckpt")

    eps = build_endpoints(ht, args, [n for n in names if n != "cdist"])
    if "cdist" in names:
        rng = np.random.default_rng(args.seed)
        eps["cdist"] = ht.serve.cdist_query(
            rng.standard_normal((128, args.features)).astype(np.float32)
        )
    server = ht.serve.Server()
    for name, ep in eps.items():
        server.register(name, ep)
    server.save(ckpt)
    server.close()

    features = {n: eps[n].features for n in eps}
    dtypes = {n: eps[n].dtype for n in eps}
    reqs = loadgen.make_requests(
        features, args.requests, args.seed, max_rows=1, dtypes=dtypes,
    )

    # the driver hosts the router, so its own tracing posture is staged
    # through the same env the replicas get (benchmark-runner env
    # staging, not a knob read)
    sink = os.path.join(workdir, "driver_events.jsonl")
    reg = telemetry.enable(sink)
    reg.clear()

    def _run_pool(extra_env, slos=None, requests=None, rate=None,
                  log_name="pool"):
        pool = ReplicaPool(
            ckpt, args.replicas, mesh=args.replica_mesh,
            env=_pool_env(args, extra_env),
            log_dir=os.path.join(workdir, f"logs_{log_name}"),
        ).start()
        router = Router(pool, workers=8, slos=slos)
        report = loadgen.run_open_loop(
            router, requests if requests is not None else reqs,
            rate if rate is not None else args.rate,
            seed=args.seed, streams=args.streams,
        )
        return pool, router, report

    # -- phase A: tracing OFF -------------------------------------------------
    os.environ["HEAT_TPU_TRACE_REQUESTS"] = "0"
    pool, router, rep_off = _run_pool(
        {"HEAT_TPU_TRACE_REQUESTS": "0"}, log_name="off"
    )
    try:
        scrapes = router.scrape_metrics()
        merged_off = summarize_cluster(scrapes)
        phase_off = {
            "digest": rep_off["digest"],
            "completed": rep_off["completed"],
            "failed": rep_off["failed"],
            "shed": rep_off["shed"],
            "replica_tracing": _tracing_counters(scrapes),
            "driver_tracing": {
                "sampled": int(reg.counters.get("tracing.sampled", 0)),
                "spans": int(reg.counters.get("tracing.spans", 0)),
            },
            "merged_requests_total": sum(
                ep["requests"] for ep in merged_off["endpoints"].values()
            ),
            "scrape_failures": merged_off["scrape_failures"],
        }
        _emit(lines, {"phase_off": phase_off})
    finally:
        router.close()
        pool.close()

    # -- phase B: tracing ON, sample 1.0 --------------------------------------
    os.environ["HEAT_TPU_TRACE_REQUESTS"] = "1"
    os.environ["HEAT_TPU_TRACE_SAMPLE"] = "1.0"
    reg.clear()
    pool, router, rep_on = _run_pool(
        {"HEAT_TPU_TRACE_REQUESTS": "1", "HEAT_TPU_TRACE_SAMPLE": "1.0"},
        log_name="on",
    )
    try:
        time.sleep(0.3)  # let the last batch's reply hop land
        summary = router.cluster_summary()
        traces = router.scrape_traces()
        sync = router.clock_sync()
        ids, complete, hop_counts = _hop_completeness(reg.events, traces)
        trace_path = os.path.join(workdir, "merged_trace.json")
        router.export_cluster_trace(trace_path)
        doc = json.load(open(trace_path))
        pids = {e["pid"] for e in doc["traceEvents"]}
        sync_pids = {e["pid"] for e in doc["traceEvents"]
                     if e.get("cat") == "clock_sync"}

        # merged (server-side) p99 vs the router's client-observed p99:
        # the server histogram must sit within ~one bucket width BELOW
        # the client number (client = server + wire + router queue)
        growth = serve_metrics._GROWTH
        p99 = {}
        for name, ep in summary["endpoints"].items():
            client = (rep_on["per_endpoint"].get(name) or {}).get("p99_s")
            merged = ep["latency"].get("p99_s")
            p99[name] = {
                "merged_s": merged,
                "client_s": client,
                "within_bucket_of_client": bool(
                    merged and client
                    and merged <= client * growth * 1.05
                ),
            }

        # in-process control: one scrape through the merge plumbing must
        # reproduce the server's own per-endpoint p99 EXACTLY (raw
        # buckets -> wire JSON -> merge -> quantile is lossless)
        direct = ht.serve.Server.restore(ckpt)
        direct.warmup()
        rep_direct = loadgen.run_open_loop(
            direct, reqs, args.rate, seed=args.seed, streams=args.streams,
        )
        m = json.loads(json.dumps(direct.metrics()))
        direct.close()
        s_inproc = summarize_cluster({"inproc": m})
        p99_exact = all(
            round(s_inproc["endpoints"][n]["latency"]["p99_s"], 6)
            == (rep_direct["per_endpoint"][n] or {}).get("p99_s")
            for n in s_inproc["endpoints"]
        )

        phase_on = {
            "digest": rep_on["digest"],
            "digest_match_off": rep_on["digest"] == rep_off["digest"],
            "completed": rep_on["completed"],
            "failed": rep_on["failed"],
            "shed": rep_on["shed"],
            "sampled_ids": len(ids),
            "complete_ids": len(complete),
            "hop_span_counts": hop_counts,
            "replica_tracing": _tracing_counters(router.scrape_metrics()),
            "merged_requests_total": sum(
                ep["requests"] for ep in summary["endpoints"].values()
            ),
            "fleet_qps": {
                n: ep["qps"] for n, ep in summary["endpoints"].items()
            },
            "p99": p99,
            "p99_exact_match_inproc": p99_exact,
            "clock_sync": {
                url: {"offset_s": round(s["offset"], 6),
                      "uncertainty_s": round(s["uncertainty"], 6)}
                for url, s in sync.items()
            },
            "merged_trace": {
                "path": trace_path,
                "pids": len(pids),
                "clock_sync_tracks": len(sync_pids),
                "trace_spans": sum(
                    1 for e in doc["traceEvents"]
                    if e.get("cat") == "trace_span"
                ),
            },
        }
        _emit(lines, {"phase_on": phase_on})
    finally:
        router.close()
        pool.close()

    # -- phase C: injected latency drives SLO burn ----------------------------
    reg.clear()
    slo_reqs = loadgen.make_requests(
        {"cdist": features.get("cdist", args.features)},
        args.slo_requests, args.seed + 2, max_rows=1,
    )
    fault = (f"serve.*:kind=latency:delay={args.fault_delay}:p=1.0"
             f":seed={args.seed}")
    pool, router, rep_slo = _run_pool(
        {"HEAT_TPU_TRACE_REQUESTS": "1", "HEAT_TPU_TRACE_SAMPLE": "1.0",
         "HEAT_TPU_FAULTS": fault},
        slos=[SLO("cdist", p99_s=args.slo_p99, availability=0.999)],
        requests=slo_reqs, rate=args.slo_rate, log_name="slo",
    )
    try:
        rows = router.check_slos()
        burn_events = [
            e for e in reg.events
            if e.get("kind") == "serve_net" and e.get("event") == "slo_burn"
        ]
        cdist_row = next(
            (r for r in rows if r["endpoint"] == "cdist"), {}
        )
        phase_slo = {
            "fault": fault,
            "completed": rep_slo["completed"],
            "failed": rep_slo["failed"],
            "shed": rep_slo["shed"],
            "slo": rows,
            "burn_rate": cdist_row.get("burn_rate"),
            "breach": cdist_row.get("breach"),
            "slo_burn_events": len(burn_events),
            "slo_burns_counter": int(
                reg.counters.get("serve_net.slo_burns", 0)
            ),
        }
        _emit(lines, {"phase_slo": phase_slo})
    finally:
        router.close()
        pool.close()

    summary_line = {
        "bench": "cluster_obs",
        "requests": args.requests,
        "offered_rate": args.rate,
        "replicas": args.replicas,
        "endpoints": sorted(eps),
        "off_tracing_zero": all(
            c == {"sampled": 0, "spans": 0}
            for c in phase_off["replica_tracing"].values()
        ) and phase_off["driver_tracing"] == {"sampled": 0, "spans": 0},
        "off_clean": rep_off["failed"] == 0 and rep_off["shed"] == 0,
        "on_clean": rep_on["failed"] == 0 and rep_on["shed"] == 0,
        "digest_match": phase_on["digest_match_off"],
        "metrics_merge_match": (
            phase_off["merged_requests_total"] == rep_off["completed"]
            and phase_on["merged_requests_total"] == rep_on["completed"]
        ),
        "sampled_ids": phase_on["sampled_ids"],
        "complete_ids": phase_on["complete_ids"],
        "hops_complete": (
            phase_on["sampled_ids"] > 0
            and phase_on["complete_ids"] == phase_on["sampled_ids"]
        ),
        "p99_within_bucket": all(
            v["within_bucket_of_client"]
            for v in phase_on["p99"].values()
        ),
        "p99_exact_match_inproc": phase_on["p99_exact_match_inproc"],
        "merged_trace_ok": (
            phase_on["merged_trace"]["pids"] >= 1 + args.replicas
            and phase_on["merged_trace"]["clock_sync_tracks"]
            == phase_on["merged_trace"]["pids"]
            and phase_on["merged_trace"]["trace_spans"] > 0
        ),
        "slo_breach": bool(phase_slo["breach"]),
        "slo_burn_emitted": phase_slo["slo_burn_events"] >= 1
        and phase_slo["slo_burns_counter"] >= 1,
        "on_chip": False,
        "cpu_fallback": "replica processes run on virtual cpu meshes "
                        "(an attached accelerator cannot be shared "
                        "across replica processes)",
    }
    _emit(lines, summary_line)
    telemetry.disable()

    if args.artifact:
        with open(args.artifact, "a") as f:
            for obj in lines:
                f.write(json.dumps(obj) + "\n")


if __name__ == "__main__":
    main()
