#!/usr/bin/env python
"""Streaming benchmark: out-of-core fit under a pinned memory budget,
then a versioned rolling replica update under open-loop load (ISSUE 16).

No reference analog (the reference framework streams through torch
DataLoaders; it has no bounded-memory fit-while-serve story). Phases,
each one JSONL line:

* ``{"stream_fit": ...}`` — write the synthetic workload to row-major
  files, pin ``HEAT_TPU_HBM_BUDGET``, and drive
  :class:`heat_tpu.streaming.ChunkStream` →
  :class:`~heat_tpu.streaming.StreamingMoments`. Reports rows/s
  ingested, the chunk-bytes watermark vs the load-all bytes (the
  out-of-core claim: ``watermark_below_load_all`` must be true when the
  budget is pinned below the file set), digest parity of the streamed
  moments against the in-memory full-pass reference, and the
  steady-stream compile ledger (``site_stats("streaming.")`` — one miss
  for the steady chunk shape, zero for every later chunk);
* ``{"rolling": ...}`` — the fit-while-serve headline: a 2-replica
  pool serves version 1 while checkpoints v2 and v3 are rolled through
  it replica-by-replica (:func:`heat_tpu.streaming.rolling_update`)
  under the SAME open-loop Poisson load as an undisturbed steady
  window. Reports p99 during the roll vs steady state, zero failed
  requests (the router's ``retry_in_flight`` at-least-once re-dispatch
  over idempotent queries), every surviving replica on the final
  version, and each replica's ``steady_backend_compiles`` (must be 0 —
  replacements warm from the shared compile cache);
* final summary — the ``on_chip`` + ``cpu_fallback`` honesty pair. The
  stream-fit phase runs on the attached platform (the pallas Welford
  kernel on TPU, masked XLA on CPU) and reports which one ran; replica
  processes ALWAYS run virtual CPU meshes (an attached accelerator
  cannot be shared across processes), so the rolling phase is a CPU
  number by construction and says so in-band.

``--artifact PATH`` appends the emitted lines (the committed
``artifacts/bench_streaming_r16.jsonl``). The CI streaming gate
(scripts/run_ci.sh) runs both phases small and asserts the
watermark/digest/zero-compile/zero-failure verdicts.
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

ROLL_CPU_REASON = (
    "replica processes run on virtual cpu meshes (an attached accelerator "
    "cannot be shared across replica processes)"
)


def add_args(p):
    p.add_argument("--files", type=int, default=2,
                   help="number of files the workload is sharded into")
    p.add_argument("--hbm-budget", default="64M",
                   help="HEAT_TPU_HBM_BUDGET pinned for the stream-fit "
                        "phase (chunks are sized from a quarter of it; "
                        "pick it below the file-set bytes to exercise "
                        "the out-of-core path). 'off' = unpinned")
    p.add_argument("--hdf5", action="store_true",
                   help="write HDF5 files instead of npy (needs h5py)")
    p.add_argument("--replicas", type=int, default=2,
                   help="replica count of the rolling-update pool")
    p.add_argument("--replica-mesh", type=int, default=4,
                   help="virtual CPU mesh size of every replica process")
    p.add_argument("--versions", type=int, default=3,
                   help="total endpoint versions rolled through the pool "
                        "(v1 serves at start; v2..vN roll in live)")
    p.add_argument("--requests", type=int, default=400,
                   help="requests per serving load window")
    p.add_argument("--rate", type=float, default=120.0,
                   help="offered Poisson rate, requests/second (the SAME "
                        "for the steady and the under-roll window)")
    p.add_argument("--streams", type=int, default=4,
                   help="concurrent loadgen submitter threads")
    p.add_argument("--serve-features", type=int, default=16,
                   help="feature width of the served cdist endpoint")
    p.add_argument("--skip-rolling", action="store_true",
                   help="stream-fit phase only (no subprocess pool)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--workdir", default=None,
                   help="data/checkpoint directory (default: a fresh "
                        "temp dir)")
    p.add_argument("--artifact", default=None,
                   help="append the emitted JSONL lines to this file")


def _emit(lines, obj):
    print(json.dumps(obj), flush=True)
    lines.append(obj)


def _write_files(args, workdir):
    """Shard the synthetic workload into row-major files; return
    (paths, dataset, the full array kept host-side for the in-memory
    reference)."""
    rng = np.random.default_rng(args.seed)
    full = rng.standard_normal((args.n, args.features)).astype(np.float32)
    per = -(-args.n // args.files)
    paths, dataset = [], None
    for i in range(args.files):
        block = full[i * per:(i + 1) * per]
        if not len(block):
            break
        if args.hdf5:
            import h5py

            dataset = "data"
            p = os.path.join(workdir, f"shard{i}.h5")
            with h5py.File(p, "w") as f:
                f.create_dataset(dataset, data=block)
        else:
            p = os.path.join(workdir, f"shard{i}.npy")
            np.save(p, block)
        paths.append(p)
    return paths, dataset, full


def _stream_fit(ht, args, lines, workdir):
    from heat_tpu import streaming, telemetry
    from heat_tpu.core import program_cache

    paths, dataset, full = _write_files(args, workdir)
    # heatlint: disable=HL005 -- deliberate benchmark-phase pin: the
    # bounded-memory claim is only a claim under a declared budget
    if args.hbm_budget and args.hbm_budget != "off":
        os.environ["HEAT_TPU_HBM_BUDGET"] = args.hbm_budget

    cs = streaming.ChunkStream(paths, dataset)
    sm = streaming.StreamingMoments()
    before = program_cache.site_stats("streaming.moments")
    t0 = time.perf_counter()
    for chunk in cs:
        sm.partial_fit(chunk)
    wall = time.perf_counter() - t0
    after = program_cache.site_stats("streaming.moments")

    # in-memory full-pass reference (host f64 — the order-independent
    # ground truth the streamed carry must agree with)
    ref_mean = full.astype(np.float64).mean(axis=0)
    ref_var = full.astype(np.float64).var(axis=0)
    mean_err = float(np.abs(sm.mean - ref_mean).max())
    var_err = float(np.abs(sm.var() - ref_var).max())

    watermark = None
    if telemetry.enabled():
        watermark = telemetry.get_registry().watermarks.get(
            "streaming.chunk_bytes"
        )
    row = {
        "rows": cs.rows_read,
        "files": len(paths),
        "format": "hdf5" if args.hdf5 else "npy",
        "chunks": cs.chunks_read,
        "chunk_rows": cs.chunk_rows,
        "seconds": round(wall, 4),
        "rows_per_s": round(cs.rows_read / wall, 1) if wall > 0 else None,
        "hbm_budget": args.hbm_budget,
        "chunk_bytes": cs.chunk_bytes(),
        "chunk_bytes_watermark": int(watermark) if watermark else None,
        "load_all_bytes": cs.load_all_bytes(),
        "watermark_below_load_all":
            cs.chunk_bytes() < cs.load_all_bytes(),
        "digest": {
            "mean_max_abs_err": mean_err,
            "var_max_abs_err": var_err,
            "match": bool(mean_err < 1e-4 and var_err < 1e-4),
        },
        "compiles": {
            "misses": after["misses"] - before["misses"],
            "hits": after["hits"] - before["hits"],
            # one program per distinct chunk shape (a ragged final
            # chunk is one more honest miss); everything else re-enters
            "steady_zero_compile":
                (after["misses"] - before["misses"])
                <= min(2, cs.chunks_read),
        },
    }
    _emit(lines, {"stream_fit": row})
    return row


def _versioned_checkpoints(ht, args, workdir):
    """v1..vN checkpoints of the same cdist endpoint with scaled
    parameters — same avals, so every publish/roll is a zero-compile
    program-argument swap."""
    rng = np.random.default_rng(args.seed + 3)
    y1 = rng.standard_normal(
        (128, args.serve_features)
    ).astype(np.float32)
    ckpts = []
    srv = ht.serve.Server()
    ep = ht.serve.cdist_query(y1)
    srv.register("cdist", ep)
    for v in range(1, args.versions + 1):
        if v > 1:
            srv.publish(
                "cdist", ep.with_params([y1 * float(v)], version=v),
                warm=False,
            )
        ck = os.path.join(workdir, f"v{v}.ckpt")
        srv.save(ck)
        ckpts.append(ck)
    srv.close()
    return ckpts


def _replica_net(pool):
    out = []
    for h in pool.replicas:
        if h.state != "up" or not h.alive():
            continue
        try:
            st = pool.stats(h.index)
        except Exception as e:  # noqa: BLE001 — a dead replica is data
            out.append({"replica": h.index, "error": repr(e)})
            continue
        out.append({
            "replica": h.index,
            "steady_backend_compiles":
                st.get("net", {}).get("steady_backend_compiles"),
            "versions": st.get("versions"),
        })
    return out


def _rolling(ht, args, lines, workdir):
    from benchmarks.serving import loadgen
    from heat_tpu import streaming
    from heat_tpu.serve.net import ReplicaPool, Router

    ckpts = _versioned_checkpoints(ht, args, workdir)
    env = {
        "HEAT_TPU_SERVE_MAX_BATCH": "4",
        "HEAT_TPU_SERVE_QUEUE_MAX": "64",
    }
    reqs = loadgen.make_requests(
        {"cdist": args.serve_features}, args.requests, args.seed,
        max_rows=1,
    )
    pool = ReplicaPool(
        ckpts[0], args.replicas, mesh=args.replica_mesh, env=env,
        log_dir=os.path.join(workdir, "logs"),
    )
    row = {"versions": len(ckpts), "replicas": args.replicas}
    try:
        t0 = time.perf_counter()
        pool.start()
        row["pool_ready_seconds"] = round(time.perf_counter() - t0, 3)
        # retry_in_flight: queries are idempotent and a draining replica
        # may reset accepted connections — the zero-failure roll contract
        router = Router(pool, retries=3, workers=8, poll_ms=100.0,
                        retry_in_flight=True)
        try:
            steady = loadgen.run_open_loop(
                router, reqs, args.rate, seed=args.seed,
                streams=args.streams,
            )
            row["steady"] = {
                "achieved_qps": steady["achieved_qps"],
                "completed": steady["completed"],
                "failed": steady["failed"],
                "p50_s": steady["latency"].get("p50_s"),
                "p99_s": steady["latency"].get("p99_s"),
            }

            # the under-roll window: the SAME load runs while v2..vN
            # roll through the pool replica-by-replica
            result = {}

            def load():
                result["report"] = loadgen.run_open_loop(
                    router, reqs, args.rate, seed=args.seed + 1,
                    streams=args.streams,
                )

            t = threading.Thread(target=load, daemon=True)
            t.start()
            rolls = []
            for ck in ckpts[1:]:
                rolls.append(streaming.rolling_update(pool, router, ck))
            t.join()
            under = result["report"]
            net = _replica_net(pool)
            row["rolls"] = [
                {"seconds": r["seconds"], "steps": len(r["steps"])}
                for r in rolls
            ]
            row["under_roll"] = {
                "achieved_qps": under["achieved_qps"],
                "completed": under["completed"],
                "failed": under["failed"],
                "p50_s": under["latency"].get("p50_s"),
                "p99_s": under["latency"].get("p99_s"),
            }
            row["p99_roll_over_steady"] = (
                round(row["under_roll"]["p99_s"] / row["steady"]["p99_s"], 2)
                if row["steady"].get("p99_s") else None
            )
            row["zero_failed_requests"] = (
                steady["failed"] == 0 and under["failed"] == 0
            )
            row["per_replica"] = net
            row["all_on_final_version"] = all(
                (r.get("versions") or {}).get("cdist") == len(ckpts)
                for r in net
            )
            row["steady_backend_compiles_ok"] = all(
                r.get("steady_backend_compiles") == 0 for r in net
            )
        finally:
            router.close()
    finally:
        pool.close()
    _emit(lines, {"rolling": row})
    return row


def main():
    p = base_parser("heat_tpu streaming benchmark (out-of-core fit + "
                    "versioned rolling replica update)")
    add_args(p)
    args = p.parse_args()
    ht = bootstrap(args)
    import jax

    from heat_tpu import telemetry

    devs = jax.devices()
    on_chip = devs[0].platform != "cpu"
    lines = []
    workdir = args.workdir or tempfile.mkdtemp(prefix="heat_tpu_stream_")
    os.makedirs(workdir, exist_ok=True)

    stream_row = _stream_fit(ht, args, lines, workdir)
    rolling_row = None
    if not args.skip_rolling:
        rolling_row = _rolling(ht, args, lines, workdir)

    summary = {
        "bench": "streaming",
        "rows": args.n,
        "features": args.features,
        "stream_fit": {
            "rows_per_s": stream_row.get("rows_per_s"),
            "watermark_below_load_all":
                stream_row.get("watermark_below_load_all"),
            "digest_match": stream_row.get("digest", {}).get("match"),
            "steady_zero_compile":
                stream_row.get("compiles", {}).get("steady_zero_compile"),
            # the stream-fit phase runs on the attached platform
            "on_chip": on_chip,
            **({} if on_chip else {
                "cpu_fallback":
                    "default backend is cpu (no accelerator attached)",
            }),
        },
        "rolling": None if rolling_row is None else {
            "p99_steady_s": rolling_row.get("steady", {}).get("p99_s"),
            "p99_under_roll_s":
                rolling_row.get("under_roll", {}).get("p99_s"),
            "p99_roll_over_steady":
                rolling_row.get("p99_roll_over_steady"),
            "zero_failed_requests":
                rolling_row.get("zero_failed_requests"),
            "all_on_final_version":
                rolling_row.get("all_on_final_version"),
            "steady_backend_compiles_ok":
                rolling_row.get("steady_backend_compiles_ok"),
            # replicas are subprocesses: always a CPU number
            "on_chip": False,
            "cpu_fallback": ROLL_CPU_REASON,
        },
        "on_chip": on_chip and rolling_row is None,
        "cpu_fallback": (
            None if on_chip and rolling_row is None
            else ROLL_CPU_REASON if rolling_row is not None
            else "default backend is cpu (no accelerator attached)"
        ),
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
