"""Shared runner plumbing for the scaling-benchmark harness.

Every per-algorithm runner (reference: per-framework scripts like
benchmarks/kmeans/heat-gpu.py:1-27) goes through here: mesh bootstrap,
workload construction (synthetic or HDF5 via ``ht.load``), timed trials,
and JSON reporting.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def base_parser(description: str) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=description)
    p.add_argument("--n", type=int, default=100_000,
                   help="rows of the synthetic workload")
    p.add_argument("--features", type=int, default=64,
                   help="columns of the synthetic workload")
    p.add_argument("--trials", type=int, default=3)
    p.add_argument("--file", type=str, default=None,
                   help="HDF5 file to load instead of synthetic data "
                        "(reference data parity: cityscapes/SUSY/eurad)")
    p.add_argument("--dataset", type=str, default=None,
                   help="dataset name inside --file")
    p.add_argument("--mesh", type=int, default=0,
                   help="force an n-device virtual CPU mesh (0 = use the "
                        "attached platform as-is)")
    p.add_argument("--audit", action="store_true",
                   help="enable telemetry plus the HLO collective auditor: "
                        "every instrumented op lower-compiles its program "
                        "and diffs the collectives XLA actually emitted "
                        "against the analytic cost model; the summary gains "
                        "a telemetry.hlo_collectives section "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--plan", choices=("auto", "monolithic", "chunked",
                                      "alltoall"),
                   default=None,
                   help="relayout planning policy for this run (sets "
                        "HEAT_TPU_RELAYOUT_PLAN; ISSUE 6, "
                        "docs/TUNING_RUNBOOK.md §0.8). With telemetry on, "
                        "the summary gains a telemetry.relayout_plan "
                        "block of the planner's decisions")
    p.add_argument("--tune-db", metavar="DIR",
                   # heatlint: disable=HL005 -- read before `import heat_tpu`:
                   # the env must be set before the package import
                   default=os.environ.get("HEAT_TPU_TUNE_DB") or None,
                   help="persistent tuning-DB directory (default: "
                        "$HEAT_TPU_TUNE_DB). Arms the autotuner "
                        "(HEAT_TPU_AUTOTUNE=1): persisted knob winners for "
                        "this mesh are adopted at dispatch time, so a "
                        "repeated bench process starts *tuned* with zero "
                        "measured trials (docs/AUTOTUNE.md)")
    return p


def bootstrap(args):
    """Apply --mesh BEFORE jax initializes a backend, then import heat_tpu."""
    if getattr(args, "plan", None):
        os.environ["HEAT_TPU_RELAYOUT_PLAN"] = args.plan
    if getattr(args, "tune_db", None):
        # set before anything imports heat_tpu; --tune-db arms
        # the autotuner UNLESS the environment already pins
        # HEAT_TPU_AUTOTUNE (an explicit =0 must keep a baseline run
        # untuned even when HEAT_TPU_TUNE_DB is exported globally)
        os.environ["HEAT_TPU_TUNE_DB"] = args.tune_db
        os.environ.setdefault("HEAT_TPU_AUTOTUNE", "1")
    if args.mesh:
        # one canonical copy of the XLA_FLAGS/JAX_PLATFORMS dance, shared
        # with the telemetry audit CLI (backend init is lazy, so importing
        # the package to reach the helper is safe)
        from heat_tpu.utils.backend_probe import force_virtual_cpu_mesh

        force_virtual_cpu_mesh(args.mesh)
    import heat_tpu as ht

    # repeated sweep processes deserialize instead of recompiling: JAX's
    # persistent cache at $JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache
    ht.program_cache.enable_persistent_cache()

    if getattr(args, "audit", False):
        # ground-truth collective accounting rides on the telemetry event
        # stream, so --audit implies recording
        if not ht.telemetry.enabled():
            ht.telemetry.enable()
        ht.telemetry.hlo.enable_audit()
    return ht


def load_or_make(ht, args, *, dtype=None, split=0):
    """The benchmark operand: ``ht.load`` when --file is given (per-slab
    range reads on multi-host, io.py), synthetic ``randn`` otherwise."""
    dtype = dtype or ht.float32
    if args.file:
        if not args.dataset:
            raise SystemExit("--file requires --dataset (the HDF5 dataset "
                             "name inside the file)")
        data = ht.load(args.file, dataset=args.dataset, split=split)
        return data.astype(dtype) if data.dtype != dtype else data
    return ht.random.randn(args.n, args.features, dtype=dtype, split=split)


def timed_trials(args, fit, sync):
    """Run ``fit`` ``args.trials`` times; print one JSON line per trial
    (the reference prints per-trial wall-clock, heat-gpu.py:22-27) and a
    summary with the best time. With ``HEAT_TPU_TELEMETRY=1`` the summary
    gains a ``telemetry`` block: per-phase compile/execute/bytes-moved
    columns plus the memory high-water mark; with ``--audit`` also an
    ``hlo_collectives`` section of ground-truth emitted collective
    counts/bytes and the drift verdict (docs/OBSERVABILITY.md)."""
    times = []
    for trial in range(args.trials):
        t0 = time.perf_counter()
        out = fit()
        sync(out)  # device-queue barrier: timing must include the work
        dt = time.perf_counter() - t0
        times.append(dt)
        print(json.dumps({"trial": trial, "seconds": round(dt, 4)}),
              flush=True)
    summary = {
        "best_seconds": round(min(times), 4),
        "mean_seconds": round(sum(times) / len(times), 4),
        "trials": args.trials,
        "devices": _device_info(),
    }
    from heat_tpu import autotune, telemetry

    if telemetry.enabled():
        telemetry.memory.watermark("post_trials")
        summary.update(telemetry.report.bench_fields())
    if autotune.enabled():
        # what the tuner did for THIS run: trials, DB hits, adopted
        # config per site (docs/AUTOTUNE.md; --tune-db arms this)
        summary["autotune"] = autotune.bench_field()
    print(json.dumps(summary), flush=True)
    return summary


def _device_info():
    import jax

    d = jax.devices()
    return {"count": len(d), "kind": d[0].device_kind}


def run(description, add_args, build, fit_factory):
    """Standard runner main: parse → bootstrap → build workload →
    timed trials. ``add_args(parser)`` adds algorithm flags;
    ``build(ht, args)`` returns the operand(s); ``fit_factory(ht, args,
    operands)`` returns (fit, sync)."""
    parser = base_parser(description)
    add_args(parser)
    args = parser.parse_args()
    ht = bootstrap(args)
    operands = build(ht, args)
    fit, sync = fit_factory(ht, args, operands)
    # The first call compiles AND executes; the two must not be blended
    # into one "compile_seconds" (the old behavior — advisor round-5
    # finding). A CompileWatcher accumulates the XLA trace/lower/backend
    # compile durations that fire during the call — the same stages an AOT
    # `jit(f).lower(...).compile()` runs (`fit` itself mixes host logic
    # with device ops, so it cannot be lowered whole) — giving the honest
    # split: compile_seconds (pipeline time) vs first_call_seconds (wall).
    with ht.telemetry.CompileWatcher() as cw:
        t0 = time.perf_counter()
        sync(fit())
        first_call = time.perf_counter() - t0
    print(json.dumps({
        "compile_seconds": round(cw.seconds, 4),
        "first_call_seconds": round(first_call, 4),
    }), flush=True)
    if ht.telemetry.enabled():
        # drop ONLY the warmup call's span events: their wall-clock
        # contains compile time, and leaving them in would re-blend
        # compile into the per-phase execute_seconds the summary reports.
        # The compile and collective_trace events must survive: for
        # jit-cached fits they fire only while the warmup traces/compiles,
        # so a full clear() would permanently empty the summary's
        # telemetry.compile_seconds / traced_collectives fields. (Ops that
        # build a fresh traced closure per call — the shard_map ring
        # kernels — re-trace on every trial, so those accumulated fields
        # scale with --trials; the top-level compile_seconds printed above
        # is the warmup-window number either way.) The JSONL sink keeps
        # the full stream (append-only) regardless.
        ht.telemetry.get_registry().clear(kinds=("span",))
    timed_trials(args, fit, sync)


if __name__ == "__main__":
    print("import me from a per-algorithm runner", file=sys.stderr)
    sys.exit(2)
