"""One run of one cell:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout (where BENCHMARK.json lies). Set-up (imports,
device, data from the seed, one warm-up call, which compiles or loads the
compile cache), then a window of calls as the cell's traffic mix says, then
the comparison with the plain reference. The last line of the output is the
result; the lines before it give the sample count and every number compared
beside its limit.
"""

import time

_T0 = time.perf_counter()  # set-up is counted from here

import argparse
import json
import os
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Any, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import loadgen, manifest, trace_reduce  # noqa: E402

NO_DEVICE = 2


@dataclass
class Reading:
    """What a metric's reader is given."""

    parts: manifest.Parts
    cell: dict
    config: dict
    chips: int
    peak: dict
    window: loadgen.Window
    setup_s: float
    items_per_call: int
    compiles: int
    trace: Optional[trace_reduce.Reduced] = None
    notes: dict = field(default_factory=dict)


def say(**fields: Any) -> None:
    print(json.dumps(fields), flush=True)


def pick_devices(parts: manifest.Parts, chips: int):
    """The cell's chips and their row of the peaks table, or None: a device
    that the table does not name is an error, not a default."""
    import jax

    devices = jax.devices()
    peak = parts.table("peaks").get(devices[0].device_kind)
    if peak is None or peak["platform"] != devices[0].platform:
        print(
            f"chipbench: {devices[0].platform} '{devices[0].device_kind}' is not in the "
            f"peaks table: this cell runs on {sorted(parts.table('peaks'))} only",
            file=sys.stderr,
        )
        return None, None
    if len(devices) < chips:
        print(f"chipbench: the cell asks for {chips} chips, JAX finds {len(devices)}", file=sys.stderr)
        return None, None
    return devices[:chips], peak


def compare(rows, limits):
    """Print each number compared beside its limit; the calls that failed."""
    failed = set()
    for call_index, row in rows:
        for name, value in row.items():
            ok = value <= limits[name]  # a NaN is not within any limit
            say(compared=name, call=call_index, value=value, limit=limits[name], ok=bool(ok))
            if not ok:
                failed.add(call_index)
    return failed


def main(argv=None, root: str = ".") -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    t0 = _T0 if argv is None else time.perf_counter()

    parts = manifest.load(root)
    cell = parts.cell(args.workload)
    config = parts.config(cell)
    mix = parts.data("traffic", cell["traffic"])
    loadgen.check_mix(mix)
    kind = parts.module("kinds", config["kind"])
    reference = parts.module("references", config["reference"])

    import jax

    import heat_tpu as ht
    from heat_tpu.core import program_cache
    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.telemetry import CompileWatcher

    program_cache.enable_persistent_cache()
    devices, peak = pick_devices(parts, cell["chips"])
    if devices is None:
        return NO_DEVICE
    comm = MeshCommunication(devices=devices)
    ht.use_comm(comm)

    state = kind.setup(config, comm, args.seed, reference)

    def one_call(i):
        result = kind.call(state, i)
        jax.block_until_ready(kind.outputs(result))
        return result

    kind.summary(one_call(-1))  # warm-up: every program the window uses
    setup_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as trace_dir:
        if args.trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with CompileWatcher() as watcher, jax.profiler.TraceAnnotation("chipbench.window"):
                window = loadgen.drive(
                    mix, args.seconds, one_call, kind.summary, jax.profiler.TraceAnnotation
                )
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        reduced = None
        if args.trace:
            reduced = trace_reduce.reduce(trace_reduce.load(trace_dir), peak["trace"])

    stats = [d.memory_stats() or {} for d in devices]
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(s.get("peak_bytes_in_use", 0) for s in stats),
    }

    good = [c for c in window.calls if c.error is None]
    for c in window.calls:
        if c.error:
            say(call=c.index, error=c.error)
    t_check = time.perf_counter()
    failed = {c.index for c in window.calls if c.error}
    if good:
        failed |= compare(kind.check(state, good, window.last_result), config["limits"])
    ordered = sorted(window.calls, key=lambda c: c.t0)
    say(
        samples=len(window.calls), window_s=window.seconds, setup_s=setup_s,
        check_s=time.perf_counter() - t_check, compiles_in_window=watcher.backend_compiles,
        slowest_call_ms=max(c.ms for c in window.calls),
        longest_gap_ms=max([(b.t0 - a.t1) * 1e3 for a, b in zip(ordered, ordered[1:])], default=0.0),
    )

    reading = Reading(
        parts, cell, config, cell["chips"], peak, window, setup_s,
        kind.items_per_call(config, cell["chips"]), watcher.backend_compiles, reduced,
    )
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in parts.metrics(section, cell):
        value = parts.module("metrics", m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if reading.notes:
        say(notes=reading.notes)

    result = {
        "correct": not failed and bool(good),
        "attempted": len(window.calls),
        "failed": len(failed),
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        if reduced is None:
            print("chipbench: the trace holds no device operation in the window", file=sys.stderr)
            return 1
        device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduced.top_ops()],
            "idle_gaps": [list(x) for x in reduced.top_gaps()],
        }
    say(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
