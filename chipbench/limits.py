"""Read what a cell's limits are set from, on the chip at the cell's own
size: for each seed, the numbers of ``correct`` for the program's answer to
one call and for the control's (the reference computed a precision below the
one the configuration states), all in one process.

    python3 chipbench/limits.py --workload <name> --seeds 1,2,3

Not part of a run: the benchmark's own runs never run the control. One JSON
line a seed; PERF.md keeps the readings beside each limit.
"""

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import loadgen, manifest  # noqa: E402
from chipbench.run import NO_DEVICE, pick_devices  # noqa: E402


def worst(rows):
    out = {}
    for _, row in rows:
        for name, v in row.items():
            out[name] = max(out.get(name, v), v)
    return out


def main(argv=None, root: str = ".") -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="whole numbers, comma-separated")
    args = ap.parse_args(argv)

    parts = manifest.load(root)
    cell = parts.cell(args.workload)
    config = parts.config(cell)
    kind = parts.module("kinds", config["kind"])
    reference = parts.module("references", config["reference"])

    import jax

    import heat_tpu as ht
    from heat_tpu.core import program_cache
    from heat_tpu.core.communication import MeshCommunication

    program_cache.enable_persistent_cache()
    devices, _ = pick_devices(parts, cell["chips"])
    if devices is None:
        return NO_DEVICE
    comm = MeshCommunication(devices=devices)
    ht.use_comm(comm)

    clock = time.perf_counter
    for seed in (int(s) for s in args.seeds.split(",")):
        t = clock()
        state = kind.setup(config, comm, seed, reference)
        jax.block_until_ready(kind.outputs(kind.call(state, 0)))
        t_setup = clock() - t
        t0 = clock()
        result = kind.call(state, 1)
        jax.block_until_ready(kind.outputs(result))
        t1 = clock()
        call = loadgen.Call(1, 0, t0, t1, kind.summary(result))
        program = worst(kind.check(state, [call], result))
        t2 = clock()
        del result, call
        control = kind.control(state, 1)
        t3 = clock()
        print(json.dumps({
            "seed": seed, "program": program, "control": control,
            "call_ms": (t1 - t0) * 1e3, "setup_s": t_setup,
            "reference_s": t2 - t1, "control_s": t3 - t2,
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
            ),
        }), flush=True)
        del state
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
