"""A training cell's step timed over many seeds, or many steps, in one process
(one compile), without the check: how the step's time follows the seed and the
step's number, and with them the share of the assignments that lands on the held
experts.

    python3 chipbench/step_sweep.py --workload <name> --seeds 1,2,3 --steps 24 [--series] [--set key=value ...]

A line a seed: the median, least and largest call (ms, host clock), the held
share by expert layer (in even shares: its mean, its largest a layer, the first
step's) and how many steps had a layer past ``--edge`` even shares (the default:
the configuration's ``held_window``, the first window of held rows; 2 where it
has none). ``--series`` adds every step's time beside the largest share of a
layer in it. ``--set`` overrides keys of the configuration for this process
alone (``held_window=2``): what a change of the configuration would cost, before
it is made.

Not part of a run: the benchmark's own runs never call it. PERF.md keeps what
it read (sections 4 and 6). For the kinds whose ``call`` returns the routing's
counts (``lm_step`` and those built on it).
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from chipbench import manifest  # noqa: E402
from chipbench.run import NO_DEVICE, pick_devices  # noqa: E402


def main(argv=None, root: str = ".") -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="whole numbers, comma-separated")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--series", action="store_true")
    ap.add_argument("--edge", type=float, default=None)
    ap.add_argument("--set", action="append", default=[], metavar="key=value", help="a configuration key for this process (JSON value)")
    args = ap.parse_args(argv)

    parts = manifest.load(root)
    cell = parts.cell(args.workload)
    config = dict(parts.config(cell))
    for item in args.set:
        key, _, value = item.partition("=")
        config[key] = json.loads(value)
    kind = parts.module("kinds", config["kind"])
    reference = parts.module("references", config["reference"])

    import jax
    import numpy as np

    import heat_tpu as ht
    from heat_tpu.core import program_cache
    from heat_tpu.core.communication import MeshCommunication

    program_cache.enable_persistent_cache()
    devices, _ = pick_devices(parts, cell["chips"])
    if devices is None:
        return NO_DEVICE
    comm = MeshCommunication(devices=devices)
    ht.use_comm(comm)

    seeds = [int(s) for s in args.seeds.split(",")]
    edge = config.get("held_window", 2.0) if args.edge is None else args.edge
    first, held = config.get("first_expert_held", 0), config.get("num_experts_held", config["num_experts"])
    state = kind.setup(config, comm, seeds[0], reference)
    kind.call(state, -1)  # the compile, outside every timing
    for seed in seeds:
        state.seed = seed
        state.reset()
        ms, shares = [], []
        for i in range(args.steps):
            t0 = time.perf_counter()
            result = kind.call(state, i)
            jax.block_until_ready(kind.outputs(result))
            ms.append((time.perf_counter() - t0) * 1e3)
            counts = np.asarray(result.aux["expert_counts"])  # expert layers x experts
            shares.append(counts[:, first:first + held].sum(-1) / (counts.sum(-1) * held / config["num_experts"]))
        share = np.asarray(shares)
        print(json.dumps({
            "seed": seed, "steps": args.steps, "p50_ms": statistics.median(ms), "min_ms": min(ms), "max_ms": max(ms),
            "p50_ms_by_quarter": [statistics.median(q.tolist()) for q in np.array_split(np.asarray(ms), 4)],
            "share_mean": float(share.mean()), "share_max_by_layer": share.max(0).round(3).tolist(),
            "share_first_step": share[0].round(3).tolist(), "edge": edge,
            "steps_past_the_edge": int((share > edge).any(1).sum()), **{k: config[k] for k in ("held_window",) if k in config},
        }), flush=True)
        if args.series:
            print(json.dumps({"seed": seed, "series": [[round(m, 1), round(float(s.max()), 3), int(s.argmax())] for m, s in zip(ms, share)]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
