"""Central registry of every ``HEAT_TPU_*`` environment knob (ISSUE 10).

Before this module, ~20 ``os.environ`` reads were scattered across the
package — each with its own parse convention, its own default, and its own
(often missing) documentation. The static analyzer's HL005 rule now rejects
any direct ``HEAT_TPU_*`` environ read outside this file, so every knob is
declared exactly once, carrying its type, default, and docstring. The
``docs/API.md`` knob table is generated from :func:`markdown_table` and a
test pins the two in sync, so the env-var docs can never drift again.

This module is deliberately a **leaf**: stdlib imports only, no package
imports. ``heat_tpu.telemetry`` and ``heat_tpu.resilience`` load *before*
``heat_tpu.core`` during ``import heat_tpu``, so the registry must be
importable from anywhere in the package graph without touching
``heat_tpu.core.__init__``. The public face is
:mod:`heat_tpu.core.knobs`, a re-export of this module.

Usage inside the package::

    from heat_tpu import _knobs as knobs       # safe at any import depth
    raw = knobs.raw("HEAT_TPU_FUSION", "1")    # registered-name-checked
    on = knobs.get("HEAT_TPU_FUSION")          # typed parse

Modules with bespoke parse rules (byte-suffix budgets, fault specs,
comma ladders) call :func:`raw` and keep their local parser; simple
bool/int/float/enum knobs can use :func:`get` directly. Either way the
read is registered, typed, and documented here.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field
from typing import Dict, Iterable, Optional, Tuple, Union

__all__ = [
    "Knob",
    "Tunable",
    "REGISTRY",
    "raw",
    "get",
    "names",
    "tunables",
    "default_raw",
    "overrides",
    "set_override",
    "clear_overrides",
    "overlay",
    "markdown_table",
    "FALSY",
    "TRUTHY",
]

# Shared string-to-bool conventions. Default-ON knobs ("is the feature
# still enabled?") treat anything outside FALSY as on; default-OFF
# activation knobs ("did the user opt in?") require an explicit TRUTHY.
FALSY = ("0", "false", "off", "no")
TRUTHY = ("1", "true", "yes", "on")


@dataclass(frozen=True)
class Tunable:
    """Autotuner metadata for one knob (ISSUE 11): the candidate search
    space declared NEXT TO the knob, not hardcoded in the tuner.

    ``values`` are raw environment strings (what ``heat_tpu.autotune``
    installs into the knob overlay while searching). ``kind`` is the
    constraint class the trial validator enforces:

    * ``exact`` — every candidate value must leave results bit-identical
      (fusion depth, relayout plan, ring overlap); validated by digest.
    * ``lossy`` — values other than ``exact_value`` may change numerics
      (collective precision, cdist dot strategy, non-exact serve
      kernels); only searched under a caller-stated error budget, and a
      winning lossy pick must measure within it.
    * ``neutral`` — scheduling/throughput only (serve ladder, gather
      window, queue bound); results are still digest-validated where the
      workload produces any.
    """

    values: Tuple[str, ...]
    kind: str  # 'exact' | 'lossy' | 'neutral'
    exact_value: Optional[str] = None  # lossy knobs: the exact-semantics value


@dataclass(frozen=True)
class Knob:
    """One declared environment knob.

    ``type`` is one of ``bool`` / ``int`` / ``float`` / ``str`` / ``enum``
    / ``bytes`` (byte count with K/M/G/T suffixes) / ``spec`` (structured
    mini-language parsed by its owning module). ``default`` is the
    effective value when the variable is unset or malformed (None = the
    feature is simply off / derived elsewhere). ``scope`` groups the docs
    table: ``runtime`` knobs are read by the package itself, ``bench`` by
    the benchmark harnesses, ``ci`` by ``scripts/run_ci.sh``, ``tests`` by
    the pytest conftest. ``tunable`` (perf-relevant knobs only) declares
    the autotuner's candidate values and constraint class.
    """

    name: str
    type: str
    default: Union[bool, int, float, str, None]
    doc: str
    choices: Tuple[str, ...] = field(default=())
    scope: str = "runtime"
    tunable: Optional[Tunable] = None


REGISTRY: Dict[str, Knob] = {}


def _register(
    name: str,
    type: str,
    default,
    doc: str,
    *,
    choices: Tuple[str, ...] = (),
    scope: str = "runtime",
    tunable: Optional[Tunable] = None,
) -> None:
    if name in REGISTRY:
        raise ValueError(f"knob {name!r} registered twice")
    if not name.startswith("HEAT_TPU_"):
        raise ValueError(f"knob {name!r} must be namespaced HEAT_TPU_*")
    if tunable is not None:
        if tunable.kind not in ("exact", "lossy", "neutral"):
            raise ValueError(
                f"knob {name!r}: tunable kind {tunable.kind!r} is not one "
                "of exact/lossy/neutral"
            )
        if not tunable.values or not all(
            isinstance(v, str) and v for v in tunable.values
        ):
            raise ValueError(
                f"knob {name!r}: tunable values must be non-empty raw "
                f"strings, got {tunable.values!r}"
            )
        if tunable.kind == "lossy" and tunable.exact_value is None:
            raise ValueError(
                f"knob {name!r}: a lossy tunable must declare its "
                "exact-semantics value"
            )
    REGISTRY[name] = Knob(
        name, type, default, doc, choices=choices, scope=scope,
        tunable=tunable,
    )


# -- runtime knobs ------------------------------------------------------------

_register(
    "HEAT_TPU_TELEMETRY", "bool", False,
    "Turn telemetry recording on at `import heat_tpu` "
    "(docs/OBSERVABILITY.md). Counters, spans, collective cost events and "
    "compile accounting; one flag check per call site when off.",
)
_register(
    "HEAT_TPU_TELEMETRY_SINK", "str", None,
    "JSONL file that telemetry events stream to; unset records in memory "
    "only.",
)
_register(
    "HEAT_TPU_HLO_AUDIT", "bool", False,
    "Lower-compile every cached program and fail on predicted-vs-emitted "
    "collective drift (telemetry/hlo.py; the ground-truth auditor).",
)
_register(
    "HEAT_TPU_HLO_TOLERANCE", "float", 0.1,
    "Relative wire-byte drift tolerated by the HLO auditor before an "
    "audit fails.",
)
_register(
    "HEAT_TPU_PROGRAM_CACHE", "int", 512,
    "Max entries in the process-global compiled-program registry "
    "(core/program_cache.py); LRU eviction beyond it.",
)
_register(
    "HEAT_TPU_FUSION", "bool", True,
    "Elementwise defer-and-fuse dispatch (core/fusion.py). `0` restores "
    "pure-eager dispatch bit-for-bit.",
    tunable=Tunable(("1", "0"), "exact"),
)
_register(
    "HEAT_TPU_FUSION_REDUCE", "bool", True,
    "Fusion 2.0 through-reduction absorption and matmul/moments epilogue "
    "grafting. `0` restores flush-at-reduction dispatch.",
    tunable=Tunable(("1", "0"), "exact"),
)
_register(
    "HEAT_TPU_FUSION_DEPTH", "int", 16,
    "Max fused-chain depth before a forced flush (node cap is 4x this).",
    tunable=Tunable(("4", "8", "16", "32", "64"), "exact"),
)
_register(
    "HEAT_TPU_RELAYOUT_PLAN", "enum", "auto",
    "Relayout planning policy (core/relayout_planner.py): `auto` picks "
    "from tensor size vs the HBM budget; the rest force one decomposition.",
    choices=("auto", "monolithic", "chunked", "alltoall"),
    tunable=Tunable(("auto", "monolithic", "chunked", "alltoall"), "exact"),
)
_register(
    "HEAT_TPU_RING_OVERLAP", "bool", True,
    "Double-buffered ring schedules (cdist/manhattan/rbf, TSQR gram "
    "ring): issue the next hop's ppermute under the local GEMM. `0` "
    "restores the serial p-hop kernels verbatim.",
    tunable=Tunable(("1", "0"), "exact"),
)
_register(
    "HEAT_TPU_COLLECTIVE_PREC", "enum", "off",
    "Wire precision of payload-moving collectives "
    "(core/collective_prec.py, ISSUE 9): bf16 cast-move-upcast, int8 / "
    "blockwise EQuARX max-abs quantization. Exact-semantics sites pin "
    "`off` per call.",
    choices=("off", "bf16", "int8", "blockwise"),
    tunable=Tunable(
        ("off", "bf16", "int8", "blockwise"), "lossy", exact_value="off"
    ),
)
_register(
    "HEAT_TPU_COLLECTIVE_PREC_BLOCK", "int", 128,
    "Blockwise-quantization scale granularity in elements.",
    tunable=Tunable(("64", "128", "256"), "lossy", exact_value="128"),
)
_register(
    "HEAT_TPU_RETRIES", "int", 0,
    "Transient-failure retry budget of the guarded dispatch sites "
    "(resilience/guard.py); 0 = retries off.",
)
_register(
    "HEAT_TPU_RETRY_BASE", "float", 0.05,
    "First retry backoff in seconds (doubles per attempt, jittered).",
)
_register(
    "HEAT_TPU_RETRY_CAP", "float", 2.0,
    "Retry backoff ceiling in seconds.",
)
_register(
    "HEAT_TPU_HBM_BUDGET", "bytes", None,
    "Per-device memory budget for pre-flight admission (plain bytes or "
    "K/M/G/T suffixes, e.g. `8G`). Unset disables the guard; malformed "
    "values disable it too (resilience/memory_guard.py).",
)
_register(
    "HEAT_TPU_FAULTS", "spec", None,
    "Deterministic fault-injection spec installed at `import heat_tpu` "
    "(resilience/faults.py), e.g. `relayout:kind=resource:calls=1`.",
)
_register(
    "HEAT_TPU_SERVE_MAX_BATCH", "int", 64,
    "Top bucket of the serving micro-batch ladder (serve/server.py).",
    tunable=Tunable(("16", "32", "64", "128"), "neutral"),
)
_register(
    "HEAT_TPU_SERVE_LADDER", "str", None,
    "Explicit comma-separated bucket ladder; unset derives powers of two "
    "up to the max batch.",
)
_register(
    "HEAT_TPU_SERVE_MAX_WAIT_MS", "float", 2.0,
    "Micro-batch gather window in milliseconds.",
    tunable=Tunable(("0.5", "1.0", "2.0", "4.0"), "neutral"),
)
_register(
    "HEAT_TPU_SERVE_QUEUE_MAX", "int", 1024,
    "Admission-control bound on pending serving requests (503-style shed "
    "beyond it).",
    tunable=Tunable(("256", "1024", "4096"), "neutral"),
)
_register(
    "HEAT_TPU_SERVE_EXACT", "bool", True,
    "Batch-shape-stable exact serving kernels (batched == solo "
    "bit-identity); `0` selects the MXU GEMM forms.",
    tunable=Tunable(("1", "0"), "lossy", exact_value="1"),
)

# -- hierarchy-aware tiered collectives (heat_tpu/core/topology.py, ISSUE 15) -

_register(
    "HEAT_TPU_TOPOLOGY", "str", None,
    "Declared 2-level (node x local) factorization of the device mesh, "
    "e.g. `2x4`: `node` is the slow (DCN) tier, `local` the fast (ICI) "
    "tier (core/topology.py). Unset auto-detects: the host-process "
    "structure on real multi-host hardware, the DASO-style emulated "
    "2-node split on a single even-sized host mesh. Malformed or "
    "mismatched values (node*local != mesh size) fall back to "
    "auto-detection.",
)
_register(
    "HEAT_TPU_HIERARCHICAL", "bool", False,
    "Tiered lowering of the payload-moving MeshCommunication wrappers "
    "(psum/all_gather/reduce_scatter/all_to_all): in-node reduce-scatter "
    "-> cross-node collective over the 1/local shard -> in-node "
    "all-gather, with per-tier wire precision (exact inside the node, "
    "HEAT_TPU_HIERARCHICAL_PREC across). `0` (default) keeps the flat "
    "lowering bit-for-bit.",
    tunable=Tunable(("0", "1"), "exact"),
)
_register(
    "HEAT_TPU_HIERARCHICAL_PREC", "str", None,
    "Wire precision of the CROSS-NODE tier of a tiered collective "
    "(core/topology.py; the DCN wire): off | bf16 | int8 | blockwise. "
    "Unset inherits HEAT_TPU_COLLECTIVE_PREC; the in-node (ICI) tier "
    "always moves exact.",
    tunable=Tunable(
        ("off", "bf16", "int8", "blockwise"), "lossy", exact_value="off"
    ),
)
_register(
    "HEAT_TPU_DCN_PREMIUM", "float", 8.0,
    "Relative cost of one cross-node (DCN) wire byte vs one in-node "
    "(ICI) byte in the analytic cost model "
    "(telemetry/collectives.weighted_wire): the planner and autotuner "
    "price tiered vs flat lowerings with DCN bytes multiplied by this "
    "factor. ~8-10 matches the production ICI/DCN bandwidth gap.",
)

# -- full FSDP parameter sharding (heat_tpu/parallel/fsdp.py, ISSUE 18) -------

_register(
    "HEAT_TPU_FSDP", "bool", False,
    "Full FSDP parameter sharding in heat_tpu.nn.FSDP: parameters live "
    "as flat 1/p shards on the mesh and each layer's weights are "
    "all-gathered just-in-time (tiered under HEAT_TPU_HIERARCHICAL=1), "
    "consumed, and re-scattered through the gather's transpose. `0` "
    "(default) keeps the replicated DataParallel dispatch bit-for-bit "
    "— the FSDP wrapper falls back to the identical replicated step "
    "program.",
    tunable=Tunable(("0", "1"), "exact"),
)
_register(
    "HEAT_TPU_FSDP_PREFETCH", "int", 1,
    "FSDP gather-prefetch depth: how many layers AHEAD of the one "
    "computing the weight all-gather is issued (parallel/fsdp.py "
    "prefetch window; the PR 6 ring-overlap trick applied to the "
    "weight stream, arXiv:2211.05322). Depth d keeps at most d+1 "
    "layers' gathered weights live — 0 is fully serial "
    "(minimum memory), larger depths give XLA's latency-hiding "
    "scheduler room to hide the gather under the previous layers' "
    "GEMMs. Pure scheduling: outputs are bit-identical at every depth.",
    tunable=Tunable(("0", "1", "2"), "neutral"),
)
_register(
    "HEAT_TPU_FSDP_PREC", "str", None,
    "Wire precision of FSDP weight gathers (and their transpose "
    "reduce-scatters) for partition rules that do not pin one: off | "
    "bf16 | int8 | blockwise. Unset inherits the tiered cross-node "
    "chain (HEAT_TPU_HIERARCHICAL_PREC, then HEAT_TPU_COLLECTIVE_PREC) "
    "under HEAT_TPU_HIERARCHICAL=1, and `off` (exact) on a flat mesh — "
    "compressed weight gathers change the model every step, so the "
    "flat default stays bit-exact.",
    tunable=Tunable(
        ("off", "bf16", "int8", "blockwise"), "lossy", exact_value="off"
    ),
)

# -- pipeline parallelism knobs (heat_tpu/parallel, ISSUE 19) -----------------

_register(
    "HEAT_TPU_PIPELINE_SCHEDULE", "enum", "gpipe",
    "Pipeline-training schedule of ht.nn.Pipeline / parallel/pipeline.py "
    "site pipeline.step (parallel/schedule.py tables): `gpipe` (default "
    "— all-forward wave, flush, all-backward wave, bit-compat with the "
    "historical kernel lineage) or `1f1b` (PipeDream-flush one-forward-"
    "one-backward: same results bit-for-bit — every stage still runs "
    "its backwards in increasing microbatch order — with the activation "
    "stash cut from M to min(S, M) in-flight microbatches and strictly "
    "fewer steady-window bubble ticks whenever M > 1 and S > 2).",
    choices=("gpipe", "1f1b"),
    tunable=Tunable(("gpipe", "1f1b"), "exact"),
)
_register(
    "HEAT_TPU_PIPELINE_STAGES", "int", 0,
    "Stage count of the pipeline mapping (parallel/schedule.plan_stages). "
    "0 (default) = auto: the node count of an ACTIVE 2-level topology "
    "(stages ARE the HEAT_TPU_TOPOLOGY node groups — every inter-stage "
    "hop crosses the DCN tier, and the `local` positions inside a stage "
    "keep the FSDP weight tier), else one stage per mesh position. Must "
    "divide the mesh size.",
)
_register(
    "HEAT_TPU_PIPELINE_MICROBATCHES", "int", 0,
    "Microbatch count M of ht.nn.Pipeline steps. 0 (default) = auto "
    "(the stage count S, the classic balanced point: bubble fraction "
    "(S-1)/(S+M-1) at M=S). Must divide the batch. Pure scheduling at "
    "fixed M; CHANGING M regroups the per-microbatch loss mean and "
    "gradient accumulation, so M itself tunes as a neutral axis only "
    "through the autotuner's guarded measured trials.",
    tunable=Tunable(("0", "2", "4", "8"), "neutral"),
)

# -- sparse container knobs (heat_tpu/sparse, ISSUE 13) -----------------------

_register(
    "HEAT_TPU_SPARSE_DENSE_THRESHOLD", "float", 0.25,
    "Density (nnz / rows*cols) above which sparse construction paths "
    "fall back to the dense pipeline (heat_tpu/sparse; the "
    "graph.Laplacian eNeighbour path densifies past it — a CSR denser "
    "than this moves more bytes than the dense GEMM it replaces).",
)
_register(
    "HEAT_TPU_SPARSE_SPMV_PREC", "enum", "off",
    "Wire precision of the float VALUE payloads in the sparse "
    "spmv/spmm collectives (operand gather + result all-reduce, "
    "heat_tpu/sparse/ops.py). Default pinned exact: index/indptr "
    "payloads never ride these hops at all (they stay shard-local), "
    "and the default keeps Krylov matvecs bit-stable. `bf16` moves the "
    "gathered operand as the uint16 bit pattern and the all-reduce on "
    "a bf16 payload.",
    choices=("off", "bf16"),
    tunable=Tunable(("off", "bf16"), "lossy", exact_value="off"),
)

# -- network serving tier knobs (heat_tpu/serve/net, ISSUE 12) ----------------

_register(
    "HEAT_TPU_SERVE_NET_PORT", "int", 0,
    "HTTP listen port of a serving replica (serve/net/transport.py). "
    "0 (the default) binds an ephemeral port — the replica prints the "
    "bound port in its ready line, which is how ReplicaPool wires the "
    "router without port collisions.",
)
_register(
    "HEAT_TPU_SERVE_NET_REPLICAS", "int", 2,
    "Default replica-process count of serve.net.ReplicaPool (each "
    "replica restores the endpoint checkpoint and warms from the shared "
    "JAX compilation cache / HEAT_TPU_TUNE_DB).",
)
_register(
    "HEAT_TPU_SERVE_NET_POLL_MS", "float", 25.0,
    "Router /stats poll interval in milliseconds: refreshes the "
    "least-loaded scores of healthy replicas and health-probes evicted "
    "ones for re-add (serve/net/router.py).",
    tunable=Tunable(("10", "25", "50", "100"), "neutral"),
)
_register(
    "HEAT_TPU_SERVE_NET_RETRIES", "int", 2,
    "Router sibling-retry cap: how many ADDITIONAL replicas a request "
    "that was shed (503) or met a connect-refused replica is offered "
    "before the client sees the failure. In-flight connection drops are "
    "never blindly retried (the request may have executed).",
)

# -- autoscaling / priority / hedging knobs (serve/net, ISSUE 20) -------------

_register(
    "HEAT_TPU_AUTOSCALE_MIN", "int", 1,
    "Lower replica bound of serve.net.AutoscaleController: scale-down "
    "decisions clamp here (the pool never drains below it), so a "
    "diurnal trough cannot leave the endpoint cold.",
)
_register(
    "HEAT_TPU_AUTOSCALE_MAX", "int", 4,
    "Upper replica bound of the autoscale controller: scale-up clamps "
    "here (capacity/cost ceiling). A clamped-at-max tick is counted "
    "(`clamped_max`) so saturation is visible in stats().",
)
_register(
    "HEAT_TPU_AUTOSCALE_TICK_S", "float", 1.0,
    "Control-loop period of AutoscaleController.start() in seconds. "
    "Ticks observe, then maybe act; all cooldowns/streaks below are "
    "expressed in ticks or seconds of this clock.",
)
_register(
    "HEAT_TPU_AUTOSCALE_UP_COOLDOWN_S", "float", 5.0,
    "Minimum seconds between successive scale-UPS: lets the previous "
    "replica finish warm-up and absorb load before the controller "
    "decides more capacity is still needed (anti-flap, up side).",
)
_register(
    "HEAT_TPU_AUTOSCALE_DOWN_COOLDOWN_S", "float", 30.0,
    "Minimum seconds after ANY scaling action before a scale-DOWN: "
    "asymmetric hysteresis (down much slower than up) so a load dip "
    "right after a spike does not bounce replicas.",
)
_register(
    "HEAT_TPU_AUTOSCALE_BACKLOG_HIGH", "float", 4.0,
    "Per-replica backlog (queued + in-flight per live replica) above "
    "which a tick counts toward the sustained-pressure streak that "
    "triggers scale-up (see HEAT_TPU_AUTOSCALE_BACKLOG_TICKS). An "
    "`slo_burn` breach scales up immediately, bypassing the streak.",
)
_register(
    "HEAT_TPU_AUTOSCALE_BACKLOG_TICKS", "int", 2,
    "Consecutive over-backlog ticks required before a backlog-driven "
    "scale-up (debounce: one bursty tick is not a trend).",
)
_register(
    "HEAT_TPU_AUTOSCALE_IDLE_LOW", "float", 0.5,
    "Per-replica backlog below which a tick counts toward the "
    "drain-idle streak that triggers scale-down; any shed activity in "
    "the window resets the streak.",
)
_register(
    "HEAT_TPU_AUTOSCALE_IDLE_TICKS", "int", 5,
    "Consecutive idle ticks required before a scale-down (the "
    "drain-idle window; long relative to BACKLOG_TICKS — giving back "
    "capacity is cheap to delay, missing the SLO is not).",
)
_register(
    "HEAT_TPU_AUTOSCALE_SPAWN_RETRIES", "int", 2,
    "Extra spawn attempts ReplicaPool.spawn() makes after a replica "
    "dies during warmup (each failure is reaped — killed, logged, "
    "evented `spawn_fail`, never left a zombie target) with "
    "exponential backoff between attempts.",
)
_register(
    "HEAT_TPU_SERVE_PRIORITY_WEIGHTS", "str", "",
    "Priority-class weight table of the router's weighted-fair "
    "admission queue, e.g. 'latency=8,bulk=1'. Empty = every class "
    "weighs 1.0 (plain FIFO). Classes are attached per endpoint "
    "(Router.set_priority) or per request (submit(priority=...)); "
    "dispatch order follows smooth weighted round-robin over nonempty "
    "classes, and sheds take the newest job of the lowest-weight class "
    "first.",
)
_register(
    "HEAT_TPU_SERVE_PRIORITY_QUEUE_MAX", "int", 0,
    "Bound on the router's admission queue (0 = unbounded). When full, "
    "an arriving job sheds the newest queued job of the lowest-weight "
    "class strictly below its own weight — or is itself shed if no "
    "such victim exists — so a bulk tenant cannot starve a "
    "latency-sensitive one under overload.",
)
_register(
    "HEAT_TPU_HEDGE_ENABLE", "bool", False,
    "Hedged retries (router): after the hedge delay, duplicate a "
    "straggling in-flight request to an idle sibling replica, take the "
    "first answer, cancel the loser. Requires idempotent endpoints "
    "(both arms may execute). Off by default.",
)
_register(
    "HEAT_TPU_HEDGE_DELAY_MS", "float", 0.0,
    "Fixed hedge delay in milliseconds; 0 (default) derives the delay "
    "from the endpoint's observed p95 latency (no hedging until "
    "HEAT_TPU_HEDGE_MIN_SAMPLES completions exist).",
)
_register(
    "HEAT_TPU_HEDGE_MAX_FRACTION", "float", 0.05,
    "Hard cap on hedged requests as a fraction of all requests "
    "(budget earned by completions): hedging trims the tail, it must "
    "never become a load doubler during overload.",
)
_register(
    "HEAT_TPU_HEDGE_MIN_SAMPLES", "int", 32,
    "Completed-request count an endpoint needs before a p95-derived "
    "hedge delay is trusted (too few samples make p95 noise, and "
    "hedging on noise wastes the budget).",
)

# -- cluster observability knobs (ISSUE 17; docs/OBSERVABILITY.md) ------------

_register(
    "HEAT_TPU_TRACE_REQUESTS", "bool", True,
    "Record distributed request traces (serve/tracing.py): a trace id "
    "minted at ingress rides the wire `trace` field and every hop — "
    "router queue/post, replica queue/coalesce/pad/execute/reply — "
    "lands as a `trace_span` telemetry event, mergeable into ONE "
    "Perfetto timeline across processes. Off is a one-flag-check hot "
    "path; answers are bit-identical either way.",
)
_register(
    "HEAT_TPU_TRACE_SAMPLE", "float", 1.0,
    "Ingress trace-sampling rate in [0, 1]. The keep/drop decision is "
    "made ONCE where the id is minted (deterministic in the id, so "
    "every process agrees) and propagated — downstream hops never "
    "re-sample.",
)
_register(
    "HEAT_TPU_SLO_WINDOW_S", "float", 60.0,
    "Rolling window in seconds over which Router.cluster_summary() "
    "computes SLO burn rates (windowed deltas of the cumulative "
    "per-replica scrapes; the first evaluation falls back to the "
    "lifetime window).",
)
_register(
    "HEAT_TPU_SLO_BURN_THRESHOLD", "float", 1.0,
    "Burn-rate level above which Router.check_slos() emits a "
    "`slo_burn` event. 1.0 = consuming error budget exactly at the "
    "rate that exhausts it over the objective period.",
)

# -- autotuner knobs (heat_tpu/autotune, ISSUE 11) ----------------------------

_register(
    "HEAT_TPU_AUTOTUNE", "bool", False,
    "Arm the measured-feedback knob autotuner (heat_tpu/autotune, "
    "docs/AUTOTUNE.md): program-cache misses and Server construction "
    "consult the tuning DB (warm start) and `autotune.tune()` runs "
    "measured trials. Default-off is bit-for-bit the untuned dispatch "
    "path — one flag check, no DB reads.",
)
_register(
    "HEAT_TPU_TUNE_DB", "str", None,
    "Directory of the persistent tuning DB (atomic-swap JSON records "
    "keyed by program signature + mesh topology + backend). A second "
    "process pointed at a populated DB starts *tuned* with zero measured "
    "trials, the same way JAX's persistent compilation cache makes it "
    "start *compiled*.",
)
_register(
    "HEAT_TPU_AUTOTUNE_TRIALS", "int", 5,
    "Measured trials per surviving candidate config (median-of-k with "
    "MAD outlier rejection).",
)
_register(
    "HEAT_TPU_AUTOTUNE_BUDGET", "float", None,
    "Ambient max amax-normalized relative error the tuner may trade for "
    "speed when the caller states none. Unset = exact-only: lossy knob "
    "values are never searched.",
)

# -- bench harness knobs ------------------------------------------------------

_register(
    "HEAT_TPU_SWEEP_ATTN", "bool", False,
    "bench.py: sweep ring/ulysses attention variants in the headline run.",
    scope="bench",
)
_register(
    "HEAT_TPU_BENCH_BUDGET", "float", 1500.0,
    "bench.py: wall-clock budget in seconds; rows past the deadline are "
    "skipped and marked partial.",
    scope="bench",
)

# -- streaming knobs (ISSUE 16; docs/STREAMING.md) ----------------------------

_register(
    "HEAT_TPU_STREAM_CHUNK_ROWS", "int", 0,
    "streaming.ChunkStream: rows per out-of-core chunk. 0 = auto-size "
    "so the chunk's device bytes fit memory_guard.temp_budget() "
    "(a quarter of HEAT_TPU_HBM_BUDGET when armed).",
)

_register(
    "HEAT_TPU_STREAM_DRAIN_TIMEOUT", "float", 60.0,
    "streaming.rolling_update: seconds an old replica may take to drain "
    "its backlog before the roll fails loudly (the version-swap drain "
    "policy).",
)

# -- test-suite knobs ---------------------------------------------------------

_register(
    "HEAT_TPU_TEST_DEVICES", "int", 8,
    "tests/conftest.py: virtual CPU mesh size the suite runs on "
    "(deliberately not a power of two by default).",
    scope="tests",
)

# -- CI knobs (read by scripts/run_ci.sh, not by Python) ----------------------

for _name, _doc in (
    ("HEAT_TPU_CI_SIZES", "Space-separated virtual-device sweep list "
     "(default `1 2 3 5 8`)."),
    ("HEAT_TPU_CI_CHUNKS", "Run each size's suite in N fresh-process "
     "chunks of test files (bounds accumulated XLA state)."),
    ("HEAT_TPU_CI_ALLOW_MISSING_IO", "Skip the loud optional-I/O backend "
     "presence check."),
    ("HEAT_TPU_CI_SKIP_AUDIT", "Skip the HLO collective-audit step."),
    ("HEAT_TPU_CI_SKIP_WARMCACHE", "Skip the warm-compile-cache reuse "
     "check."),
    ("HEAT_TPU_CI_SKIP_FUSION", "Skip the fusion dispatch check."),
    ("HEAT_TPU_CI_SKIP_FUSION_REDUCE", "Skip the fusion-reduce dispatch "
     "check."),
    ("HEAT_TPU_CI_SKIP_PLANNER", "Skip the budget-constrained relayout "
     "planner step."),
    ("HEAT_TPU_CI_SKIP_COLLPREC", "Skip the quantized-collective wire "
     "audit step."),
    ("HEAT_TPU_CI_SKIP_CHAOS", "Skip the fault-injection chaos step."),
    ("HEAT_TPU_CI_SKIP_SERVING", "Skip the open-loop serving gate."),
    ("HEAT_TPU_CI_SKIP_SERVING_NET", "Skip the horizontally-scaled "
     "serving gate (ISSUE 12: 2-replica pool, router-vs-direct digest "
     "bit-identity, kill-one-replica recovery, zero steady-state "
     "compiles on the warm-started second replica)."),
    ("HEAT_TPU_CI_SKIP_HEATLINT", "Skip the heatlint static-analysis "
     "gate (ISSUE 10)."),
    ("HEAT_TPU_CI_SKIP_AUTOTUNE", "Skip the autotune gate (ISSUE 11: "
     "tuned-vs-default wall, budget/digest validation, second-process "
     "zero-trial warm start)."),
    ("HEAT_TPU_CI_SKIP_SPARSE", "Skip the sparse gate (ISSUE 13: spmv "
     "digest bit-identical to the dense reference mask-matmul, "
     "budget-bounded transpose, zero HLO-audit drift on the sparse "
     "collective sites)."),
    ("HEAT_TPU_CI_SKIP_STREAMING", "Skip the streaming gate (ISSUE 16: "
     "2-file HDF5 out-of-core stream under a pinned HEAT_TPU_HBM_BUDGET "
     "that forbids load-all, watermark strictly below the load-all "
     "bytes, digest parity vs the in-memory fit, and a 2-replica "
     "rolling update with zero steady-state compiles and zero failed "
     "requests)."),
    ("HEAT_TPU_CI_SKIP_HIERARCHY", "Skip the hierarchy gate (ISSUE 15: "
     "flat-vs-tiered digest bit-identity on the emulated 2x2 mesh, "
     "audited cross-node byte reduction >= the local shard factor, "
     "DASO tiered-send equivalence, ZeRO watermark check)."),
    ("HEAT_TPU_CI_SKIP_CLUSTER_OBS", "Skip the cluster-observability "
     "gate (ISSUE 17: 2-replica pool under loadgen — merged-trace hop "
     "completeness with a consistent trace id, /metrics merge equal to "
     "the loadgen totals, tracing-off digest bit-identity with zero "
     "tracing counters, and an induced-latency SLO burn emitting "
     "slo_burn events)."),
    ("HEAT_TPU_CI_SKIP_FSDP", "Skip the FSDP gate (ISSUE 18: sharded "
     "per-device param+state bytes strictly below replicated, train "
     "parity vs the replicated baseline, per-layer audited gather "
     "bytes equal to the cost model with zero drift, knob-off "
     "bit-identical dispatch, zero steady-state compiles)."),
    ("HEAT_TPU_CI_SKIP_PIPELINE", "Skip the pipeline gate (ISSUE 19: "
     "1f1b digest bit-identical to gpipe, measured bubble ticks equal "
     "to the analytic schedule table, audited inter-stage hop bytes "
     "equal to pipeline_hop_cost with zero drift, elastic kill/restore "
     "onto a different node-by-local factorization matching the "
     "uninterrupted trajectory, zero steady-state compiles)."),
    ("HEAT_TPU_CI_SKIP_AUTOSCALE", "Skip the autoscale gate (ISSUE 20: "
     "step-load scale-up then drain-down with zero failed requests, "
     "chaos SIGKILL under load replaced within bounded ticks with zero "
     "steady-state compiles on the respawned replica)."),
):
    _register(_name, "str", None, _doc, scope="ci")
del _name, _doc


# -- overlay ------------------------------------------------------------------
# Tuned knob values (heat_tpu/autotune, ISSUE 11) are installed HERE, in
# front of the environment, so every consumer of the registry — fusion,
# the relayout planner, collective precision, the serving ladder, and any
# future knob — sees tuned values through the reads it already performs.
# The overlay is the ONLY sanctioned way to override a knob in-process;
# it never writes os.environ (subprocesses inherit only what the caller
# exports deliberately).

_OVERRIDES: Dict[str, str] = {}
_OVERRIDE_LOCK = threading.RLock()


def overrides() -> Dict[str, str]:
    """Snapshot of the active overlay (knob name -> raw string)."""
    with _OVERRIDE_LOCK:
        return dict(_OVERRIDES)


def set_override(name: str, value: Optional[str]) -> None:
    """Install (or with ``None`` remove) one overlay entry. The name must
    be registered — the overlay cannot smuggle in undeclared knobs."""
    if name not in REGISTRY:
        raise KeyError(
            f"{name!r} is not a registered HEAT_TPU knob — declare it in "
            "heat_tpu/_knobs.py before overriding it"
        )
    with _OVERRIDE_LOCK:
        if value is None:
            _OVERRIDES.pop(name, None)
        else:
            _OVERRIDES[name] = str(value)


def clear_overrides(names_: Optional[Iterable[str]] = None) -> None:
    """Drop the whole overlay (default) or just ``names_``."""
    with _OVERRIDE_LOCK:
        if names_ is None:
            _OVERRIDES.clear()
        else:
            for n in names_:
                _OVERRIDES.pop(n, None)


@contextlib.contextmanager
def overlay(mapping: Dict[str, Optional[str]]):
    """Temporarily install ``mapping`` into the overlay (the autotuner's
    per-candidate scope), restoring the previous entries — including
    their absence — on exit."""
    with _OVERRIDE_LOCK:
        # validate every name BEFORE installing anything: a mid-loop
        # KeyError would otherwise leak the already-installed entries
        # permanently (the restore below never runs on an install error)
        unknown = [n for n in mapping if n not in REGISTRY]
        if unknown:
            raise KeyError(
                f"{unknown[0]!r} is not a registered HEAT_TPU knob — "
                "declare it in heat_tpu/_knobs.py before overriding it"
            )
        prev = {n: _OVERRIDES.get(n) for n in mapping}
        for n, v in mapping.items():
            set_override(n, v)
    try:
        yield
    finally:
        with _OVERRIDE_LOCK:
            for n, v in prev.items():
                if v is None:
                    _OVERRIDES.pop(n, None)
                else:
                    _OVERRIDES[n] = v


# -- reads --------------------------------------------------------------------


def names() -> frozenset:
    """Every registered knob name (the set HL005 validates against)."""
    return frozenset(REGISTRY)


def tunables() -> Dict[str, Knob]:
    """The knobs carrying autotuner search-space metadata."""
    return {n: k for n, k in REGISTRY.items() if k.tunable is not None}


def default_raw(name: str) -> str:
    """The raw string a knob effectively has RIGHT NOW without tuning:
    the overlay/environment value when set, else the declared default
    rendered in env convention. This is the autotuner's "default config"
    entry — the candidate the winner must beat or tie."""
    k = REGISTRY[name]
    v = raw(name)
    if v is not None and v.strip():
        return v.strip()
    if k.type == "bool":
        return "1" if k.default else "0"
    return "" if k.default is None else str(k.default)


def raw(name: str, default: Optional[str] = None) -> Optional[str]:
    """The raw string for a registered knob: the overlay entry when one
    is installed (tuned values, ISSUE 11), else the environment.

    This is the ONE sanctioned ``os.environ`` read for ``HEAT_TPU_*``
    variables (heatlint HL005). Unregistered names raise — a new knob
    must be declared above, with its type, default, and docstring, before
    any code can read it.
    """
    if name not in REGISTRY:
        raise KeyError(
            f"{name!r} is not a registered HEAT_TPU knob — declare it in "
            "heat_tpu/_knobs.py (type, default, docstring; re-exported via "
            "heat_tpu.core.knobs) before reading it"
        )
    if _OVERRIDES:
        with _OVERRIDE_LOCK:
            v = _OVERRIDES.get(name)
        if v is not None:
            return v
    return os.environ.get(name, default)


def get(name: str):
    """Typed live read of a registered knob: parse the raw string by the
    knob's declared type, falling back to the declared default when unset
    or malformed. Bool parsing follows the shared conventions: default-on
    knobs stay on unless the value is in :data:`FALSY`; default-off knobs
    need an explicit :data:`TRUTHY`. Consults the overlay first, like
    :func:`raw`."""
    k = REGISTRY[name]
    s = (raw(name) or "").strip()
    if not s:
        return k.default
    if k.type == "bool":
        low = s.lower()
        return (low not in FALSY) if k.default else (low in TRUTHY)
    if k.type == "int":
        try:
            return int(s)
        except ValueError:
            return k.default
    if k.type == "float":
        try:
            return float(s)
        except ValueError:
            return k.default
    if k.type == "enum":
        low = s.lower()
        return low if low in k.choices else k.default
    return s  # str / bytes / spec: owning module parses further


# -- documentation ------------------------------------------------------------

_SCOPE_TITLES = (
    ("runtime", "Runtime knobs"),
    ("bench", "Benchmark-harness knobs"),
    ("tests", "Test-suite knobs"),
    ("ci", "CI sweep knobs (`scripts/run_ci.sh`)"),
)


def _default_str(k: Knob) -> str:
    if k.default is None:
        return "*(unset)*"
    if k.type == "bool":
        return "on" if k.default else "off"
    return f"`{k.default}`"


def _tunable_str(k: Knob) -> str:
    t = k.tunable
    if t is None:
        return "—"
    vals = ", ".join(t.values)
    if t.kind == "lossy":
        return f"lossy (exact: `{t.exact_value}`): `{vals}`"
    return f"{t.kind}: `{vals}`"


def markdown_table() -> str:
    """The knob catalog as markdown, grouped by scope — the generated
    section of docs/API.md (``tests/test_heatlint.py`` pins the committed
    doc to this output; regenerate with
    ``python -m heat_tpu.analysis --knob-table``). The *Tunable* column
    is the autotuner's declared search space (docs/AUTOTUNE.md)."""
    out = []
    for scope, title in _SCOPE_TITLES:
        knobs = [k for k in REGISTRY.values() if k.scope == scope]
        if not knobs:
            continue
        out.append(f"### {title}\n")
        out.append("| Knob | Type | Default | Tunable | Description |")
        out.append("|---|---|---|---|---|")
        for k in sorted(knobs, key=lambda k: k.name):
            typ = k.type
            if k.choices:
                typ = " \\| ".join(k.choices)
            doc = " ".join(k.doc.split())
            out.append(
                f"| `{k.name}` | {typ} | {_default_str(k)} | "
                f"{_tunable_str(k)} | {doc} |"
            )
        out.append("")
    return "\n".join(out).rstrip() + "\n"
