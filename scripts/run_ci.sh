#!/usr/bin/env bash
# CI sweep (reference: Jenkinsfile:19-27 runs the whole suite under
# `mpirun -n {1..8}` with coverage, then merges the per-size coverage files
# and archives junit XML, Jenkinsfile:33-44). The TPU-native analog re-runs
# the suite over virtual CPU meshes of several sizes — divisible and ragged
# — so every sharding path is exercised at every world size.
#
# Usage:
#   scripts/run_ci.sh                 # plain sweep (1 2 3 5 8)
#   CI_REPORT_DIR=out scripts/run_ci.sh
#       # + junit XML per device count (out/junit_<n>.xml) and, when the
#       # `coverage` module is available, per-size coverage data merged
#       # into one report (out/coverage.txt) — the Jenkinsfile analog
#   HEAT_TPU_CI_SIZES="2 8" scripts/run_ci.sh   # custom size list
#   HEAT_TPU_CI_CHUNKS=4 scripts/run_ci.sh
#       # run each size's suite in N fresh-process chunks of test files —
#       # bounds accumulated XLA state (a 3-device full pass aborts flakily
#       # inside XLA after ~300 tests in one process on this host)
set -euo pipefail
cd "$(dirname "$0")/.."

# optional-I/O gate check (VERDICT r4 weak 7): the HDF5/NetCDF suites skip
# silently when their backends are missing — in CI that silence is a lie,
# so fail loudly up front instead. HEAT_TPU_CI_ALLOW_MISSING_IO=1 opts out
# for deliberately minimal environments.
if [ -z "${HEAT_TPU_CI_ALLOW_MISSING_IO:-}" ]; then
    JAX_PLATFORMS=cpu python - <<'EOF'
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")
import jax; jax.config.update("jax_platforms", "cpu")
import heat_tpu as ht
missing = [name for name, ok in (
    ("hdf5 (h5py)", ht.supports_hdf5()),
    ("netcdf (netCDF4 or scipy)", ht.supports_netcdf()),
) if not ok]
if missing:
    raise SystemExit(
        "CI env is missing optional I/O backends: " + ", ".join(missing)
        + " - their test suites would silently skip. Install the backend "
        "or set HEAT_TPU_CI_ALLOW_MISSING_IO=1."
    )
print("I/O backends present: hdf5 + netcdf")
EOF
fi

SIZES=${HEAT_TPU_CI_SIZES:-"1 2 3 5 8"}
REPORT=${CI_REPORT_DIR:-}

# heatlint gate (ISSUE 10): the static analyzer enforces the dispatch /
# collective / precision / knob invariants (docs/STATIC_ANALYSIS.md) over
# the package, benchmarks, examples, driver, and scripts. It runs FIRST —
# an invariant regression fails in seconds, before any suite compiles.
# Passes on the committed baseline (.heatlint-baseline.json) and inline
# suppressions; fails on any NEW finding. HEAT_TPU_CI_SKIP_HEATLINT=1
# opts out.
HEATLINT_FAILED=""
if [ -z "${HEAT_TPU_CI_SKIP_HEATLINT:-}" ]; then
    echo "=== heatlint static-analysis gate ==="
    heatlint_out=$(mktemp)
    if JAX_PLATFORMS=cpu python -m heat_tpu.analysis \
            heat_tpu benchmarks examples bench.py scripts \
            | tee "$heatlint_out"; then
        echo "=== heatlint gate ok ==="
    else
        echo "=== heatlint gate FAILED — new invariant violations above ==="
        HEATLINT_FAILED=" heatlint"
    fi
    if [ -n "$REPORT" ]; then
        mkdir -p "$REPORT"
        cp "$heatlint_out" "${REPORT}/heatlint.log" || true
    fi
    rm -f "$heatlint_out"
fi

# Persistent XLA compile cache shared across the whole sweep (ISSUE 3): the
# suite is compile-bound, and retried chunks / repeated sizes / the per-
# module jax.clear_caches() in conftest all recompile programs a previous
# process already built. tests/conftest.py and the benchmark harness turn
# JAX's on-disk cache on under the one placement rule
# (program_cache.enable_persistent_cache): $JAX_COMPILATION_CACHE_DIR where
# set, else <checkout>/.jax_cache. To measure true cold-compile time, point
# JAX_COMPILATION_CACHE_DIR at an empty directory.
COMPILE_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$(pwd)/.jax_cache}"
echo "=== persistent compile cache: ${COMPILE_CACHE_DIR} ==="

have_coverage=0
if [ -n "$REPORT" ]; then
    mkdir -p "$REPORT"
    # drop artifacts of previous (possibly aborted or differently-sized)
    # runs so the merge below only sees this sweep's data
    rm -f "$REPORT"/.coverage* "$REPORT"/junit_*.xml "$REPORT"/coverage.txt \
        "$REPORT"/resilience_report.log
    if python -c "import coverage" 2>/dev/null; then
        have_coverage=1
    fi
fi

CHUNKS=${HEAT_TPU_CI_CHUNKS:-1}
FAILED_SIZES=""
RETRIED_ABORTS=""

# Unified resilience report (ISSUE 5): every fault-tolerance event of the
# sweep — retried SIGABRT chunks, chaos-step verdicts — lands here in one
# `<utc-ts> kind=<what> key=value...` line format, archived to
# ${REPORT}/resilience_report.log when a report dir is set.
log_resilience() {
    local line="$(date -u +%FT%TZ) $*"
    echo "$line"
    if [ -n "$REPORT" ]; then
        echo "$line" >> "${REPORT}/resilience_report.log"
    fi
}

# entries in the persistent compile cache (each "-cache" file is one XLA
# executable some process had to backend-compile)
cc_count() {
    if [ -d "${COMPILE_CACHE_DIR}" ]; then
        ls "${COMPILE_CACHE_DIR}" 2>/dev/null | grep -c -- '-cache$' || true
    else
        echo 0
    fi
}

for n in $SIZES; do
    echo "=== suite @ ${n} virtual devices (${CHUNKS} chunk(s)) ==="
    cc_before=$(cc_count)
    rc=0
    ran_chunks=0
    for ((k = 0; k < CHUNKS; k++)); do
        # round-robin test files into chunks; each chunk is a fresh process
        mapfile -t files < <(ls tests/test_*.py | awk -v k=$k -v c=$CHUNKS 'NR % c == k')
        [ ${#files[@]} -eq 0 ] && continue
        args=(-q -p no:cacheprovider)
        if [ -n "$REPORT" ]; then
            if [ "$CHUNKS" = 1 ]; then
                args+=("--junitxml=${REPORT}/junit_${n}.xml")
            else
                args+=("--junitxml=${REPORT}/junit_${n}_${k}.xml")
            fi
        fi
        # rc 134 = SIGABRT: the XLA CPU client nondeterministically
        # corrupts the glibc heap on this host ("corrupted size vs.
        # prev_size", seen ONLY on odd virtual-mesh sizes; the abort
        # detonates at an arbitrary LATER allocation, so it is not a
        # test failure). A fresh process gets a fresh heap layout —
        # retry an aborted chunk once, but ONLY in the known flake
        # configuration (odd size): an abort at an even size is a new
        # native crash and must fail loudly, not be masked. Every retry
        # is recorded (stdout + ${REPORT}/resilience_report.log) so a
        # rising abort rate stays visible in the archived artifacts.
        for attempt in 1 2; do
            crc=0
            if [ "$have_coverage" = 1 ]; then
                HEAT_TPU_TEST_DEVICES=$n COVERAGE_FILE="${REPORT}/.coverage.${n}.${k}" \
                    python -m coverage run --source=heat_tpu -m pytest "${files[@]}" "${args[@]}" || crc=$?
            else
                HEAT_TPU_TEST_DEVICES=$n python -m pytest "${files[@]}" "${args[@]}" || crc=$?
            fi
            [ "$crc" != 134 ] && break
            if [ $((n % 2)) -eq 0 ]; then
                echo "=== chunk ${k} aborted (SIGABRT) at EVEN size ${n} — outside the known flake scope, NOT retrying ==="
                break
            fi
            [ "$attempt" = 2 ] && break
            RETRIED_ABORTS="$RETRIED_ABORTS size=${n}/chunk=${k}"
            log_resilience "kind=sigabrt-retry size=${n} chunk=${k} attempt=${attempt} rc=134 note=known-xla-cpu-heap-flake"
            echo "=== chunk ${k} aborted (SIGABRT, known XLA CPU heap flake at odd size ${n}) — retrying once ==="
        done
        # pytest rc 5 = no tests collected in this chunk — not a failure
        # on its own, but at least one chunk must actually run tests
        if [ "$crc" = 0 ]; then
            ran_chunks=$((ran_chunks + 1))
        elif [ "$crc" != 5 ]; then
            rc=$crc
        fi
    done
    if [ "$ran_chunks" = 0 ] && [ "$rc" = 0 ]; then
        echo "=== suite @ ${n} devices ran NO tests — failing the size ==="
        rc=2
    fi
    cc_after=$(cc_count)
    echo "=== compile-count @ ${n} devices: $((cc_after - cc_before)) new XLA executables (cache total ${cc_after}) ==="
    if [ "$rc" != 0 ]; then
        echo "=== suite @ ${n} devices FAILED (rc=$rc) — continuing sweep ==="
        FAILED_SIZES="$FAILED_SIZES $n"
    fi
done

# HLO collective audit: run the resplit redistribution microbenchmark with
# the predicted-vs-emitted auditor on and fail on any drift above tolerance
# (telemetry/hlo.py; HEAT_TPU_HLO_TOLERANCE overrides the default 10%).
# This is the schedule-level regression oracle: a jax/XLA upgrade that
# changes the emitted collectives breaks HERE, not in a wall-clock graph.
# HEAT_TPU_CI_SKIP_AUDIT=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_AUDIT:-}" ]; then
    echo "=== hlo collective audit (resplit microbenchmark, 4-device mesh) ==="
    audit_out=$(mktemp)
    audit_rc=0
    if HEAT_TPU_TELEMETRY=1 python benchmarks/resplit/heat_tpu.py \
        --n 4096 --features 64 --trials 1 --mesh 4 --audit > "$audit_out"; then
        python - "$audit_out" <<'EOF' || audit_rc=$?
import json, sys

summary = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if "telemetry" in obj:
        summary = obj
if summary is None:
    raise SystemExit("audit: no summary line with a telemetry block")
hlo = summary["telemetry"].get("hlo_collectives")
if not hlo or not hlo.get("audits"):
    raise SystemExit(f"audit: auditor recorded no audits: {hlo}")
if hlo.get("drift", 0) > 0:
    raise SystemExit(
        "audit: predicted-vs-emitted drift detected:\n"
        + json.dumps(hlo, indent=2)
    )
print(f"audit ok: {hlo['audits']} audits, 0 drift")
EOF
    else
        audit_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$audit_out" "${REPORT}/audit_resplit.jsonl" || true
    fi
    rm -f "$audit_out"
    if [ "$audit_rc" != 0 ]; then
        echo "=== hlo collective audit FAILED (rc=$audit_rc) ==="
        FAILED_SIZES="$FAILED_SIZES audit"
    fi
fi

# Warm-cache regression check (ISSUE 3): run the resplit microbenchmark
# twice with a FRESH persistent compile cache — the second process must
# report lower compile_seconds than the first (it deserializes executables
# the first one built instead of re-running XLA). This pins the cross-
# process compile-skip behavior the sweep above relies on.
# HEAT_TPU_CI_SKIP_WARMCACHE=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_WARMCACHE:-}" ]; then
    echo "=== persistent compile cache warm/reuse check (resplit microbenchmark x2) ==="
    warm_dir=$(mktemp -d -t heat_tpu_warm.XXXXXX)
    warm_rc=0
    cold_out=$(mktemp); warm_out=$(mktemp)
    if JAX_COMPILATION_CACHE_DIR="$warm_dir" python benchmarks/resplit/heat_tpu.py \
            --n 2048 --features 32 --trials 1 --mesh 4 > "$cold_out" \
       && JAX_COMPILATION_CACHE_DIR="$warm_dir" python benchmarks/resplit/heat_tpu.py \
            --n 2048 --features 32 --trials 1 --mesh 4 > "$warm_out"; then
        python - "$cold_out" "$warm_out" <<'EOF' || warm_rc=$?
import json, sys

def compile_seconds(path):
    for line in open(path):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "compile_seconds" in obj:
            return obj["compile_seconds"]
    raise SystemExit(f"warm-cache: no compile_seconds line in {path}")

cold, warm = compile_seconds(sys.argv[1]), compile_seconds(sys.argv[2])
print(f"warm-cache: cold compile_seconds={cold} warm compile_seconds={warm}")
if not warm < cold:
    raise SystemExit(
        f"warm-cache: second process did not get cheaper compiles "
        f"(cold={cold}, warm={warm}) — persistent compile cache broken?"
    )
print("warm-cache ok")
EOF
    else
        warm_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$cold_out" "${REPORT}/warmcache_cold.jsonl" || true
        cp "$warm_out" "${REPORT}/warmcache_warm.jsonl" || true
    fi
    rm -f "$cold_out" "$warm_out"
    rm -rf "$warm_dir"
    if [ "$warm_rc" != 0 ]; then
        echo "=== warm-cache check FAILED (rc=$warm_rc) ==="
        FAILED_SIZES="$FAILED_SIZES warmcache"
    fi
fi

# Fusion dispatch check (ISSUE 4): run the elementwise-chain microbenchmark
# (normalize→scale→clip, 7 ops) in both dispatch modes and assert the fused
# chain compiled FEWER XLA programs than eager while matching or beating its
# wall clock — the defer-and-fuse engine's regression oracle
# (core/fusion.py). HEAT_TPU_CI_SKIP_FUSION=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_FUSION:-}" ]; then
    echo "=== fusion dispatch check (elementwise microbenchmark, 4-device mesh) ==="
    fusion_out=$(mktemp)
    fusion_rc=0
    # an empty compile cache: the program-count comparison must see real
    # backend compiles, not deserializations from the sweep's cache
    fusion_cc=$(mktemp -d -t heat_tpu_cold.XXXXXX)
    if JAX_COMPILATION_CACHE_DIR="$fusion_cc" python benchmarks/elementwise/heat_tpu.py \
            --n 100000 --features 64 --trials 2 --mesh 4 > "$fusion_out"; then
        python - "$fusion_out" <<'EOF' || fusion_rc=$?
import json, sys

cmp = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if "elementwise_compare" in obj:
        cmp = obj["elementwise_compare"]
if cmp is None:
    raise SystemExit("fusion: no elementwise_compare summary line")
eager, fused = cmp["eager"], cmp["fused"]
print(
    f"fusion: eager programs={eager['programs_compiled']} "
    f"best={eager['best_seconds']}s | fused programs={fused['programs_compiled']} "
    f"best={fused['best_seconds']}s | chain flushed as "
    f"{cmp['fused_programs']} cached program(s)"
)
if not fused["programs_compiled"] < eager["programs_compiled"]:
    raise SystemExit(
        f"fusion: fused chain did not compile fewer programs than eager "
        f"(fused={fused['programs_compiled']}, eager={eager['programs_compiled']})"
    )
if cmp["fused_programs"] != 1:
    raise SystemExit(
        f"fusion: the 7-op chain should flush as exactly ONE registry "
        f"program, got {cmp['fused_programs']}"
    )
if fused["deferred_ops"] == 0:
    raise SystemExit("fusion: no ops deferred — engine disabled?")
print("fusion ok")
EOF
    else
        fusion_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$fusion_out" "${REPORT}/fusion_elementwise.jsonl" || true
    fi
    rm -f "$fusion_out"; rm -rf "$fusion_cc"
    if [ "$fusion_rc" != 0 ]; then
        echo "=== fusion dispatch check FAILED (rc=$fusion_rc) ==="
        FAILED_SIZES="$FAILED_SIZES fusion"
    fi
    # Bit-for-bit parity spot check: the fusion test module's numeric
    # oracles re-run with fusion forced OFF (the sweep above already ran
    # them with the default ON), pinning HEAT_TPU_FUSION=0 == eager.
    echo "=== fusion-off parity spot check (tests/test_fusion.py eager mode) ==="
    if ! HEAT_TPU_FUSION=0 python -m pytest tests/test_fusion.py \
            -q -p no:cacheprovider -k "NumpyParity or FusionOff"; then
        echo "=== fusion-off parity check FAILED ==="
        FAILED_SIZES="$FAILED_SIZES fusion-off"
    fi
fi

# Fusion 2.0 step (ISSUE 7): run the reduction microbenchmark (normalize→
# scale→sum + mean/var moment chains) in eager / flush-at-reduction /
# fully-fused modes and assert (a) the fused moment chain dispatches FEWER
# programs than eager and the map+reduce chain compiles as exactly ONE
# program, (b) the DP-forward dense (matmul+bias+relu) is ONE program,
# (c) the fused chain+sum digests bit-identical to the knob-off baseline,
# and (d) HEAT_TPU_FUSION_REDUCE=0 really disarms absorption (zero
# reductions_absorbed, no fusion_reduce registry entries).
# HEAT_TPU_CI_SKIP_FUSION_REDUCE=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_FUSION_REDUCE:-}" ]; then
    echo "=== fusion-reduce dispatch check (reduction microbenchmark, 4-device mesh) ==="
    fr_out=$(mktemp)
    fr_rc=0
    # an empty compile cache: the program-count comparison must see real
    # backend compiles, not deserializations from the sweep's cache
    fr_cc=$(mktemp -d -t heat_tpu_cold.XXXXXX)
    if JAX_COMPILATION_CACHE_DIR="$fr_cc" python benchmarks/reduction/heat_tpu.py \
            --n 100000 --features 64 --trials 2 --mesh 4 > "$fr_out"; then
        python - "$fr_out" <<'EOF' || fr_rc=$?
import json, sys

cmp = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if "reduction_compare" in obj:
        cmp = obj["reduction_compare"]
if cmp is None:
    raise SystemExit("fusion-reduce: no reduction_compare summary line")
eager, flush, fused = cmp["eager"], cmp["flush"], cmp["fused"]
cp = cmp["chain_programs"]
print(
    f"fusion-reduce: chain programs eager={cp['eager']} flush={cp['flush']} "
    f"fused={cp['fused']} | moment programs eager={eager['programs_compiled']} "
    f"fused={fused['programs_compiled']} | dense={cmp['dense_programs']} "
    f"| absorbed={fused['reductions_absorbed']}"
)
if cp["fused"] != 1:
    raise SystemExit(
        f"fusion-reduce: the map+reduce chain should compile as exactly ONE "
        f"program, got {cp['fused']}"
    )
if cp["eager"] < 3 * cp["fused"]:
    raise SystemExit(
        f"fusion-reduce: fused chain must compile >=3x fewer programs than "
        f"eager (eager={cp['eager']}, fused={cp['fused']})"
    )
if not fused["programs_compiled"] < eager["programs_compiled"]:
    raise SystemExit(
        f"fusion-reduce: fused moment chain did not dispatch fewer programs "
        f"than eager (fused={fused['programs_compiled']}, "
        f"eager={eager['programs_compiled']})"
    )
if cmp["dense_programs"] != 1:
    raise SystemExit(
        f"fusion-reduce: matmul+bias+relu (dense) should be ONE cached "
        f"program, got {cmp['dense_programs']}"
    )
if not cmp["digest_chain_match"]:
    raise SystemExit(
        "fusion-reduce: fused chain+sum digest differs from the knob-off "
        "flush-then-reduce baseline (bit-identity pin)"
    )
if not cmp["moments_allclose"]:
    raise SystemExit(
        "fusion-reduce: fused moment chain drifted beyond tolerance vs the "
        "knob-off baseline"
    )
if fused["reductions_absorbed"] == 0:
    raise SystemExit("fusion-reduce: nothing absorbed — engine disabled?")
if flush["reductions_absorbed"] != 0 or "fusion_reduce" in flush["site_misses"]:
    raise SystemExit(
        "fusion-reduce: HEAT_TPU_FUSION_REDUCE=0 did not disarm absorption"
    )
print("fusion-reduce ok")
EOF
    else
        fr_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$fr_out" "${REPORT}/fusion_reduction.jsonl" || true
    fi
    rm -f "$fr_out"; rm -rf "$fr_cc"
    if [ "$fr_rc" != 0 ]; then
        echo "=== fusion-reduce dispatch check FAILED (rc=$fr_rc) ==="
        FAILED_SIZES="$FAILED_SIZES fusion-reduce"
    fi
    # Knob-off parity spot check: the fusion-reduce numeric oracles re-run
    # with absorption forced OFF, pinning HEAT_TPU_FUSION_REDUCE=0 ==
    # flush-at-reduction dispatch.
    echo "=== fusion-reduce knob-off parity spot check (tests/test_fusion_reduce.py) ==="
    if ! HEAT_TPU_FUSION_REDUCE=0 python -m pytest tests/test_fusion_reduce.py \
            -q -p no:cacheprovider \
            -k "NumpyParity or (NanVariants and not nan_chain_absorbs)"; then
        echo "=== fusion-reduce knob-off parity check FAILED ==="
        FAILED_SIZES="$FAILED_SIZES fusion-reduce-off"
    fi
fi

# Planner step (ISSUE 6): the resplit whose monolithic program exceeds a
# tight HEAT_TPU_HBM_BUDGET must succeed through the planner's chunked
# program chain with (a) every stage's memory_analysis() temp bytes within
# the budget and (b) a result sha256 BIT-IDENTICAL to the unconstrained
# monolithic run. The budget is computed IN-PROCESS (live bytes + half the
# monolithic program's measured temp+output need) because the flip point
# depends on live bytes at decision time — a fixed env value would race
# allocator state. HEAT_TPU_CI_SKIP_PLANNER=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_PLANNER:-}" ]; then
    echo "=== planner step: budget-constrained resplit via chunked plan (4-device mesh) ==="
    planner_rc=0
    planner_out=$(mktemp)
    XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
        HEAT_TPU_TELEMETRY=1 python - <<'EOF' > "$planner_out" 2>&1 || planner_rc=$?
import hashlib
import json
import os

import numpy as np

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import relayout_planner as rp
from heat_tpu.resilience import memory_guard

comm = ht.get_comm()
assert comm.size == 4, f"expected a 4-device mesh, got {comm.size}"
n, m = 4096, 256
xn = np.arange(n * m, dtype=np.float32).reshape(n, m)
x = ht.array(xn, split=0)

# unconstrained run: auto with no budget stays monolithic
ref = x.resplit(1)
sha_ref = hashlib.sha256(
    np.ascontiguousarray(ref.numpy()).tobytes()
).hexdigest()
del ref

# measure the program FIRST, then gc, then read live — the ordering
# maybe_plan itself uses, so the flip arithmetic is deterministic
need = memory_guard.program_bytes(x._relayout_executable(1), (x.larray,))
assert need > 0, "memory_analysis unavailable — cannot gate the planner"
import gc

gc.collect()
live = memory_guard._live_total()
budget = live + need // 2  # the monolithic program can no longer fit
os.environ["HEAT_TPU_HBM_BUDGET"] = str(budget)

reg = telemetry.get_registry()
reg.clear()
y = x.resplit(1)
sha = hashlib.sha256(np.ascontiguousarray(y.numpy()).tobytes()).hexdigest()
events = [e for e in reg.events if e["kind"] == "relayout_plan"]
assert events, "budgeted resplit recorded no relayout_plan event"
ev = events[0]
assert ev["plan"] == "chunked", f"expected a chunked plan, got {ev}"

plan = rp.plan(
    (n, m), 4, 0, 1, comm, budget=budget, live=live, measured_need=need
)
mem = rp.plan_memory(plan, x.larray, comm)
assert 0 <= mem["peak_temp_bytes"] <= budget, (mem, budget)
assert mem["peak_temp_bytes"] < need, (mem, need)
assert sha == sha_ref, (
    f"chunked plan diverged from monolithic result ({sha} != {sha_ref})"
)
print(json.dumps({
    "planner": "ok", "budget": budget, "live": live,
    "monolithic_need": need, "chunks": ev["chunks"],
    "peak_stage_temp_bytes": mem["peak_temp_bytes"],
    "digest": sha[:12],
}))
EOF
    cat "$planner_out"
    if [ -n "$REPORT" ]; then
        cp "$planner_out" "${REPORT}/planner_gate.log" || true
    fi
    rm -f "$planner_out"
    if [ "$planner_rc" != 0 ]; then
        echo "=== planner step FAILED (rc=$planner_rc) ==="
        FAILED_SIZES="$FAILED_SIZES planner"
    fi
fi

# Collective-precision step (ISSUE 9): resplit + DP-step microbench under
# every HEAT_TPU_COLLECTIVE_PREC mode on the 4-device mesh. Gates:
#   (a) the HLO-audited emitted wire bytes of each compressed program
#       match the analytic compressed prediction (zero drift), and the
#       audited byte REDUCTION clears the acceptance floor — resplit
#       >=1.9x under bf16 and >=3.5x under int8/blockwise, DP gradient
#       all-reduce >=3.5x under int8/blockwise (the CPU backend
#       legalizes a bf16 all-reduce payload to f32, so bf16-DP only
#       gates "not worse"; the true 2x is the resplit's, whose bf16
#       payload travels as its u16 bit pattern);
#   (b) HEAT_TPU_COLLECTIVE_PREC=off (the default) stays BIT-identical
#       to the unknobbed baseline;
#   (c) each mode's executed error stays within the pinned bound.
# HEAT_TPU_CI_SKIP_COLLPREC=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_COLLPREC:-}" ]; then
    echo "=== collective-precision step: quantized wire audit (4-device mesh) ==="
    collprec_rc=0
    collprec_out=$(mktemp)
    XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
        python - <<'EOF' > "$collprec_out" 2>&1 || collprec_rc=$?
import json

import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from heat_tpu.telemetry import collectives, hlo

comm = ht.get_comm()
p = comm.size
assert p == 4, f"expected a 4-device mesh, got {p}"
MODES = ("off", "bf16", "int8", "blockwise")
rng = np.random.default_rng(0)
report = {"mesh": p}

# -- resplit microbench ------------------------------------------------------
shape = (4096, 256)
xn = rng.standard_normal(shape).astype(np.float32)
x = ht.array(xn, split=0)
baseline = x.resplit(1).numpy()
assert baseline.tobytes() == xn.tobytes(), "exact resplit corrupted data"
wires, errs = {}, {}
for m in MODES:
    fn = x._relayout_executable(1, precision=m)
    aud = hlo.audit_computation(fn, x.larray)
    phys = [comm.padded_size(shape[0]), comm.padded_size(shape[1])]
    pred = collectives.relayout_cost(phys, 4, 0, 1, p, precision=m)
    rep = hlo.compare(aud, pred)
    if not rep.ok:
        raise SystemExit(
            f"collective-prec: {m} resplit audit drifted: "
            f"{json.dumps(rep.summary())}"
        )
    wires[m] = aud.total_wire()
    out = np.asarray(fn(x.larray))
    errs[m] = float(np.abs(out - baseline).max() / np.abs(xn).max())
if baseline.tobytes() != np.asarray(
    x._relayout_executable(1, precision="off")(x.larray)
).tobytes():
    raise SystemExit("collective-prec: off mode is not bit-identical")
for m, floor in (("bf16", 1.9), ("int8", 3.5), ("blockwise", 3.5)):
    got = wires["off"] / wires[m]
    if got < floor:
        raise SystemExit(
            f"collective-prec: resplit {m} audited reduction {got:.2f}x "
            f"below the {floor}x floor ({wires})"
        )
bounds = {"off": 0.0, "bf16": 2.0 ** -7, "int8": 1.05 / 127,
          "blockwise": 1.05 / 127}
for m in MODES:
    if errs[m] > bounds[m]:
        raise SystemExit(
            f"collective-prec: resplit {m} error {errs[m]:.5f} over the "
            f"pinned bound {bounds[m]:.5f}"
        )
report["resplit"] = {"wire_bytes": wires, "max_rel_err": errs}

# -- DP-step microbench ------------------------------------------------------
D = 512
xb = rng.standard_normal((128, D)).astype(np.float32)
yb = rng.standard_normal((128, 1)).astype(np.float32)

def loss_fn(params, bx, by):
    return jnp.mean((bx @ params["w"] - by) ** 2)

dp_wires, dp_final = {}, {}
for m in MODES:
    dp = ht.nn.DataParallel(
        lambda pr, bx: bx @ pr["w"], optimizer=optax.sgd(0.05),
        blocking_parameter_updates=True,
    )
    params = {"w": jnp.zeros((D, 1))}
    opt_state = optax.sgd(0.05).init(params)
    step = dp.make_train_step(loss_fn, optax.sgd(0.05), precision=m)
    batch = dp.shard_batch(xb, yb)
    aud = hlo.audit_computation(step, params, opt_state, *batch)
    dp_wires[m] = aud.total_wire()
    if m in ("int8", "blockwise"):
        pred = collectives.allreduce_cost(D, 4, p, precision=m)
        loss_ar = collectives.allreduce_cost(1, 4, p)
        rep = hlo.compare(aud, collectives.CollectiveCost(
            pred.kind + "+all-reduce", pred.bytes + loss_ar.bytes
        ))
        if not rep.ok:
            raise SystemExit(
                f"collective-prec: {m} DP-step audit drifted: "
                f"{json.dumps(rep.summary())}"
            )
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, *batch)
    dp_final[m] = np.asarray(params["w"])
for m, floor in (("int8", 3.5), ("blockwise", 3.5)):
    got = dp_wires["off"] / dp_wires[m]
    if got < floor:
        raise SystemExit(
            f"collective-prec: DP {m} audited reduction {got:.2f}x below "
            f"the {floor}x floor ({dp_wires})"
        )
if dp_wires["bf16"] > dp_wires["off"]:
    raise SystemExit(
        f"collective-prec: bf16 DP wire not smaller than off ({dp_wires})"
    )
for m in ("bf16", "int8", "blockwise"):
    drift = float(np.abs(dp_final[m] - dp_final["off"]).max())
    if drift > 5e-2:
        raise SystemExit(
            f"collective-prec: {m} DP trajectory drifted {drift} from "
            "exact after 8 steps"
        )
report["dp_step"] = {"wire_bytes": dp_wires}
print(json.dumps({"collective_prec": "ok", **report}))
EOF
    cat "$collprec_out"
    if [ -n "$REPORT" ]; then
        cp "$collprec_out" "${REPORT}/collective_prec_gate.log" || true
    fi
    rm -f "$collprec_out"
    if [ "$collprec_rc" != 0 ]; then
        echo "=== collective-precision step FAILED (rc=$collprec_rc) ==="
        FAILED_SIZES="$FAILED_SIZES collective-prec"
    fi
fi

# Chaos step (ISSUE 5): run the resplit microbenchmark twice — fault-free,
# then under deterministic fault injection (one synthetic transient per
# matched site: the relayout dispatch and every collective wrapper) with
# retries armed. The guarded dispatch must absorb the faults: the run
# succeeds, its result digest is BIT-IDENTICAL to the fault-free run, the
# summary records resilience.retries >= 1, and the fault-free run carries
# no resilience counters at all (the zero-overhead-when-disarmed oracle).
# HEAT_TPU_CI_SKIP_CHAOS=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_CHAOS:-}" ]; then
    echo "=== chaos step: resplit microbenchmark under fault injection ==="
    chaos_rc=0
    clean_out=$(mktemp); chaos_out=$(mktemp)
    if env -u HEAT_TPU_FAULTS -u HEAT_TPU_RETRIES HEAT_TPU_TELEMETRY=1 \
            python benchmarks/resplit/heat_tpu.py \
            --n 2048 --features 32 --trials 1 --mesh 4 --digest > "$clean_out" \
       && HEAT_TPU_TELEMETRY=1 HEAT_TPU_RETRIES=3 HEAT_TPU_RETRY_BASE=0.01 \
            HEAT_TPU_FAULTS='relayout:kind=resource:calls=1;collective.*:kind=reset:calls=1' \
            python benchmarks/resplit/heat_tpu.py \
            --n 2048 --features 32 --trials 1 --mesh 4 --digest > "$chaos_out"; then
        python - "$clean_out" "$chaos_out" <<'EOF' || chaos_rc=$?
import json, sys

def parse(path):
    digest, summary = None, None
    for line in open(path):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "result_sha256" in obj:
            digest = obj["result_sha256"]
        if "telemetry" in obj:
            summary = obj
    return digest, summary

clean_digest, clean_summary = parse(sys.argv[1])
chaos_digest, chaos_summary = parse(sys.argv[2])
if not clean_digest or not chaos_digest:
    raise SystemExit("chaos: missing result_sha256 line (need --digest)")
if clean_summary is None or chaos_summary is None:
    raise SystemExit("chaos: missing telemetry summary line")
if chaos_digest != clean_digest:
    raise SystemExit(
        f"chaos: fault-injected run diverged from fault-free run "
        f"({chaos_digest} != {clean_digest}) — retries are not transparent"
    )
res = chaos_summary["telemetry"].get("resilience") or {}
if res.get("retries", 0) < 1:
    raise SystemExit(
        f"chaos: injected faults produced no recorded retries: {res}"
    )
if res.get("gave_up", 0):
    raise SystemExit(f"chaos: a guarded site gave up: {res}")
clean_res = clean_summary["telemetry"].get("resilience")
if clean_res:
    raise SystemExit(
        f"chaos: fault-free run carries resilience counters {clean_res} — "
        "the disarmed path is not zero-overhead"
    )
print(
    f"chaos ok: bit-identical digest {chaos_digest[:12]}…, "
    f"retries={res['retries']}, faults_injected={res.get('faults_injected')}, "
    "fault-free run clean"
)
EOF
    else
        chaos_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$clean_out" "${REPORT}/chaos_clean.jsonl" || true
        cp "$chaos_out" "${REPORT}/chaos_faulted.jsonl" || true
    fi
    rm -f "$clean_out" "$chaos_out"
    if [ "$chaos_rc" != 0 ]; then
        log_resilience "kind=chaos verdict=FAIL rc=${chaos_rc}"
        echo "=== chaos step FAILED (rc=$chaos_rc) ==="
        FAILED_SIZES="$FAILED_SIZES chaos"
    else
        log_resilience "kind=chaos verdict=ok sites='relayout collective.*' retries-armed=3"
    fi
fi

# Serving gate (ISSUE 8): a short open-loop Poisson run against a live
# heat_tpu.serve server, three phases —
#   clean:  ZERO program-registry misses and ZERO backend compiles after
#           warmup() (the zero-compile steady-state acceptance oracle),
#           no failures, p99 under a generous bound, post-load probe ok;
#   retry:  one injected transient per serve site with retries armed —
#           the guarded per-batch retry must absorb every fault
#           (retries>=1, no gave_up, zero failed requests) and the
#           response digest must be BIT-IDENTICAL to the clean run;
#   shed:   the same faults with retries DISARMED — the affected batches
#           shed cleanly (failed>=1, futures resolve with the error, no
#           hang) and the server recovers (post_ok). calls=6 lands the
#           injection past the 5 warmup executions of the --max-batch 16
#           ladder (buckets 1,2,4,8,16), i.e. on the first load batches.
# HEAT_TPU_CI_SKIP_SERVING=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_SERVING:-}" ]; then
    echo "=== serving gate: open-loop load vs live server (4-device mesh) ==="
    serve_rc=0
    serve_clean=$(mktemp); serve_retry=$(mktemp); serve_shed=$(mktemp)
    SERVE_ARGS="--n 2048 --features 32 --mesh 4 --requests 240 --rate 400 --max-batch 16 --digest"
    if env -u HEAT_TPU_FAULTS -u HEAT_TPU_RETRIES HEAT_TPU_TELEMETRY=1 \
            python benchmarks/serving/heat_tpu.py $SERVE_ARGS > "$serve_clean" \
       && HEAT_TPU_TELEMETRY=1 HEAT_TPU_RETRIES=3 HEAT_TPU_RETRY_BASE=0.01 \
            HEAT_TPU_FAULTS='serve.*:kind=reset:calls=6' \
            python benchmarks/serving/heat_tpu.py $SERVE_ARGS > "$serve_retry" \
       && env -u HEAT_TPU_RETRIES HEAT_TPU_TELEMETRY=1 \
            HEAT_TPU_FAULTS='serve.*:kind=resource:calls=6' \
            python benchmarks/serving/heat_tpu.py $SERVE_ARGS > "$serve_shed"; then
        python - "$serve_clean" "$serve_retry" "$serve_shed" <<'EOF' || serve_rc=$?
import json, sys

def parse(path):
    cmp_, summary = None, None
    for line in open(path):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "serving_compare" in obj:
            cmp_ = obj["serving_compare"]
        if obj.get("bench") == "serving":
            summary = obj
    if cmp_ is None or summary is None:
        raise SystemExit(f"serving: missing serving_compare/summary in {path}")
    return cmp_, summary

clean, clean_sum = parse(sys.argv[1])
retry, retry_sum = parse(sys.argv[2])
shed, _ = parse(sys.argv[3])

# clean phase: zero-compile steady state + SLO
if clean["misses_during_load"] != 0 or clean["backend_compiles_during_load"] != 0:
    raise SystemExit(
        f"serving: steady state recompiled after warmup "
        f"(misses={clean['misses_during_load']}, "
        f"backend_compiles={clean['backend_compiles_during_load']})"
    )
if clean["failed"] or not clean["post_ok"]:
    raise SystemExit(f"serving: clean run failed requests: {clean}")
p99 = clean["latency"].get("p99_s")
if p99 is None or p99 > 2.0:
    raise SystemExit(f"serving: clean p99 {p99}s exceeds the 2s CI bound")
res = clean_sum.get("telemetry", {}).get("resilience")
if res:
    raise SystemExit(f"serving: fault-free run carries resilience counters {res}")

# retry phase: per-batch retries absorb the faults, answers bit-identical
rres = retry_sum.get("telemetry", {}).get("resilience") or {}
if rres.get("retries", 0) < 1:
    raise SystemExit(f"serving: injected faults produced no retries: {rres}")
if rres.get("gave_up", 0) or retry["failed"]:
    raise SystemExit(f"serving: retry phase lost requests: {retry} {rres}")
if retry["digest"] != clean["digest"]:
    raise SystemExit(
        f"serving: fault-injected digest diverged from clean "
        f"({retry['digest']} != {clean['digest']}) — retries not transparent"
    )

# shed phase: retries disarmed -> affected batches shed, server recovers
if shed["failed"] < 1:
    raise SystemExit(f"serving: shed phase absorbed faults with no retries armed? {shed}")
if not shed["post_ok"]:
    raise SystemExit(f"serving: server did not recover after shedding: {shed}")
print(
    f"serving ok: 0 recompiles, p99={p99}s, qps={clean['achieved_qps']} "
    f"(offered {clean['offered_rate']}), retry digest bit-identical "
    f"(retries={rres.get('retries')}), shed-and-recover "
    f"(failed={shed['failed']}, post_ok)"
)
EOF
    else
        serve_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$serve_clean" "${REPORT}/serving_clean.jsonl" || true
        cp "$serve_retry" "${REPORT}/serving_retry.jsonl" || true
        cp "$serve_shed" "${REPORT}/serving_shed.jsonl" || true
    fi
    rm -f "$serve_clean" "$serve_retry" "$serve_shed"
    if [ "$serve_rc" != 0 ]; then
        log_resilience "kind=serving verdict=FAIL rc=${serve_rc}"
        echo "=== serving gate FAILED (rc=$serve_rc) ==="
        FAILED_SIZES="$FAILED_SIZES serving"
    else
        log_resilience "kind=serving verdict=ok phases='clean retry shed' sites='serve.*'"
    fi
fi

# Autotune gate (ISSUE 11): tune the resplit + reduction + serving
# microbench workloads on the 4-device mesh against a fresh tuning DB,
# then replay the SAME tunes from a second process. Gates:
#   tune phase:   every site's tuned wall <= the measured default wall
#                 (the default config is candidate 0 under the identical
#                 protocol); an exact/neutral pick is BIT-identical to
#                 the default result; a lossy pick measures within the
#                 stated error budget (the int8 single-hop bound the
#                 collective-precision step pins);
#   replay phase: a fresh process pointed at the same HEAT_TPU_TUNE_DB
#                 reaches every tuned config with ZERO measured trials
#                 (db-hit warm start) and its steady-state dispatch
#                 under the adopted config backend-compiles nothing.
# HEAT_TPU_CI_SKIP_AUTOTUNE=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_AUTOTUNE:-}" ]; then
    echo "=== autotune gate: measured-feedback tuning + second-process warm start (4-device mesh) ==="
    at_rc=0
    at_db=$(mktemp -d -t heat_tpu_tune.XXXXXX)
    at_script=$(mktemp)
    at_tune_out=$(mktemp); at_replay_out=$(mktemp)
    cat > "$at_script" <<'EOF'
import json
import os

import numpy as np

import heat_tpu as ht
from heat_tpu import _knobs as knobs
from heat_tpu import autotune as at
from heat_tpu import telemetry
from heat_tpu.autotune import cost, trials

PHASE = os.environ["HEAT_TPU_CI_AUTOTUNE_PHASE"]  # tune | replay
BUDGET = 1.05 / 127  # the int8 single-hop bound (collective-prec gate)
replay = PHASE == "replay"

comm = ht.get_comm()
assert comm.size == 4, f"expected a 4-device mesh, got {comm.size}"
reg = telemetry.get_registry()
rng = np.random.default_rng(0)
report = {"phase": PHASE, "sites": {}}


def check(res, exact_ref=None, lossy_knob=None):
    rec = res.record
    if replay:
        assert res.from_db and res.trials_run == 0, (
            f"{res.site}: second process ran trials "
            f"(from_db={res.from_db}, trials={res.trials_run})"
        )
    else:
        assert not res.from_db and res.trials_run > 0, res
        assert rec["tuned_wall"] <= rec["baseline_wall"], (
            f"{res.site}: tuned wall {rec['tuned_wall']} worse than the "
            f"measured default {rec['baseline_wall']}"
        )
    # validation contract: lossy picks carry a bounded measured error,
    # everything else is digest-validated (bit-identical to default)
    if rec["validation"] == "allclose":
        assert rec["max_rel_err"] <= rec["error_budget"], rec
    else:
        assert rec["max_rel_err"] == 0.0, rec
    if exact_ref is not None:
        out = np.asarray(exact_ref["run"]())  # under the ADOPTED config
        if lossy_knob and res.config.get(lossy_knob) not in (None, "off"):
            err = trials.max_rel_err(out, exact_ref["value"])
            assert err <= BUDGET, (
                f"{res.site}: adopted lossy config error {err} over "
                f"budget {BUDGET}"
            )
        else:
            assert out.tobytes() == exact_ref["value"].tobytes(), (
                f"{res.site}: exact pick not bit-identical to default"
            )
    report["sites"][res.site] = {
        "config": res.config, "trials": res.trials_run,
        "from_db": res.from_db,
        "baseline_wall": rec["baseline_wall"],
        "tuned_wall": rec["tuned_wall"],
        "validation": rec["validation"],
        "max_rel_err": rec["max_rel_err"],
    }


# -- resplit: exact + lossy lattice under the int8 budget --------------------
n, d = 2048, 64
x = ht.array(rng.standard_normal((n, d)).astype(np.float32), split=0)
exact_resplit = np.asarray(x.resplit(1).larray)  # untuned default result
res = at.tune(
    "resplit", lambda: x.resplit(1).larray,
    signature=("resplit", (n, d), 0, 1),
    search=["HEAT_TPU_RELAYOUT_PLAN", "HEAT_TPU_COLLECTIVE_PREC"],
    error_budget=BUDGET, trials_per_config=2, prune_to=6,
    cost_fn=cost.relayout_cost_fn(x.shape, 4, 0, 1, comm.size),
)
check(
    res,
    exact_ref={"run": lambda: x.resplit(1).larray, "value": exact_resplit},
    lossy_knob="HEAT_TPU_COLLECTIVE_PREC",
)

# -- reduction: exact-class fusion knobs, bit-identity required --------------
xr = ht.array(rng.standard_normal((4096, 64)).astype(np.float32), split=0)


def red_work():
    return ((xr - 0.5) * 2.0 + 1.0).sum(axis=0).larray


exact_red = np.asarray(red_work())
res = at.tune(
    "reduction", red_work,
    signature=("reduction", (4096, 64), 0),
    search=["HEAT_TPU_FUSION", "HEAT_TPU_FUSION_REDUCE"],
    trials_per_config=2,
)
check(res, exact_ref={"run": red_work, "value": exact_red})

# -- serving: neutral gather-window knob, digest-validated -------------------
w = rng.standard_normal((d, 8)).astype(np.float32)
b = rng.standard_normal(8).astype(np.float32)
endpoint = ht.serve.dense_forward(w, b, activation="relu")
payloads = [rng.standard_normal(d).astype(np.float32) for _ in range(24)]
servers = {}


def serve_work():
    key = knobs.raw("HEAT_TPU_SERVE_MAX_WAIT_MS")
    srv = servers.get(key)
    if srv is None:
        srv = ht.serve.Server(max_batch=8)
        srv.register("dense", endpoint)
        srv.warmup()
        servers[key] = srv
    futs = [srv.submit("dense", p) for p in payloads]
    return np.stack([f.result() for f in futs])


try:
    exact_serve = serve_work()
    res = at.tune(
        "serving", serve_work,
        signature=("serving", ("dense",), d, 8),
        search=["HEAT_TPU_SERVE_MAX_WAIT_MS"],
        trials_per_config=2,
    )
    check(res, exact_ref={"run": serve_work, "value": exact_serve})
finally:
    for srv in servers.values():
        srv.close()

if replay:
    # zero measured trials across ALL sites (counter oracle), and the
    # steady-state dispatch under the adopted configs compiles nothing
    assert reg.counters.get("autotune.trials", 0) == 0, dict(reg.counters)
    x.resplit(1).larray  # first dispatch under the adopted config
    with telemetry.CompileWatcher() as cw:
        x.resplit(1).larray
    assert cw.backend_compiles == 0, (
        f"steady-state dispatch compiled {cw.backend_compiles} programs"
    )
    report["steady_state_backend_compiles"] = cw.backend_compiles

print(json.dumps({"autotune_gate": "ok", **report}))
EOF
    at_env=(XLA_FLAGS="--xla_force_host_platform_device_count=4"
            JAX_PLATFORMS=cpu HEAT_TPU_TELEMETRY=1
            HEAT_TPU_AUTOTUNE=1 HEAT_TPU_TUNE_DB="$at_db"
            PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}")
    if env "${at_env[@]}" HEAT_TPU_CI_AUTOTUNE_PHASE=tune \
            python "$at_script" > "$at_tune_out" 2>&1 \
       && env "${at_env[@]}" HEAT_TPU_CI_AUTOTUNE_PHASE=replay \
            python "$at_script" > "$at_replay_out" 2>&1; then
        tail -1 "$at_tune_out"
        tail -1 "$at_replay_out"
        echo "autotune ok: tuned <= default on all sites, replay ran zero trials"
    else
        at_rc=$?
        cat "$at_tune_out" "$at_replay_out"
    fi
    if [ -n "$REPORT" ]; then
        cp "$at_tune_out" "${REPORT}/autotune_tune.jsonl" || true
        cp "$at_replay_out" "${REPORT}/autotune_replay.jsonl" || true
    fi
    rm -f "$at_script" "$at_tune_out" "$at_replay_out"
    rm -rf "$at_db"
    if [ "$at_rc" != 0 ]; then
        echo "=== autotune gate FAILED (rc=$at_rc) ==="
        FAILED_SIZES="$FAILED_SIZES autotune"
    fi
fi

# Serving-net gate (ISSUE 12): a 2-replica pool on the 4-dev CPU mesh
# behind the least-loaded router, one shared compile cache. Gates:
#   digest:   the same seeded request set through an in-process Server
#             and through the router over HTTP produces BIT-IDENTICAL
#             response digests (wire round-trip is bitwise; zero sheds
#             on both sides);
#   warm:     every replica reports steady_backend_compiles == 0 in
#             /stats — the CompileWatcher armed post-warmup saw nothing
#             (the warm-started second replica is the headline: it
#             reached steady state from the SHARED cache);
#   chaos:    SIGKILL one replica mid-load — only its in-flight
#             requests fail (bounded by the router worker count), and
#             the post-kill recovery probe (fresh replica spawned from
#             the checkpoint, joined via add_target) answers
#             bit-identically to the direct single-dispatch reference.
# HEAT_TPU_CI_SKIP_SERVING_NET=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_SERVING_NET:-}" ]; then
    echo "=== serving-net gate: 2-replica pool + router (4-device mesh) ==="
    snet_rc=0
    snet_out=$(mktemp)
    if HEAT_TPU_TELEMETRY=1 python benchmarks/serving/net.py \
            --n 256 --features 16 --mesh 4 --replica-mesh 4 \
            --replicas-list 2 --requests 80 --rate 120 \
            --digest-requests 40 --digest-rate 60 \
            --endpoints cdist,dense --chaos > "$snet_out"; then
        python - "$snet_out" <<'EOF' || snet_rc=$?
import json, sys

summary = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if obj.get("bench") == "serving_net":
        summary = obj
if summary is None:
    raise SystemExit("serving-net: no summary line")

dp = summary["digest_probe"] or {}
if not (dp.get("match") and dp.get("direct_clean") and dp.get("routed_clean")):
    raise SystemExit(f"serving-net: router-vs-direct digest diverged: {dp}")

if not summary["steady_backend_compiles_ok"]:
    raise SystemExit(
        "serving-net: a replica backend-compiled in steady state "
        "(warm start from the shared cache failed): "
        f"{summary['qps_by_replicas']}"
    )

chaos = summary["chaos"] or {}
if not chaos.get("post_ok"):
    raise SystemExit(
        f"serving-net: post-kill recovery probe not bit-identical: {chaos}"
    )
if not chaos.get("failed_within_inflight_bound"):
    raise SystemExit(
        f"serving-net: killing one replica lost more than its in-flight "
        f"requests (failed={chaos.get('failed')}, "
        f"bound={chaos.get('max_inflight_bound')})"
    )
if (chaos.get("completed") or 0) + (chaos.get("failed") or 0) + \
        (chaos.get("shed") or 0) != summary["requests"]:
    raise SystemExit(f"serving-net: chaos phase dropped requests: {chaos}")

print(
    f"serving-net ok: digest bit-identical router-vs-direct, "
    f"steady compiles 0 across replicas, chaos lost "
    f"{chaos.get('failed')} in-flight (bound "
    f"{chaos.get('max_inflight_bound')}), replacement joined in "
    f"{chaos.get('replacement_join_seconds')}s, post_ok"
)
EOF
    else
        snet_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$snet_out" "${REPORT}/serving_net.jsonl" || true
    fi
    rm -f "$snet_out"
    if [ "$snet_rc" != 0 ]; then
        echo "=== serving-net gate FAILED (rc=$snet_rc) ==="
        FAILED_SIZES="$FAILED_SIZES serving-net"
    fi
fi

# Sparse gate (ISSUE 13, heat_tpu/sparse): the density-sweep
# microbenchmark on the 4-device mesh must show
#   (a) the row-split spmv digest BIT-identical to the dense reference
#       mask-matmul evaluated in the same per-row element order, at
#       every density (0.1%/1%/10%),
#   (b) the budget-bounded transpose (stage-decomposed slab exchange)
#       bit-identical to the monolithic exchange,
#   (c) zero HLO-audit drift on every audited sparse collective site
#       (--audit arms the auditor over the whole run), and
#   (d) the Spectral eNeighbour end-to-end row agreeing with the dense
#       pipeline's labels exactly.
# HEAT_TPU_CI_SKIP_SPARSE=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_SPARSE:-}" ]; then
    echo "=== sparse gate: density sweep + transpose + spectral (4-device mesh) ==="
    sp_rc=0
    sp_out=$(mktemp)
    if HEAT_TPU_TELEMETRY=1 python benchmarks/sparse/heat_tpu.py \
            --n 512 --features 8 --trials 2 --mesh 4 --audit \
            --spectral-n 128 > "$sp_out"; then
        python - "$sp_out" <<'EOF' || sp_rc=$?
import json, sys

summary = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if "sparse_compare" in obj:
        summary = obj["sparse_compare"]
if summary is None:
    raise SystemExit("sparse: no sparse_compare summary line")

bad = [r["density"] for r in summary["densities"] if not r["digest_match"]]
if bad:
    raise SystemExit(
        f"sparse: spmv digest diverged from the dense reference "
        f"mask-matmul at densities {bad}"
    )
tr = summary["transpose"]
if tr["chunked_stages"] < 2:
    raise SystemExit(
        f"sparse: transpose did not decompose ({tr['chunked_stages']} stage)"
    )
if not tr["digest_match"]:
    raise SystemExit(
        "sparse: stage-decomposed transpose diverged from the monolithic "
        "exchange"
    )
hlo = (summary.get("telemetry") or {}).get("hlo_collectives") or {}
if hlo.get("audits", 0) < 1:
    raise SystemExit("sparse: --audit recorded no HLO audits")
if hlo.get("drift", 0) != 0:
    raise SystemExit(
        f"sparse: HLO audit drift on sparse collective sites: "
        f"{ {k: v for k, v in (hlo.get('sites') or {}).items() if v.get('drift')} }"
    )
spec = summary.get("spectral") or {}
if spec.get("label_agreement") != 1.0:
    raise SystemExit(
        f"sparse: Spectral sparse-vs-dense labels disagree "
        f"({spec.get('label_agreement')})"
    )
print(
    f"sparse ok: digest bit-identical at densities "
    f"{[r['density'] for r in summary['densities']]}, transpose "
    f"{tr['chunked_stages']}-stage bit-identical, "
    f"{hlo.get('audits')} audits zero-drift, spectral agreement 1.0"
)
EOF
    else
        sp_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$sp_out" "${REPORT}/sparse.jsonl" || true
    fi
    rm -f "$sp_out"
    if [ "$sp_rc" != 0 ]; then
        echo "=== sparse gate FAILED (rc=$sp_rc) ==="
        FAILED_SIZES="$FAILED_SIZES sparse"
    fi
fi

# Hierarchy gate (ISSUE 15): on the emulated 2x2 mesh — flat-vs-tiered
# digest bit-identity for exact payloads, audited cross-node wire-byte
# reduction >= the 1/local shard factor (x the PR 9 compression factor
# under a cross-tier precision), DASO send bit-equivalence through the
# shared tier primitive, and the ZeRO sharded-state watermark strictly
# below the replicated base. HEAT_TPU_CI_SKIP_HIERARCHY=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_HIERARCHY:-}" ]; then
    echo "=== hierarchy gate: tiered collectives + ZeRO (emulated 2x2 mesh) ==="
    hier_rc=0
    hier_out=$(mktemp)
    XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
        HEAT_TPU_TOPOLOGY=2x2 \
        python - <<'EOF' > "$hier_out" 2>&1 || hier_rc=$?
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from heat_tpu.telemetry import collectives as model, hlo

comm = ht.get_comm()
p = comm.size
assert p == 4, f"expected a 4-device mesh, got {p}"
topo = comm.topology()
assert (topo.node, topo.local) == (2, 2), topo
report = {"mesh": p, "topology": topo.describe()}
spec = comm.spec(0, 2)


def run(kernel, x):
    return jax.shard_map(
        kernel, mesh=comm.mesh, in_specs=spec, out_specs=spec
    )(x)


# -- flat-vs-tiered digest bit-identity (exact payloads) ---------------------
rng = np.random.default_rng(0)
xi = jnp.asarray(np.round(rng.standard_normal((4, 1027)) * 8).astype(np.float32))
xs = jax.device_put(xi, comm.sharding(0, 2))
digests = {}
for hier in ("0", "1"):
    os.environ["HEAT_TPU_HIERARCHICAL"] = hier
    out = {
        "psum": np.asarray(run(lambda v: comm.psum(v), xs)),
        "gather": np.asarray(run(lambda v: comm.all_gather(v)[: v.shape[0]], xs)),
        "rs": np.asarray(run(lambda v: comm.reduce_scatter(v).reshape(1, -1), xs)),
    }
    digests[hier] = {k: v.tobytes() for k, v in out.items()}
for k in digests["0"]:
    if digests["0"][k] != digests["1"][k]:
        raise SystemExit(f"hierarchy: {k} tiered digest != flat digest")

# -- audited cross-node byte reduction >= local shard factor ------------------
n = 4096
xb = jax.device_put(jnp.ones((4, n), jnp.float32), comm.sharding(0, 2))
os.environ["HEAT_TPU_HIERARCHICAL"] = "0"
aud_flat = hlo.audit_computation(
    lambda v: jax.shard_map(lambda b: comm.psum(b), mesh=comm.mesh,
                            in_specs=spec, out_specs=spec)(v), xb)
os.environ["HEAT_TPU_HIERARCHICAL"] = "1"
aud_hier = hlo.audit_computation(
    lambda v: jax.shard_map(lambda b: comm.psum(b), mesh=comm.mesh,
                            in_specs=spec, out_specs=spec)(v), xb)
flat_ar = [c for c in aud_flat.collectives if c.op == "all-reduce"]
cross = [c for c in aud_hier.collectives if c.op == "all-reduce"]
assert len(flat_ar) == 1 and len(cross) == 1
if flat_ar[0].in_bytes != cross[0].in_bytes * topo.local:
    raise SystemExit(
        f"hierarchy: cross-node payload {cross[0].in_bytes} is not the "
        f"1/{topo.local} shard of the flat {flat_ar[0].in_bytes}"
    )
reduction = flat_ar[0].wire_bytes / cross[0].wire_bytes
if reduction < topo.local:
    raise SystemExit(
        f"hierarchy: cross wire reduction {reduction:.2f}x below the "
        f"{topo.local}x shard factor"
    )
pred = model.hierarchical_allreduce_cost(n, 4, topo.node, topo.local)
rep = hlo.compare(aud_hier, pred)
if not rep.ok:
    raise SystemExit(
        f"hierarchy: tiered psum audit drifted: {json.dumps(rep.summary())}"
    )
report["cross_reduction"] = round(reduction, 2)

# x the PR 9 compression factor under a cross-tier precision
aud_q = hlo.audit_computation(
    lambda v: jax.shard_map(lambda b: comm.psum(b, precision="int8"),
                            mesh=comm.mesh, in_specs=spec,
                            out_specs=spec)(v), xb)
pred_q = model.hierarchical_allreduce_cost(n, 4, topo.node, topo.local, "int8")
rep_q = hlo.compare(aud_q, pred_q)
if not rep_q.ok:
    raise SystemExit(
        f"hierarchy: int8 cross-tier audit drifted: "
        f"{json.dumps(rep_q.summary())}"
    )
if pred_q.dcn_bytes * 3.5 > pred.dcn_bytes:
    raise SystemExit(
        f"hierarchy: int8 cross tier did not compress "
        f"({pred_q.dcn_bytes} vs exact {pred.dcn_bytes})"
    )
report["dcn_bytes"] = {"exact": pred.dcn_bytes, "int8": pred_q.dcn_bytes}

# -- DASO send bit-equivalence through the tier primitive ---------------------
os.environ.pop("HEAT_TPU_HIERARCHICAL", None)
from jax.sharding import PartitionSpec as P

daso = ht.optim.DASO(optax.sgd(0.05), total_epochs=2)
params = daso.stack_params(
    {"w": jnp.asarray(rng.standard_normal((24, 3)).astype(np.float32))}
)


def legacy_send(params):
    cast = daso.cast_dtype

    def kernel(params):
        params = jax.tree.map(lambda x: x[0], params)

        def one(x):
            rep = jax.lax.pmean(x, "local")
            return jax.lax.psum(rep.astype(cast), "node")[None]

        return jax.tree.map(one, params)

    stacked = P(("node", "local"))
    specs_p = jax.tree.map(lambda _: stacked, params)
    return jax.shard_map(
        kernel, mesh=daso.mesh, in_specs=(specs_p,), out_specs=specs_p
    )(params)


got = daso._get_global_send()(params)
want = legacy_send(params)
for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
    if np.asarray(a).tobytes() != np.asarray(b).tobytes():
        raise SystemExit("hierarchy: DASO tiered send != legacy send bits")

# -- ZeRO watermark: sharded state strictly below replicated ------------------
params0 = {"w": jnp.asarray(rng.standard_normal((512, 8)).astype(np.float32))}
zo = ht.optim.ZeroOptimizer(optax.adam(1e-2))
dp = ht.optim.DataParallelOptimizer(optax.adam(1e-2))
zb = zo.state_bytes_per_device(zo.init(params0))
db = sum(np.asarray(l).nbytes for l in jax.tree.leaves(dp.init(params0)))
if not (0 < zb < db):
    raise SystemExit(
        f"hierarchy: ZeRO state bytes/device {zb} not strictly below "
        f"replicated {db}"
    )
# and the trajectory matches the replicated base
grads = jax.tree.map(
    lambda l: jnp.asarray(rng.standard_normal(l.shape).astype(np.float32)),
    params0,
)
zp, zs = params0, zo.init(params0)
pp, ps = params0, dp.init(params0)
for _ in range(4):
    zp, zs = zo.step(zp, zs, grads)
    pp, ps = dp.step(pp, ps, grads)
drift = max(
    float(np.abs(np.asarray(a) - np.asarray(b)).max())
    for a, b in zip(jax.tree.leaves(zp), jax.tree.leaves(pp))
)
if drift > 1e-6:
    raise SystemExit(f"hierarchy: ZeRO trajectory drifted {drift}")
report["zero_state_bytes"] = {"sharded_per_device": zb, "replicated": db}
print(json.dumps({"hierarchy": "ok", **report}))
EOF
    cat "$hier_out"
    if [ -n "$REPORT" ]; then
        cp "$hier_out" "${REPORT}/hierarchy_gate.log" || true
    fi
    rm -f "$hier_out"
    if [ "$hier_rc" != 0 ]; then
        echo "=== hierarchy gate FAILED (rc=$hier_rc) ==="
        FAILED_SIZES="$FAILED_SIZES hierarchy"
    fi
fi

# FSDP gate (ISSUE 18): on the emulated 2x2 mesh — the big-model
# scenario end to end: a model whose REPLICATED parameters+state exceed
# a pinned HEAT_TPU_HBM_BUDGET trains under FSDP with the per-device
# watermark strictly below both the budget and the replicated base;
# knob-off dispatch bit-identical to the DataParallel program; enabled
# trajectory within documented-ulp (1e-6) of the replicated baseline
# (exact wire — the reduction ORDER differs, bits may not); prefetch
# depths bit-identical to each other (pure scheduling); per-layer
# audited gather wire bytes == fsdp_gather_cost with ZERO drift; and
# zero steady-state compiles at the fsdp_train_step site.
# HEAT_TPU_CI_SKIP_FSDP=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_FSDP:-}" ]; then
    echo "=== fsdp gate: sharded-parameter training (emulated 2x2 mesh) ==="
    fsdp_rc=0
    fsdp_out=$(mktemp)
    XLA_FLAGS="--xla_force_host_platform_device_count=4" JAX_PLATFORMS=cpu \
        HEAT_TPU_TOPOLOGY=2x2 \
        python - <<'EOF' > "$fsdp_out" 2>&1 || fsdp_rc=$?
import json
import os

import flax.linen as fnn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from heat_tpu.core import program_cache
from heat_tpu.nn.fsdp import FSDP
from heat_tpu.parallel import fsdp as F
from heat_tpu.telemetry import collectives as model, hlo

comm = ht.get_comm()
p = comm.size
assert p == 4, f"expected a 4-device mesh, got {p}"
topo = comm.topology()
assert (topo.node, topo.local) == (2, 2), topo
report = {"mesh": p, "topology": topo.describe()}

STAGES = [fnn.Dense(96), fnn.Dense(96), fnn.Dense(32)]
OPT = optax.adam(1e-3)
rng = np.random.default_rng(0)
x = rng.standard_normal((8, 32)).astype(np.float32)
y = rng.standard_normal((8, 32)).astype(np.float32)


def loss_fn(out, yy):
    return jnp.mean((out - yy) ** 2)


def build(enabled, prefetch=1):
    os.environ["HEAT_TPU_FSDP"] = "1" if enabled else "0"
    return FSDP(list(STAGES), optimizer=OPT, prefetch=prefetch)


def run(net, steps=4):
    params = net.shard_params(net.init(jax.random.PRNGKey(0), x))
    state = net.init_opt_state(params)
    step = net.make_train_step(loss_fn)
    xb, yb = net.shard_batch(x, y)
    for _ in range(steps):
        params, state, loss = step(params, state, xb, yb)
    return net, params, state, step, (xb, yb)


def digest(net, params):
    return b"".join(
        np.asarray(l).tobytes()
        for l in jax.tree_util.tree_leaves(net.unshard_params(params))
    )


# -- knob-off dispatch is the DataParallel program, bit for bit ---------------
off_net, off_p, _, _, _ = run(build(enabled=False))


def full_forward(params, xx):
    for m, sp in zip(STAGES, params):
        xx = m.apply(sp, xx)
    return xx


dp = ht.nn.DataParallel(
    full_forward, comm, OPT, blocking_parameter_updates=True
)
dpp = jax.device_put(
    off_net.init(jax.random.PRNGKey(0), x), comm.replicated()
)
dps = jax.device_put(OPT.init(dpp), comm.replicated())
dstep = dp.make_train_step(
    lambda params, xx, yy: loss_fn(full_forward(params, xx), yy)
)
xb, yb = dp.shard_batch(x, y)
for _ in range(4):
    dpp, dps, _ = dstep(dpp, dps, xb, yb)
if digest(off_net, off_p) != b"".join(
    np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(dpp)
):
    raise SystemExit("fsdp: knob-off dispatch != DataParallel bits")

# -- big-model scenario: replicated exceeds the budget, FSDP fits -------------
on_net, on_p, on_s, on_step, on_batch = run(build(enabled=True))
rep_params = jax.device_put(
    on_net.init(jax.random.PRNGKey(0), x), comm.replicated()
)
rb = F.bytes_per_device(rep_params) + F.bytes_per_device(
    jax.device_put(OPT.init(rep_params), comm.replicated())
)
fb = F.bytes_per_device(on_p) + F.bytes_per_device(on_s)
budget = (fb + rb) // 2
os.environ["HEAT_TPU_HBM_BUDGET"] = str(budget)
# train MORE steps with the guard budget pinned: the sharded layout must
# keep fitting where the replicated layout could not
pp, ss = on_p, on_s
for _ in range(2):
    pp, ss, _ = on_step(pp, ss, *on_batch)
if not (0 < fb < budget < rb):
    raise SystemExit(
        f"fsdp: watermark {fb} not strictly below budget {budget} "
        f"below replicated {rb}"
    )
report["bytes_per_device"] = {
    "fsdp": fb, "replicated": rb, "hbm_budget": budget,
}

# -- enabled trajectory within documented ulp of the replicated base ----------
drift = max(
    float(np.abs(np.asarray(a) - np.asarray(b)).max())
    for a, b in zip(
        jax.tree_util.tree_leaves(on_net.unshard_params(on_p)),
        jax.tree_util.tree_leaves(off_net.unshard_params(off_p)),
    )
)
if drift > 1e-6:
    raise SystemExit(f"fsdp: trajectory drifted {drift} > 1e-6")
report["trajectory_drift"] = drift

# -- prefetch depths are pure scheduling: bit-identical -----------------------
d0 = digest(*run(build(enabled=True, prefetch=0))[:2])
d2 = digest(*run(build(enabled=True, prefetch=2))[:2])
if d0 != d2:
    raise SystemExit("fsdp: prefetch depth changed the bits")

# -- per-layer audited gather bytes == cost model, zero drift -----------------
plan = on_net._plan
axis = comm.axis_name
p_specs = plan.unflatten(
    [P(axis) if l.sharded else P() for l in plan.leaves]
)
fwd = jax.jit(jax.shard_map(
    lambda ps, xx: on_net._forward_local(
        ps, xx, plan, on_net.prefetch, remat=False
    ),
    mesh=comm.mesh, in_specs=(p_specs, P(axis)), out_specs=P(axis),
))
aud = hlo.audit_computation(fwd, on_p, on_batch[0])
predicted = sum(
    model.fsdp_gather_cost(
        l.chunk, 4, topo.node, topo.local, l.wire
    ).bytes
    for l in plan.leaves if l.sharded
)
audited = sum(
    c.wire_bytes for c in aud.collectives if c.op == "all-gather"
)
if audited != predicted:
    raise SystemExit(
        f"fsdp: audited gather bytes {audited} != predicted {predicted}"
    )
report["gather_wire_bytes"] = {"audited": audited, "predicted": predicted}

# -- zero steady-state compiles ----------------------------------------------
before = program_cache.site_stats("fsdp_train_step")
pp, ss = on_p, on_s
for _ in range(3):
    pp, ss, _ = on_step(pp, ss, *on_batch)
again = on_net.make_train_step(loss_fn)
after = program_cache.site_stats("fsdp_train_step")
if after["misses"] != before["misses"] or again is not on_step:
    raise SystemExit(
        f"fsdp: steady state recompiled ({before} -> {after})"
    )
report["train_step_site"] = after
print(json.dumps({"fsdp": "ok", **report}))
EOF
    cat "$fsdp_out"
    if [ -n "$REPORT" ]; then
        cp "$fsdp_out" "${REPORT}/fsdp_gate.log" || true
    fi
    rm -f "$fsdp_out"
    if [ "$fsdp_rc" != 0 ]; then
        echo "=== fsdp gate FAILED (rc=$fsdp_rc) ==="
        FAILED_SIZES="$FAILED_SIZES fsdp"
    fi
fi

# Pipeline gate (ISSUE 19): on an emulated 4x2 mesh (stages == node
# groups) —
#   (a) the 1f1b training digest is BIT-identical to gpipe (same loss,
#       params, and optimizer state bytes: pure scheduling),
#   (b) measured per-tick telemetry bubbles reconcile EXACTLY with the
#       analytic ScheduleTable for both schedules, and 1f1b's
#       steady-window bubble ticks are strictly fewer (12 -> 10 at
#       S=4, M=8),
#   (c) the 1f1b activation watermark (memory_analysis temp bytes) is
#       strictly below gpipe's,
#   (d) the audited inter-stage hop is zero-drift: emitted
#       collective-permute count == 2*(n_ticks-1), per-instruction wire
#       == pipeline_hop_cost, and the DCN split re-derived from the
#       emitted source-target pairs == the model's dcn_bytes exactly,
#   (e) a run SIGKILLed after checkpointing resumes onto a DIFFERENT
#       node x local factorization AND schedule with a bit-identical
#       continued trajectory, and
#   (f) zero steady-state compiles at the pipeline.step site.
# HEAT_TPU_CI_SKIP_PIPELINE=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_PIPELINE:-}" ]; then
    echo "=== pipeline gate: 1F1B over node groups (emulated 4x2 mesh) ==="
    pipe_rc=0
    pipe_out=$(mktemp)
    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
        HEAT_TPU_TOPOLOGY=4x2 \
        python - <<'EOF' > "$pipe_out" 2>&1 || pipe_rc=$?
import json
import os
import signal
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from heat_tpu import telemetry as tm
from heat_tpu.core import program_cache
from heat_tpu.nn import Pipeline
from heat_tpu.parallel import pipeline as pl
from heat_tpu.parallel import schedule as sch
from heat_tpu.telemetry import collectives as model, hlo

comm = ht.get_comm()
p = comm.size
assert p == 8, f"expected an 8-device mesh, got {p}"
report = {"mesh": p, "topology": comm.topology().describe()}

S, M, L, DIN = 4, 8, 4, 8
OPT = optax.adam(1e-2)


def layer_fn(w, h):
    return jnp.tanh(h @ w["w"] + w["b"])


def loss_fn(out, yy):
    return jnp.mean((out - yy) ** 2)


def make_layers():
    rng = np.random.default_rng(0)
    return [
        {"w": jnp.asarray(rng.standard_normal((DIN, DIN)) * 0.3,
                          jnp.float32),
         "b": jnp.asarray(rng.standard_normal((DIN,)) * 0.1, jnp.float32)}
        for _ in range(L)
    ]


def make_data():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((16, DIN)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((16, DIN)), jnp.float32)
    return x, y


def run(schedule, n_stages=S, steps=4):
    pipe = Pipeline(layer_fn, L, comm, OPT, loss_fn, n_stages=n_stages,
                    n_microbatches=M, schedule=schedule)
    params = pipe.shard_params(make_layers())
    state = pipe.init_opt_state(params)
    step = pipe.make_train_step()
    x, y = make_data()
    loss = None
    for _ in range(steps):
        params, state, loss = step(params, state, x, y)
    return pipe, params, state, step, (x, y), loss


def digest(pipe, params, state, loss):
    blobs = [
        np.asarray(l).tobytes()
        for layer in pipe.unshard_params(params)
        for l in jax.tree_util.tree_leaves(layer)
    ]
    blobs.append(np.asarray(loss).tobytes())
    return b"".join(blobs)


# -- (a) 1f1b digest bit-identical to gpipe -----------------------------------
g_pipe, g_p, g_s, g_step, g_batch, g_loss = run("gpipe")
f_pipe, f_p, f_s, f_step, f_batch, f_loss = run("1f1b")
if digest(g_pipe, g_p, g_s, g_loss) != digest(f_pipe, f_p, f_s, f_loss):
    raise SystemExit("pipeline: 1f1b digest differs from gpipe")
if np.asarray(g_loss).tobytes() != np.asarray(f_loss).tobytes():
    raise SystemExit("pipeline: schedule changed the loss bytes")
report["digest_bit_identical"] = True
report["loss"] = float(g_loss)

# -- (b) measured per-tick bubbles == analytic table, 1f1b strictly wins ------
measured = {}
for name in ("gpipe", "1f1b"):
    table = sch.build_schedule(S, M, name)
    mapping = sch.StageMapping(p, S)
    layers = make_layers()
    layout = pl.plan_pipeline(layers, mapping)
    rows = pl.shard_pipeline_params(layers, layout, comm)
    st = OPT.init(rows)
    x, y = make_data()
    mx, my = x.reshape(M, 2, DIN), y.reshape(M, 2, DIN)

    def fresh_layer(w, h):  # new callable => fresh trace under telemetry
        return jnp.tanh(h @ w["w"] + w["b"])

    sink = tempfile.mktemp(suffix=".jsonl")
    reg = tm.enable(sink)
    n0 = len(reg.events)
    try:
        step = pl.pipeline_step_program(
            fresh_layer, layout, mapping, table, comm=comm,
            loss_fn=loss_fn, optimizer=OPT)
        step(rows, st, mx, my)
        events = list(reg.events)[n0:]
    finally:
        tm.disable()
        os.path.exists(sink) and os.unlink(sink)
    ticks = [e for e in events if e.get("name") == "pipeline_tick"]
    if len(ticks) != table.n_ticks:
        raise SystemExit(
            f"pipeline: {name} traced {len(ticks)} tick spans, "
            f"table has {table.n_ticks}"
        )
    steady = sum(e["bubble"] for e in ticks if e["phase"] == "steady")
    total = sum(e["bubble"] for e in ticks)
    if steady != table.steady_bubble_ticks():
        raise SystemExit(
            f"pipeline: {name} measured {steady} steady bubbles, "
            f"table says {table.steady_bubble_ticks()}"
        )
    if total != table.bubble_cells():
        raise SystemExit(
            f"pipeline: {name} measured {total} bubble cells, "
            f"table says {table.bubble_cells()}"
        )
    measured[name] = {"steady_bubble_ticks": steady,
                      "bubble_cells": total,
                      "bubble_fraction": table.bubble_fraction()}
if not (measured["1f1b"]["steady_bubble_ticks"]
        < measured["gpipe"]["steady_bubble_ticks"]):
    raise SystemExit(f"pipeline: 1f1b did not win steady bubbles {measured}")
report["schedules"] = measured

# -- (c) 1f1b activation watermark strictly below gpipe -----------------------
def temp_bytes(name):
    table = sch.build_schedule(S, M, name)
    mapping = sch.StageMapping(p, S)
    layers = make_layers()
    layout = pl.plan_pipeline(layers, mapping)
    rows = pl.shard_pipeline_params(layers, layout, comm)
    st = OPT.init(rows)
    x, y = make_data()
    mx, my = x.reshape(M, 2, DIN), y.reshape(M, 2, DIN)
    step = pl.pipeline_step_program(
        layer_fn, layout, mapping, table, comm=comm,
        loss_fn=loss_fn, optimizer=OPT)
    ma = jax.jit(step).lower(rows, st, mx, my).compile().memory_analysis()
    return int(getattr(ma, "temp_size_in_bytes", 0) or 0)


g_temp, f_temp = temp_bytes("gpipe"), temp_bytes("1f1b")
if g_temp and f_temp:
    if not f_temp < g_temp:
        raise SystemExit(
            f"pipeline: 1f1b watermark {f_temp} not below gpipe {g_temp}"
        )
    report["activation_watermark"] = {"gpipe": g_temp, "1f1b": f_temp}
else:
    report["activation_watermark"] = "unavailable"

# -- (d) audited inter-stage hop: zero drift incl. the DCN split --------------
mapping = sch.StageMapping(p, S)
table = sch.build_schedule(S, M, "gpipe")
layers = make_layers()
layout = pl.plan_pipeline(layers, mapping)
rows = pl.shard_pipeline_params(layers, layout, comm)
st = OPT.init(rows)
x, y = make_data()
mx, my = x.reshape(M, 2, DIN), y.reshape(M, 2, DIN)
step = pl.pipeline_step_program(
    layer_fn, layout, mapping, table, comm=comm,
    loss_fn=loss_fn, optimizer=OPT)
audit = hlo.audit_computation(step, rows, st, mx, my)
perms = [c for c in audit.collectives if c.op == "collective-permute"]
hop = model.pipeline_hop_cost(
    2, DIN, 4, p, stride=mapping.local, local=comm.topology().local)
if hop.dcn_bytes != hop.bytes:
    raise SystemExit(
        "pipeline: stages==node groups must make the whole hop DCN"
    )
if len(perms) != 2 * (table.n_ticks - 1):
    raise SystemExit(
        f"pipeline: {len(perms)} permutes, expected {2 * (table.n_ticks - 1)}"
    )
emitted = emitted_dcn = 0
for c in perms:
    if c.wire_bytes != hop.bytes:
        raise SystemExit(
            f"pipeline: hop drift {c.wire_bytes} != {hop.bytes}"
        )
    pairs = [tuple(pr) for pr in c.groups]
    per_pair = c.wire_bytes // len(pairs)
    nl = comm.topology().local
    cross = [pr for pr in pairs if pr[0] // nl != pr[1] // nl]
    emitted += c.wire_bytes
    emitted_dcn += per_pair * len(cross)
if emitted != 2 * (table.n_ticks - 1) * hop.bytes:
    raise SystemExit("pipeline: total hop bytes drift")
if emitted_dcn != 2 * (table.n_ticks - 1) * hop.dcn_bytes:
    raise SystemExit(
        f"pipeline: DCN split drift {emitted_dcn} != "
        f"{2 * (table.n_ticks - 1) * hop.dcn_bytes}"
    )
report["hop_audit"] = {
    "permutes": len(perms), "wire_bytes": emitted,
    "dcn_bytes": emitted_dcn, "drift": 0,
}

# -- (e) SIGKILLed run resumes on a different factorization, bit-exact --------
ckpt_dir = tempfile.mkdtemp(prefix="pipe_gate_") + "/ckpt"
child = r"""
import os, signal
import jax.numpy as jnp
import numpy as np
import optax
import heat_tpu as ht
from heat_tpu.nn import Pipeline

comm = ht.get_comm()
S, M, L, DIN = 4, 8, 4, 8

def layer_fn(w, h):
    return jnp.tanh(h @ w["w"] + w["b"])

def loss_fn(out, yy):
    return jnp.mean((out - yy) ** 2)

rng = np.random.default_rng(0)
layers = [
    {"w": jnp.asarray(rng.standard_normal((DIN, DIN)) * 0.3, jnp.float32),
     "b": jnp.asarray(rng.standard_normal((DIN,)) * 0.1, jnp.float32)}
    for _ in range(L)
]
rng = np.random.default_rng(1)
x = jnp.asarray(rng.standard_normal((16, DIN)), jnp.float32)
y = jnp.asarray(rng.standard_normal((16, DIN)), jnp.float32)

pipe = Pipeline(layer_fn, L, comm, optax.adam(1e-2), loss_fn,
                n_stages=S, n_microbatches=M, schedule="1f1b")
params = pipe.shard_params(layers)
state = pipe.init_opt_state(params)
step = pipe.make_train_step()
for _ in range(2):
    params, state, loss = step(params, state, x, y)
pipe.save_checkpoint(os.environ["PIPE_GATE_CKPT"], params, state, step=2)
print("checkpointed at step 2", flush=True)
params, state, loss = step(params, state, x, y)  # dies mid-run
os.kill(os.getpid(), signal.SIGKILL)
"""
env = dict(os.environ, PIPE_GATE_CKPT=ckpt_dir)
proc = subprocess.run([sys.executable, "-c", child], env=env,
                      capture_output=True, text=True, timeout=600)
if proc.returncode != -signal.SIGKILL:
    raise SystemExit(
        f"pipeline: chaos child rc={proc.returncode}\n{proc.stdout}"
        f"\n{proc.stderr}"
    )
if "checkpointed at step 2" not in proc.stdout:
    raise SystemExit(f"pipeline: child never checkpointed\n{proc.stderr}")

# the uninterrupted reference (same seeds/schedule as the killed run)
ref_pipe, ref_p, ref_s, _, _, ref_loss = run("1f1b")
# restore onto 2 stages x 4 local AND the other schedule
res_pipe = Pipeline(layer_fn, L, comm, OPT, loss_fn, n_stages=2,
                    n_microbatches=M, schedule="gpipe")
res_params, res_state, cursor = res_pipe.resume(ckpt_dir, make_layers())
if cursor != 2:
    raise SystemExit(f"pipeline: resumed cursor {cursor} != 2")
res_step = res_pipe.make_train_step()
x, y = make_data()
res_loss = None
for _ in range(2):
    res_params, res_state, res_loss = res_step(res_params, res_state, x, y)
if np.asarray(ref_loss).tobytes() != np.asarray(res_loss).tobytes():
    raise SystemExit("pipeline: restored loss trajectory diverged")
ref_final = ref_pipe.unshard_params(ref_p)
res_final = res_pipe.unshard_params(res_params)
for ja, jb in zip(ref_final, res_final):
    for la, lb in zip(jax.tree_util.tree_leaves(ja),
                      jax.tree_util.tree_leaves(jb)):
        if np.asarray(la).tobytes() != np.asarray(lb).tobytes():
            raise SystemExit(
                "pipeline: restored params diverged from uninterrupted run"
            )
report["elastic"] = {
    "killed_at": "step 3 (SIGKILL)", "resumed_onto": "2x4 gpipe",
    "trajectory": "bit-identical",
}

# -- (f) zero steady-state compiles at the pipeline.step site -----------------
before = program_cache.site_stats("pipeline.step")
with tm.CompileWatcher() as watch:
    for _ in range(3):
        g_p, g_s, _ = g_step(g_p, g_s, *g_batch)
after = program_cache.site_stats("pipeline.step")
if after["misses"] != before["misses"]:
    raise SystemExit(
        f"pipeline: steady state recompiled ({before} -> {after})"
    )
if watch.backend_seconds != 0.0:
    raise SystemExit(
        f"pipeline: steady state hit the backend "
        f"({watch.backend_seconds}s)"
    )
report["step_site"] = after
print(json.dumps({"pipeline": "ok", **report}))
EOF
    cat "$pipe_out"
    if [ -n "$REPORT" ]; then
        cp "$pipe_out" "${REPORT}/pipeline_gate.log" || true
    fi
    rm -f "$pipe_out"
    if [ "$pipe_rc" != 0 ]; then
        echo "=== pipeline gate FAILED (rc=$pipe_rc) ==="
        FAILED_SIZES="$FAILED_SIZES pipeline"
    fi
fi

# Streaming gate (ISSUE 16, heat_tpu/streaming): a 2-file HDF5 stream
# under a pinned HEAT_TPU_HBM_BUDGET that forbids materializing the file
# set must show
#   (a) the out-of-core chunk-bytes watermark strictly below the
#       load-all bytes (the bounded-memory ingestion claim),
#   (b) digest parity of the streamed moments carry against the
#       in-memory full-pass reference,
#   (c) a zero-compile steady stream (one cached-program miss for the
#       steady chunk shape, hits for every later chunk), and
#   (d) the rolling replica update: a 2-replica pool rolls v2 and v3
#       through live open-loop traffic with ZERO failed requests, every
#       survivor on the final version, and zero steady-state backend
#       compiles on the replacements (shared-cache warm start).
# HEAT_TPU_CI_SKIP_STREAMING=1 opts out.
if [ -z "${HEAT_TPU_CI_SKIP_STREAMING:-}" ]; then
    echo "=== streaming gate: out-of-core fit + rolling update (4-device mesh) ==="
    stream_rc=0
    stream_out=$(mktemp)
    stream_fmt="--hdf5"
    python -c "import h5py" 2>/dev/null || stream_fmt=""
    if HEAT_TPU_TELEMETRY=1 python benchmarks/streaming/heat_tpu.py \
            --n 40000 --features 16 --files 2 $stream_fmt \
            --mesh 4 --replica-mesh 4 --replicas 2 --versions 3 \
            --hbm-budget 2M --requests 120 --rate 100 > "$stream_out"; then
        python - "$stream_out" <<'EOF' || stream_rc=$?
import json, sys

summary = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if obj.get("bench") == "streaming":
        summary = obj
if summary is None:
    raise SystemExit("streaming: no summary line")

sf = summary["stream_fit"] or {}
if not sf.get("watermark_below_load_all"):
    raise SystemExit(
        f"streaming: chunk watermark not below the load-all bytes: {sf}"
    )
if not sf.get("digest_match"):
    raise SystemExit(
        f"streaming: streamed moments diverged from the in-memory fit: {sf}"
    )
if not sf.get("steady_zero_compile"):
    raise SystemExit(
        f"streaming: the steady stream kept compiling: {sf}"
    )

roll = summary["rolling"] or {}
if not roll.get("zero_failed_requests"):
    raise SystemExit(
        f"streaming: requests failed during the rolling update: {roll}"
    )
if not roll.get("all_on_final_version"):
    raise SystemExit(
        f"streaming: a replica is not on the final version: {roll}"
    )
if not roll.get("steady_backend_compiles_ok"):
    raise SystemExit(
        "streaming: a rolled replica backend-compiled in steady state "
        f"(shared-cache warm start failed): {roll}"
    )

print(
    f"streaming ok: watermark below load-all, digest parity, steady "
    f"zero-compile, roll to v3 with 0 failed requests "
    f"(p99 roll/steady = {roll.get('p99_roll_over_steady')})"
)
EOF
    else
        stream_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$stream_out" "${REPORT}/streaming.jsonl" || true
    fi
    rm -f "$stream_out"
    if [ "$stream_rc" != 0 ]; then
        echo "=== streaming gate FAILED (rc=$stream_rc) ==="
        FAILED_SIZES="$FAILED_SIZES streaming"
    fi
fi

if [ -z "${HEAT_TPU_CI_SKIP_CLUSTER_OBS:-}" ]; then
    echo "=== cluster-observability gate: merged tracing + fleet metrics + SLO burn (2-replica pool) ==="
    clobs_rc=0
    clobs_out=$(mktemp)
    if python benchmarks/serving/cluster_obs.py \
            --n 256 --features 16 --requests 40 --rate 80 \
            --slo-requests 12 --slo-rate 20 > "$clobs_out"; then
        python - "$clobs_out" <<'EOF' || clobs_rc=$?
import json, sys

summary = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if obj.get("bench") == "cluster_obs":
        summary = obj
if summary is None:
    raise SystemExit("cluster-obs: no summary line")

if not (summary.get("off_clean") and summary.get("on_clean")):
    raise SystemExit(f"cluster-obs: load phases not clean: {summary}")
if not summary.get("off_tracing_zero"):
    raise SystemExit(
        "cluster-obs: tracing-off run recorded tracing counters "
        f"(the off posture must do zero per-hop work): {summary}"
    )
if not summary.get("digest_match"):
    raise SystemExit(
        "cluster-obs: tracing changed the answers (digest mismatch "
        f"between off and sampled-1.0 runs): {summary}"
    )
if not summary.get("metrics_merge_match"):
    raise SystemExit(
        "cluster-obs: merged /metrics request totals diverged from "
        f"the loadgen completions: {summary}"
    )
if not summary.get("hops_complete"):
    raise SystemExit(
        "cluster-obs: a sampled trace id is missing hop spans "
        f"({summary.get('complete_ids')}/{summary.get('sampled_ids')} "
        f"complete): {summary}"
    )
if not summary.get("p99_exact_match_inproc"):
    raise SystemExit(
        "cluster-obs: summarize_cluster p99 diverged from the "
        f"server's own histogram quantile: {summary}"
    )
if not summary.get("p99_within_bucket"):
    raise SystemExit(
        "cluster-obs: merged server-side p99 not within one bucket "
        f"width of the client-observed p99: {summary}"
    )
if not summary.get("merged_trace_ok"):
    raise SystemExit(
        "cluster-obs: merged Perfetto export missing pid tracks or "
        f"clock_sync records: {summary}"
    )
if not summary.get("slo_breach"):
    raise SystemExit(
        "cluster-obs: injected latency did not drive the SLO burn "
        f"rate above threshold: {summary}"
    )
if not summary.get("slo_burn_emitted"):
    raise SystemExit(
        "cluster-obs: breach detected but no slo_burn event/counter "
        f"emitted: {summary}"
    )

print(
    f"cluster-obs ok: digest bit-identity off/on, zero off-counters, "
    f"{summary.get('complete_ids')}/{summary.get('sampled_ids')} trace "
    f"ids complete across all hops, exact merged p99, SLO burn "
    f"breach + slo_burn emitted"
)
EOF
    else
        clobs_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$clobs_out" "${REPORT}/cluster_obs.jsonl" || true
    fi
    rm -f "$clobs_out"
    if [ "$clobs_rc" != 0 ]; then
        echo "=== cluster-observability gate FAILED (rc=$clobs_rc) ==="
        FAILED_SIZES="$FAILED_SIZES cluster-obs"
    fi
fi

if [ -z "${HEAT_TPU_CI_SKIP_AUTOSCALE:-}" ]; then
    echo "=== autoscale gate: SLO-driven scale-up/drain-down + chaos SIGKILL replacement (ISSUE 20) ==="
    autoscale_rc=0
    autoscale_out=$(mktemp)
    if python benchmarks/autoscale/run.py \
            --n 500 --features 16 --replica-mesh 1 \
            --profiles step --duration 15 --peak-rate 150 \
            --max-replicas 3 --drain-wait 25 \
            --chaos --chaos-duration 10 --chaos-rate 20 > "$autoscale_out"; then
        python - "$autoscale_out" <<'EOF' || autoscale_rc=$?
import json, sys

summary = None
for line in open(sys.argv[1]):
    line = line.strip()
    if not line:
        continue
    try:
        obj = json.loads(line)
    except json.JSONDecodeError:
        continue
    if obj.get("bench") == "autoscale":
        summary = obj
if summary is None:
    raise SystemExit("autoscale: no summary line")

step = (summary.get("profiles") or {}).get("step") or {}
if step.get("failed") != 0:
    raise SystemExit(
        f"autoscale: step-load phase had failed requests: {step}"
    )
if not step.get("drained_to_min"):
    raise SystemExit(
        "autoscale: controller did not drain back down to the minimum "
        f"footprint after the load step ended: {step}"
    )
if not summary.get("steady_backend_compiles_ok"):
    raise SystemExit(
        "autoscale: a scaled-up replica compiled in steady state (the "
        f"shared-cache warm start is broken): {summary}"
    )
chaos = summary.get("chaos") or {}
if not chaos.get("replaced_within_bound"):
    raise SystemExit(
        "autoscale: SIGKILLed replica not replaced within "
        f"{chaos.get('replace_tick_bound')} controller ticks: {chaos}"
    )
if not chaos.get("zero_failed"):
    raise SystemExit(
        "autoscale: chaos kill surfaced failed requests despite "
        f"retry_in_flight: {chaos}"
    )
if chaos.get("replacement_steady_compiles") != 0:
    raise SystemExit(
        "autoscale: the chaos-respawned replica compiled in steady "
        f"state: {chaos}"
    )
if not (step.get("scale_ups") or 0) >= 1:
    raise SystemExit(
        f"autoscale: controller never scaled up under the step load: {step}"
    )
print(
    "autoscale ok: step load scaled up then drained to min with "
    f"0 failed, chaos replacement in {chaos.get('ticks_to_replace')} "
    "tick(s) with 0 failed and 0 steady compiles"
)
EOF
    else
        autoscale_rc=$?
    fi
    if [ -n "$REPORT" ]; then
        cp "$autoscale_out" "${REPORT}/autoscale.jsonl" || true
    fi
    rm -f "$autoscale_out"
    if [ "$autoscale_rc" != 0 ]; then
        echo "=== autoscale gate FAILED (rc=$autoscale_rc) ==="
        FAILED_SIZES="$FAILED_SIZES autoscale"
    fi
fi

if [ "$have_coverage" = 1 ]; then
    # merge the per-size coverage files, as the reference CI merges its
    # 8 mpirun passes (Jenkinsfile:33-44 / codecov)
    (cd "$REPORT" && python -m coverage combine .coverage.* \
        && python -m coverage report --include='*/heat_tpu/*' > coverage.txt \
        && tail -1 coverage.txt)
fi
FAILED_SIZES="$FAILED_SIZES$HEATLINT_FAILED"
if [ -n "$RETRIED_ABORTS" ]; then
    # surfaced even on a green sweep: silent retries would hide a rising
    # native-crash rate (advisor round-5 finding)
    echo "=== retried SIGABRT chunks (known XLA CPU heap flake):$RETRIED_ABORTS ==="
fi
if [ -n "$FAILED_SIZES" ]; then
    echo "=== FAILED at device counts:$FAILED_SIZES ==="
    exit 1
fi
echo "=== all device counts green ==="
