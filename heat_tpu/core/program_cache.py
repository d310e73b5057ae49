"""Process-global compiled-program registry — the one choke point every
jitted program in the framework routes through.

Motivation (ISSUE 3 / PAPERS.md "Memory-efficient array redistribution"):
Heat's MPI choreography becomes *compiled XLA programs* in this port, so
compile time and program reuse are first-class performance axes. Before this
module, three sites memoized their jitted programs behind ad-hoc
``functools.lru_cache``\\ s (each with its own key convention) while ~18
other ``jax.jit`` call sites rebuilt fresh closures per invocation — every
``resplit``, repeated factory assembly, and re-entered kernel retraced and
recompiled an identical program. Now:

* :func:`cached_program` memoizes jitted executables in one process-global
  LRU registry keyed on ``(site, comm identity, static config, donation)``
  — input *avals* are still handled by jax's own dispatch inside each
  cached wrapper, so one registry entry serves every shape that reaches
  the same program builder while distinct static configs get distinct
  entries. Steady-state dispatch is a dict lookup.
* Telemetry counters (``program_cache.hits`` / ``.misses`` /
  ``.evictions`` plus per-site retrace counts) feed
  :func:`heat_tpu.telemetry.report.summarize` and the Chrome trace (each
  retrace/eviction is an instant event on the *events* track).
* The registry size is tunable via ``HEAT_TPU_PROGRAM_CACHE`` (max
  entries; least-recently-used programs are evicted — the *executables*
  they held are additionally bounded by jax's own caches, which the test
  conftest clears per module).
* ``donate=(argnums...)`` passes through to ``jax.jit(donate_argnums=...)``
  so callers whose source buffer is dead after the call (in-place
  ``resplit_``, ``out=`` paths) let XLA reuse the input memory instead of
  holding source + destination live. Donation is part of the cache key: a
  donating and a non-donating caller never share an executable.
* The fusion engine routes every flushed elementwise chain through site
  ``fusion``; Fusion 2.0 (ISSUE 7) adds ``fusion_reduce`` (chain+reduction
  map+reduce programs, keyed on chain signature + reduce op/axis/neutral)
  and ``fusion_moments`` (chain grafted into the pallas column-moments
  kernel) — absorption reuses this registry, so a repeated fused reduction
  is the same dict-lookup dispatch as any cached program.
* The site/key signature is shared with the HLO collective auditor
  (:func:`heat_tpu.telemetry.hlo.audit_call` sites build their memo key via
  :func:`program_key`), so an audited program and the cached program that
  actually executes carry ONE signature — the audit lowers the very same
  jitted callable the dispatch path runs.

Persistent (cross-process) compilation cache
--------------------------------------------
Orthogonal to the in-process registry, :func:`enable_persistent_cache`
turns on JAX's on-disk XLA compilation cache under ONE placement rule:
where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses that
directory and the code sets no other; where it is not, the cache lives at
``<checkout>/.jax_cache`` (git-ignored). The path is part of a cache
entry's key, so it never comes from a temporary directory. The entry
points call it (``chip_smoke.py``, ``bench.py``, ``benchmarks/_harness.py``,
``serve/net/replica.py``, ``tests/conftest.py``); child processes inherit
the environment, so replicas share their parent's cache.
"""

from __future__ import annotations

import os
import threading
import warnings
from collections import OrderedDict
from typing import Any, Callable, Sequence, Tuple

import jax

from heat_tpu import _knobs as knobs

from .. import resilience, telemetry

__all__ = [
    "cached_program",
    "program_key",
    "site_stats",
    "stats",
    "reset",
    "clear",
    "enable_persistent_cache",
    "DEFAULT_MAXSIZE",
]

# Default registry capacity. Entries are jit *wrappers* (closures + jit
# machinery, not executables), so the per-entry footprint is small; the knob
# exists for long-lived services that sweep unbounded shape families.
DEFAULT_MAXSIZE = 512

# A donated buffer whose layout cannot alias the output (e.g. a relayout
# whose physical shapes differ) makes XLA warn "Some donated buffers were
# not usable" at lowering time. The donation is still correct — the
# framework caller declared the buffer dead — so for programs built HERE
# with donate= the warning is pure noise. It is suppressed around those
# calls only (see cached_program), never process-globally: user code
# keeps the diagnostic for its own donate_argnums mistakes.
_DONATION_NOISE = "Some donated buffers were not usable"

_LOCK = threading.RLock()
_PROGRAMS: "OrderedDict[Tuple, Callable]" = OrderedDict()
_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_SITE_STATS: dict = {}


def _maxsize() -> int:
    raw = knobs.raw("HEAT_TPU_PROGRAM_CACHE", "").strip()
    if raw:
        try:
            n = int(raw)
            if n > 0:
                return n
        except ValueError:
            pass
    return DEFAULT_MAXSIZE


def program_key(
    site: str,
    key: Any,
    comm: Any = None,
    donate: Sequence[int] = (),
) -> Tuple:
    """The full registry key for one program site — also the memo key the
    HLO auditor uses for the same program, so audited and cached programs
    share one signature. ``comm`` participates by identity (two
    communicators over the same devices are distinct meshes to XLA too);
    ``key`` is the caller's static config (shapes, dtypes, splits, flags —
    anything that changes the traced program).

    The tiered-lowering state (ISSUE 15: ``HEAT_TPU_HIERARCHICAL`` +
    topology + cross-tier precision) is appended HERE, once, for every
    site: any program built over the MeshCommunication wrappers changes
    shape under the knob, and threading the token through forty caller
    keys is exactly the drift this chokepoint exists to prevent. Flat
    (the default) contributes the constant ``("flat",)``."""
    return (site, comm, key, tuple(donate), _topology_token(comm))


def _topology_token(comm: Any) -> Tuple:
    """The ISSUE 15 cache-token component (see
    :func:`heat_tpu.core.topology.cache_token`); ``("flat",)`` whenever
    tiered lowering is off or unresolvable — the zero-overhead default
    is one knob read."""
    try:
        from . import topology

        p = getattr(comm, "size", None)
        if p is None:
            p = jax.device_count()
        return topology.cache_token(int(p))
    except Exception:  # never let key construction take dispatch down
        return ("flat",)


def cached_program(
    site: str,
    key: Any,
    build: Callable[[], Callable],
    *,
    comm: Any = None,
    out_shardings: Any = None,
    donate: Sequence[int] = (),
    static_argnums: Any = None,
    static_argnames: Any = None,
) -> Callable:
    """Return the memoized jitted program for ``(site, comm, key, donate)``,
    building and jitting it on first use.

    ``build()`` returns the plain python callable to compile — it runs only
    on a registry miss (and must therefore be cheap and side-effect free;
    no tracing happens until the returned program is called).
    ``out_shardings`` / ``static_argnums`` / ``static_argnames`` pass
    through to ``jax.jit``; ``donate`` becomes ``donate_argnums``. The
    returned wrapper handles aval-level dispatch itself, so callers key
    only on *static config* — two calls with the same key but different
    shapes share one registry entry and retrace inside it.

    This is the ONLY sanctioned ``jax.jit`` site in the framework
    (enforced by ``tests/test_no_stray_jit.py``).
    """
    donate = tuple(donate)
    full_key = program_key(site, key, comm=comm, donate=donate)
    if full_key not in _PROGRAMS and knobs.get("HEAT_TPU_AUTOTUNE"):
        # measured-feedback autotuner (ISSUE 11): a registry miss is the
        # cold path (a trace+compile follows), so the tuning-DB consult —
        # a memoized warm start that installs persisted winners into the
        # knob overlay — costs nothing in steady state. Runs OUTSIDE
        # _LOCK: the first warm start may scan an on-disk DB, and
        # holding the registry lock through that would stall concurrent
        # hit-path lookups. The lock-free probe can race a concurrent
        # insert into a false miss; that costs one memoized dict check.
        # Default-off, dispatch is bit-for-bit the untuned path: the hit
        # path pays one dict probe that short-circuits before the flag
        # read, no DB is touched, no new compiles.
        from .. import autotune as _autotune

        _autotune.on_program_miss(site)
    evicted = 0
    miss = False
    with _LOCK:
        fn = _PROGRAMS.get(full_key)
        srow = _SITE_STATS.setdefault(site, {"hits": 0, "misses": 0})
        if fn is not None:
            _PROGRAMS.move_to_end(full_key)
            _STATS["hits"] += 1
            srow["hits"] += 1
        else:
            miss = True
            _STATS["misses"] += 1
            srow["misses"] += 1
            jit_kwargs: dict = {"donate_argnums": donate}
            if out_shardings is not None:
                jit_kwargs["out_shardings"] = out_shardings
            if static_argnums is not None:
                jit_kwargs["static_argnums"] = static_argnums
            if static_argnames is not None:
                jit_kwargs["static_argnames"] = static_argnames
            fn = jax.jit(build(), **jit_kwargs)
            if donate:
                fn = _quiet_donation(fn)
            # resilience dispatch wrapper (ISSUE 5): disarmed it is one
            # flag check; armed, every execution of this program runs the
            # fault injector, the HBM preflight, and the transient-retry
            # guard. Wrapped ONCE here, so the hit path stays a dict
            # lookup returning the already-wrapped callable.
            fn = resilience.wrap_program(site, fn, donated=bool(donate))
            maxsize = _maxsize()
            while len(_PROGRAMS) >= maxsize:
                _PROGRAMS.popitem(last=False)
                _STATS["evictions"] += 1
                evicted += 1
            _PROGRAMS[full_key] = fn
    if telemetry.enabled():
        reg = telemetry.get_registry()
        if miss:
            reg.add("program_cache.misses", 1)
            reg.add(f"program_cache.retrace.{site}", 1)
            # instant event → the Chrome trace's *events* track: when and
            # where a retrace happened (the expensive path)
            reg.emit("program_cache", site, event="retrace", key=repr(key))
        else:
            reg.add("program_cache.hits", 1)
        if evicted:
            reg.add("program_cache.evictions", evicted)
            reg.emit("program_cache", site, event="eviction", count=evicted)
    return fn


def _quiet_donation(jitted: Callable) -> Callable:
    """Wrap a donating jitted program so the lowering-time "donated
    buffers were not usable" warning is suppressed for ITS calls only.
    ``lower`` is forwarded so the HLO auditor can still AOT-compile the
    wrapped program."""

    def call(*args, **kwargs):
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message=_DONATION_NOISE)
            return jitted(*args, **kwargs)

    call.lower = jitted.lower
    return call


def stats() -> dict:
    """Snapshot of the registry counters:
    ``{"hits", "misses", "evictions", "size", "maxsize", "sites"}`` with
    per-site hit/miss (retrace) counts under ``sites``."""
    with _LOCK:
        return {
            "hits": _STATS["hits"],
            "misses": _STATS["misses"],
            "evictions": _STATS["evictions"],
            "size": len(_PROGRAMS),
            "maxsize": _maxsize(),
            "sites": {s: dict(row) for s, row in _SITE_STATS.items()},
        }


def site_stats(prefix: str) -> dict:
    """Aggregated ``{"hits", "misses"}`` over every site whose name
    starts with ``prefix`` — e.g. ``site_stats("serve.")`` is the
    serving front end's zero-recompile-after-warmup oracle (a steady
    state shows only the hit counter moving)."""
    with _LOCK:
        out = {"hits": 0, "misses": 0}
        for s, row in _SITE_STATS.items():
            if s.startswith(prefix):
                out["hits"] += row["hits"]
                out["misses"] += row["misses"]
        return out


def reset() -> None:
    """Drop every cached program and zero the counters (tests)."""
    with _LOCK:
        _PROGRAMS.clear()
        _STATS.update(hits=0, misses=0, evictions=0)
        _SITE_STATS.clear()


clear = reset


# -- persistent (cross-process) XLA compilation cache -------------------------

_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> str:
    """Turn on JAX's on-disk compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    function sets no directory. Unset: ``<checkout>/.jax_cache``. Either
    way the two entry thresholds drop to 0 so every executable is
    eligible — the suites and the smoke are dominated by many *small*
    compiles, exactly the entries the default 1-second threshold skips.
    Idempotent."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
