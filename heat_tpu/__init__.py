"""heat_tpu — a TPU-native distributed n-dimensional array framework.

Ground-up re-design of the Heat (Helmholtz Analytics Toolkit) capability set
(reference: /root/reference, heat/__init__.py:5-19) for the JAX/XLA stack:
arrays are sharded `jax.Array`s over a `jax.sharding.Mesh`, collectives ride
ICI/DCN via XLA instead of MPI, local math runs on the MXU instead of torch.

Importing enables 64-bit dtypes (`jax_enable_x64`) so the numpy-compatible
dtype surface (int64/float64 defaults) matches the reference; TPU compute
paths default to float32/bfloat16 regardless.
"""

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# telemetry first: it is import-light (no core dependency) and the core
# modules' instrumentation hooks reference it; HEAT_TPU_TELEMETRY=1 in the
# environment turns recording on here (docs/OBSERVABILITY.md)
from . import telemetry

# resilience second: program_cache wraps every dispatch through it, so it
# must exist before core loads; HEAT_TPU_FAULTS / HEAT_TPU_RETRIES /
# HEAT_TPU_HBM_BUDGET arm it here (docs/RESILIENCE.md). Core-facing pieces
# (checkpoint) import core lazily to keep the load order acyclic.
from . import resilience

from .core import *
from . import core
from .core import linalg, program_cache, random, version
from .core.ragged import Ragged, ragged
from .core.version import version as __version__

# sparse container + audited SpMV/SpMM (ISSUE 13): mounts right after
# core (it consumes program_cache/telemetry/memory_guard) and before the
# ML subpackages (graph/cluster/serve route workloads through it)
from . import sparse

# ML subpackages (assembled as they are built; reference heat/__init__.py
# mounts cluster/classification/graph/naive_bayes/regression/spatial/nn/
# optim/utils the same way)
from . import cluster
from . import classification
from . import graph
from . import naive_bayes
from . import regression
from . import spatial
from . import utils
from . import parallel
from . import datasets
from . import nn
from . import optim
from . import serve

# streaming (ISSUE 16) mounts after the estimators and the serving tier
# it composes: online partial_fit estimators, out-of-core ChunkStream
# ingestion, and the versioned fit-while-serve rolling-update driver
from . import streaming

# the measured-feedback knob autotuner (ISSUE 11) mounts last: it
# consumes the substrate (knobs registry, telemetry, cost model, program
# cache) and is consulted from dispatch sites only behind the
# HEAT_TPU_AUTOTUNE flag check
from . import autotune
