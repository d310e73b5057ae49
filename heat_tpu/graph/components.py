"""Connected components via iterated label-propagation spmv (ISSUE 13).

The classic min-label relay: every vertex starts as its own label (its
index) and repeatedly adopts the minimum label among its neighbours.
Each relaxation round is ONE structure-only sparse matvec —
:func:`heat_tpu.sparse.spmv` with ``reduce='min'``/``pattern=True``, the
shard-local CSR segment-min plus the (never-compressed) pmin tail — so
the whole algorithm dispatches the same cached program per round, zero
steady-state recompiles, and converges in at most the graph diameter
rounds (the host checks the fixed point between rounds; labels are a
small replicated int vector, exactly the centroid-read pattern)."""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..core import types
from ..core.dndarray import DNDarray

__all__ = ["connected_components"]


def connected_components(
    A,
    *,
    assume_symmetric: bool = False,
    max_iter: Optional[int] = None,
) -> DNDarray:
    """Component labels of the graph whose edges are ``A``'s stored
    entries (values are ignored — structure-only propagation).

    ``A`` is a :class:`~heat_tpu.sparse.SparseDNDarray` (a dense square
    DNDarray is compacted first). Undirected semantics: unless
    ``assume_symmetric=True``, the transpose pattern joins each round so
    one-directional stored edges still merge their endpoints (the
    transpose is the audited all-to-all slab exchange, paid once).
    Returns the ``(n,)`` int64 replicated label vector — two vertices
    share a component iff they share a label; labels are each
    component's minimum vertex index."""
    from .. import sparse as htsparse

    if isinstance(A, DNDarray):
        A = htsparse.csr_from_dense(A)
    if not isinstance(A, htsparse.SparseDNDarray):
        raise TypeError(
            f"expected a SparseDNDarray (or dense DNDarray), got {type(A)}"
        )
    n, n2 = A.shape
    if n != n2:
        raise ValueError(f"adjacency must be square, got {A.shape}")
    from ..core import factories

    At = None if assume_symmetric else A.transpose()
    labels = factories.array(
        np.arange(n, dtype=np.int64), device=A.device, comm=A.comm
    )
    limit = n if max_iter is None else int(max_iter)
    rounds = 0
    prev = labels.numpy()
    for _ in range(max(1, limit)):
        cand = htsparse.spmv(
            A, labels, reduce="min", pattern=True, out_split=None
        )
        new_log = jnp.minimum(labels.larray, cand.larray)
        if At is not None:
            cand_t = htsparse.spmv(
                At, labels, reduce="min", pattern=True, out_split=None
            )
            new_log = jnp.minimum(new_log, cand_t.larray)
        rounds += 1
        cur = np.asarray(new_log)
        labels = DNDarray(
            new_log, (n,), types.int64, None, A.device, A.comm, True
        )
        if np.array_equal(cur, prev):
            break
        prev = cur
    if telemetry.enabled():
        reg = telemetry.get_registry()
        reg.add("sparse.components", 1)
        reg.emit(
            "sparse", "components", event="components", rows=n,
            rounds=rounds,
            n_components=int(np.unique(prev).shape[0]),
        )
    return labels
