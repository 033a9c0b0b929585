"""Mesh factories.

FUNCTIONS (not module-level constants) so importing this module never
touches jax device state — jax locks the device count on first backend
init, and the 512-device placeholder flag must be set before that
(launch/dryrun.py sets it as its very first lines).

Every mesh the repo builds comes from :func:`make_mesh`, whose axes are
typed ``Auto``: the SPMD code here places arrays with explicit
``NamedSharding``s and ``shard_map`` specs and leaves the rest to the
partitioner.  ``jax.make_mesh``'s own default (``Explicit`` axes) would
instead make every traced reshape of a sharded array demand an
``out_sharding``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """Any (shape, axes) mesh with Auto axis types — checkpoint restore
    re-shards between meshes built here (see repro.checkpoint)."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes, axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods when multi_pod.

    Axes: ("data", "model") single pod, ("pod", "data", "model") multi-pod.
    "data" = DP/FSDP, "model" = TP/EP/sequence-parallel-KV, "pod" = cross-pod
    DP (grad-reduce only crosses pods — see repro.distributed.sharding).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_serving_mesh(data: int, items: int):
    """The retrieval-serving mesh: ``data`` shards the query batch (data-
    parallel request rows), ``items`` shards the AnchorIndex payload and the
    engine's per-shard item slabs (see ``repro.core.engine``'s SPMD engine).
    ``data * items`` must equal the visible device count."""
    return make_mesh((data, items), ("data", "items"))
