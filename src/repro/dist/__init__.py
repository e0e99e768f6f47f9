"""Distributed runtime: mesh axis conventions, sharding constraints and
parameter partitioning.

Two modules:

* :mod:`repro.dist.sharding`       — axis-name constants (``BATCH``/``MODEL``),
  the ``shard`` constraint helper, the ``use_mesh`` context manager and the
  ``resolve_spec`` resolve-or-replicate spec resolver.
* :mod:`repro.dist.param_sharding` — ``param_specs``: walk a parameter pytree
  and assign a ``NamedSharding`` per leaf (TP over 'model', optional FSDP
  over 'data', EP for expert weights, replication for small vectors).
* :mod:`repro.dist.partition`      — graph-partitioned multi-host execution:
  the metapath-aware edge-cut partitioner (per-type vertex assignment,
  halo/ghost-vertex index maps, per-partition relabeling) and the
  ``gather_halo`` feature exchange (shard_map over the BATCH axes).
  Imported lazily by the executor.
"""
from repro.dist.sharding import (  # noqa: F401
    BATCH,
    DATA,
    MODEL,
    POD,
    current_mesh,
    resolve_spec,
    shard,
    use_mesh,
)
from repro.dist.param_sharding import param_specs  # noqa: F401
