"""``repro.dist`` — the OTA collective substrate for the production mesh.

Three layers, mirroring the paper's offline/online split:

* :mod:`repro.dist.sharding_rules` — mesh-shape-aware PartitionSpec
  inference (FSDP/BATCH axis aliases, divisibility-fitted specs) for every
  parameter/batch/cache leaf of the assigned architectures.
* :mod:`repro.dist.fl_integration` — the offline FL plan (clustering,
  water-filled β, channel-noise budget) and the paper-faithful hierarchical
  OTA all-reduce usable inside ``jax.shard_map`` over the ``data`` axis.
* :mod:`repro.dist.ota_collectives` — flat-vector lowerings of the CWFL
  aggregation that reuse :mod:`repro.core.channel` math verbatim and route
  the phase-1 MAC through the Pallas ``ota_aggregate`` kernel when shapes
  allow.
"""
from __future__ import annotations

from repro.dist import fl_integration, ota_collectives, sharding_rules  # noqa: F401
from repro.dist.fl_integration import (FLPlan, hierarchical_ota_allreduce,  # noqa: F401
                                       make_fl_plan)
