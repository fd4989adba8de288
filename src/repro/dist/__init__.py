"""repro.dist — the unified aggregation / sharding layer.

One coherent API over the paper's Algorithm 1 and its generalizations
(stale rule (15), CGE filter eq. (18)) for both execution substrates:

- ``repro.dist.registry``      named ``AggregationRule`` strategy objects
  bundling, per rule, the numpy/jnp reference (``repro.core.gradagg``)
  and the shard_map-side SPMD collective (``repro.dist.collectives``).
  ``EngineConfig.rule`` and ``TrainConfig.mode`` both resolve here.
- ``repro.dist.collectives``   SPMD twins of the reference rules.
- ``repro.dist.sharding``      logical-axis -> mesh-axis resolution
  (``MeshRules``) plus tree/batch/cache PartitionSpec derivation.
- ``repro.dist.act_sharding``  in-graph activation sharding constraints
  (``constrain`` / ``act_policy``) used by all model files.

See DESIGN.md §1-§3 for the layer contract.
"""
from repro.dist import registry  # noqa: F401  (re-export the dispatch surface)
from repro.dist.registry import AggregationRule, get_rule, rule_names  # noqa: F401
