"""pinot_tpu — a TPU-native real-time distributed OLAP framework.

Brand-new design with the capabilities of Apache Pinot (reference:
/root/reference, pure JVM), rebuilt TPU-first on JAX/XLA/Pallas/pjit:

- columnar immutable/mutable segments with sorted dictionary encoding
  (reference: pinot-segment-local SegmentIndexCreationDriverImpl)
- per-segment query kernels: predicate masks -> projection gathers ->
  masked aggregations / segment_sum group-by (reference: pinot-core
  DocIdSetOperator / ProjectionOperator / AggregationOperator /
  DefaultGroupByExecutor)
- SQL subset compiler + physical planner with fast paths & pruning
  (reference: CalciteSqlParser + InstancePlanMakerImplV2)
- in-process broker scatter-gather + reduce (reference:
  BrokerReduceService), scaling out via jax.sharding Mesh + shard_map
  with psum combine over ICI instead of Netty scatter-gather.

OLAP needs exact 64-bit arithmetic (long counts, double sums — Pinot
returns double for SUM over any numeric column). We therefore enable
jax x64 at import, and with it on every float aggregate accumulates in
float64 on every backend (pinot_tpu.ops.kernels.float_acc_dtype, the one
rule). Where float64 is emulated (XLA:TPU: a pair of float32, 48 bits)
the sums are blocked, and a SUM or AVG over 2^26 rows is within 1e-12
relative of the exact decimal value; the launches that still pass
through float32 (the compact group-by's payloads there) count
float_acc_narrow.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compile cache, placed from outside: when the environment
# names a directory JAX reads it itself and nothing is set here.
# Otherwise the cache lives at a FIXED path inside the checkout (the path
# is part of how a later process finds the entries again — never a temp
# dir, pid or timestamp). This is the only place that sets it.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))

__version__ = "0.1.0"
