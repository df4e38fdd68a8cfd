"""One shared vocabulary for phase, span, kernel-family and scope names.

The flat ``OPTION(trace=true)`` envelope (utils/trace.py), the span
TREE that EXPLAIN ANALYZE renders and the always-on phase counters (both
utils/spans.py) time the same code regions, and before round 10 each
site named its region with its own string literal. The vocabularies
agreed only by luck; one drifted rename would have made the envelope and
the analyze rows disagree about what "planning" means. Every
instrumentation site now imports its name from here, and
tests/test_span_tracer.py pins envelope keys == span names for the
shared phases.

The cluster plane (round 10) extends the set: the broker roots a
``query`` span, each scatter-gather is a ``scatter`` span whose
``scatter_call`` children are the per-server attempts (primary /
failover / hedge), and each server activates a remote-rooted
``server_query`` tree that the broker stitches under the call span that
dispatched it.
"""
from __future__ import annotations

# broker/engine phases (envelope keys AND span names — must stay one set)
QUERY = "query"
PLANNING = "planning"
EXECUTION = "execution"
REDUCE = "reduce"
DISTRIBUTED_EXECUTE = "distributed_execute"
BROKER_OVERHEAD = "broker_overhead"

# cluster plane span names (span-tree only: the flat envelope has no
# cross-process children to hang them on)
SCATTER = "scatter"
SCATTER_CALL = "scatter_call"
SERVER_QUERY = "server_query"

# multistage plane (round 12): stage spans inside the QUERY tree so
# EXPLAIN ANALYZE and sampled traces cover shuffle-join/window/set-op
# queries, plus the networked dispatch plane's per-submission spans
# (multistage/dispatch.py — the scatter_call/server_query analogs)
LEAF_SCAN = "leaf_scan"
JOIN_STAGE = "join_stage"
EXCHANGE = "exchange"
WINDOW_STAGE = "window_stage"
FINAL_STAGE = "final_stage"
STAGE = "stage"                    # remote /stage worker-rooted tree
STAGE_CALL = "stage_call"          # driver-side per-submission attempt
STAGE_DISPATCH = "stage_dispatch"  # driver-side fan-out parent

# whole-plan mesh compilation (round 16): when every stage worker
# shares one mesh, the join pipeline compiles into ONE shard_map
# program (multistage/fused.py) and the mailbox spans above disappear —
# fused_plan is their replacement parent (leaf scans, the staged
# compile/execute, and the canonical-order gather are its children) and
# collective_exchange attributes each in-program stage boundary
# (hash -> all_to_all, broadcast -> replication) so EXPLAIN ANALYZE and
# the span-diff gate keep per-stage self-times when the plan fuses
FUSED_PLAN = "fused_plan"
COLLECTIVE_EXCHANGE = "collective_exchange"

# cross-query micro-batching (PR 8): every query that passes through the
# ragged admission queue wraps its wait + fused dispatch in ONE
# ragged_dispatch span on its own thread (queue_wait_ms annotated), so
# per-query wall attribution survives the fusion; the leader's span
# additionally parents the cube_build/fused_execute children.
RAGGED_DISPATCH = "ragged_dispatch"
CUBE_BUILD = "cube_build"
FUSED_EXECUTE = "fused_execute"    # metered too (PR 33): the leader's launch
# the time a query spends in the admission window (the leader, in
# MicroBatchQueue.offer) or waiting for its leader's answer (a follower)
RAGGED_WAIT = "ragged_wait"

# vector search subsystem (engine/vector_exec.py): one span per
# (query, segment) device search — batched or solo annotated on it
VECTOR_SEARCH = "vector_search"

# layer boundaries of the served path, in the order a query crosses them
# (PERF.md section 3 has the table). Each is entered through
# utils/spans.phase, which always feeds the counters phase_us_<name> /
# phase_n_<name>, writes a "pinot.<name>" event into a running profiler
# session and builds the tree node when the query is sampled. The
# device_execute / device_transfer / extract_partial names predate the
# counters as plain span names and keep their place in the tree.
BROKER_QUERY = "broker_query"        # HTTP handler: body parsed -> written
BROKER_PARSE = "broker_parse"        # parse_sql, options, admission
BROKER_ROUTE = "broker_route"        # routing snapshot, quota, context
BROKER_SELECT = "broker_select"      # segment pruning, replica choice
WIRE_DECODE = "wire_decode"          # response frame -> partials
BROKER_RESPOND = "broker_respond"    # to_dict, JSON encode, write
SERVER_HTTP = "server_http"          # HTTP handler: body parsed -> written
SERVER_QUEUE = "server_queue"        # arrival -> scheduler worker starts
SERVER_PARSE = "server_parse"        # parse_sql, deadline, context, acquire
PARAMS_HOST = "params_host"          # plans' host params and group keys
DISPATCH_PREPARE = "dispatch_prepare"  # stacks, params: host work pre-launch
DEVICE_EXECUTE = "device_execute"    # the dispatch call (fenced if sampled)
DEVICE_TRANSFER = "device_transfer"  # jax.device_get: wait + copy back
EXTRACT_PARTIAL = "extract_partial"  # host numpy -> mergeable partials
SERVER_ENCODE = "server_encode"      # partials -> DataBlock frame

# names that may appear in the flat trace envelope
TRACED_PHASES = frozenset(
    {PLANNING, EXECUTION, REDUCE, DISTRIBUTED_EXECUTE})

# every name above (the span tree uses these, the metered boundary names
# below, and dynamic kernel-level names like segment_kernel owned by
# their emit sites)
SPAN_NAMES = TRACED_PHASES | frozenset(
    {QUERY, BROKER_OVERHEAD, SCATTER, SCATTER_CALL, SERVER_QUERY,
     LEAF_SCAN, JOIN_STAGE, EXCHANGE, WINDOW_STAGE, FINAL_STAGE,
     FUSED_PLAN, COLLECTIVE_EXCHANGE,
     STAGE, STAGE_CALL, STAGE_DISPATCH,
     RAGGED_DISPATCH, CUBE_BUILD, FUSED_EXECUTE, RAGGED_WAIT})

# every name utils/spans.phase accepts (anything else is a KeyError at
# the call site: a metered boundary is named here or not at all)
METERED_PHASES = TRACED_PHASES | frozenset(
    {BROKER_QUERY, BROKER_PARSE, BROKER_ROUTE, BROKER_SELECT, SCATTER,
     SCATTER_CALL, WIRE_DECODE, BROKER_RESPOND, SERVER_HTTP, SERVER_QUEUE,
     SERVER_PARSE, PARAMS_HOST, DISPATCH_PREPARE, DEVICE_EXECUTE,
     DEVICE_TRANSFER, EXTRACT_PARTIAL, SERVER_ENCODE, RAGGED_WAIT,
     FUSED_EXECUTE})

# the metered leaves that are pure host work, with no device wait inside
# them: what one of these spends off its thread's CPU (``phase_us_<p>``
# less ``phase_cpu_us_<p>``) is time the thread waited for the
# interpreter lock or for a CPU the host did not give
# (benchmark/metrics/host_offcpu_ms_per_query.json lists the same names).
# On the chip hosts the thread CPU clock moves in 10 ms ticks booked at
# the next system call, and it is read in one nest in eight, so only the
# leaves of a millisecond or more a request can be read: the six here
# never read more CPU than wall time on the chip (PERF.md, PR 37).
# ``broker_respond`` (a socket write) and ``dispatch_prepare`` (a copy to
# the device) read up to 143 %, ``broker_route``, ``broker_select``,
# ``server_parse`` and ``params_host`` (under half a millisecond) up to
# 194 %: they are leaves too, and count in host_cpu_ms_per_query alone
HOST_WORK_PHASES = (
    BROKER_PARSE, REDUCE, WIRE_DECODE, PLANNING, EXTRACT_PARTIAL,
    SERVER_ENCODE)

# kernel families: the jitted function of each is named
# "pinot_<family>" (utils/compileplane.kernel_jit), so the profiler's
# XLA Modules line reads jit_pinot_<family>(...), and every launch counts
# kernel_dispatches_<family> (utils/spans.count_dispatch)
DENSE_VMAP = "dense_vmap"                    # engine/batch: S segments vmapped
DENSE_PER_SEGMENT = "dense_per_segment"      # plan cache, one segment
COMPACT_SEGMENTED = "compact_segmented"      # one program over S segments
COMPACT_PER_SEGMENT = "compact_per_segment"  # plan cache, one segment
SELECT_TOPK = "select"                       # selection ORDER BY/LIMIT
RAGGED_FUSED = "ragged_fused"                # cross-query cube combine
CUBE_BUILD_KERNEL = "cube_build"             # micro-batcher's cube scan
# parallel/distributed.py: one shard_map program a query over a mesh
MESH_DENSE = "mesh_dense"                    # local segments vmapped
MESH_COMPACT = "mesh_compact"                # the local shard flattened
MESH_COMPACT_PER_SEGMENT = "mesh_compact_per_segment"  # routed sort core
# the full-scan group-by (strategy 'scan'): S segments vmapped, or one
# segment through the plan cache
GROUP_SCAN = "group_scan"
KERNEL_FAMILIES = frozenset(
    {DENSE_VMAP, DENSE_PER_SEGMENT, COMPACT_SEGMENTED, COMPACT_PER_SEGMENT,
     SELECT_TOPK, RAGGED_FUSED, CUBE_BUILD_KERNEL, MESH_DENSE, MESH_COMPACT,
     MESH_COMPACT_PER_SEGMENT, GROUP_SCAN})
MODULE_PREFIX = "pinot_"


def plan_family(plan) -> str:
    """Family of a single-segment kernel built from ``plan``."""
    strategy = getattr(plan, "strategy", None)
    if strategy == "compact":
        return COMPACT_PER_SEGMENT
    return GROUP_SCAN if strategy == "scan" else DENSE_PER_SEGMENT


# jax.named_scope names of the stages inside the kernels (HLO metadata
# only: they ride an operation's op_name, never its numerics)
SCOPE_MASK = "pinot.mask"                # row validity & predicate
SCOPE_DECODE_DICT = "pinot.decode_dict"  # dict id -> value (select | gather)
SCOPE_GROUP_KEY = "pinot.group_key"      # cartesian key + sentinel
SCOPE_PAYLOAD = "pinot.payload"          # aggregation inputs, pre-compaction
SCOPE_COMPACT = "pinot.compact"          # ops/compact.compact
SCOPE_AGGREGATE = "pinot.aggregate"      # scalar, one-hot, sorted, scatter
SCOPE_FLOAT_ACC = "pinot.float_acc"      # wide float sums, inside aggregate
SCOPE_GROUP_TAIL = "pinot.group_tail"    # sparse sorted post, per live group
SCOPE_GROUP_SCAN = "pinot.group_scan"    # full-scan group-by, every row
SCOPE_XFER_COMPACT = "pinot.xfer_compact"  # live-group gather pre-transfer
SCOPE_TOPK = "pinot.topk"                # selection order key + top_k
SCOPE_COMBINE = "pinot.combine"          # the mesh's per-device combine
# the micro-batcher's two programs (engine/ragged.py)
SCOPE_CUBE_BUILD = "pinot.cube_build"      # unmasked scan -> literal-free cube
SCOPE_CUBE_COMBINE = "pinot.cube_combine"  # per-item mask + cell reduction
KERNEL_SCOPES = frozenset(
    {SCOPE_MASK, SCOPE_DECODE_DICT, SCOPE_GROUP_KEY, SCOPE_PAYLOAD,
     SCOPE_COMPACT, SCOPE_AGGREGATE, SCOPE_FLOAT_ACC, SCOPE_GROUP_TAIL,
     SCOPE_GROUP_SCAN, SCOPE_XFER_COMPACT, SCOPE_TOPK, SCOPE_COMBINE,
     SCOPE_CUBE_BUILD, SCOPE_CUBE_COMBINE})
