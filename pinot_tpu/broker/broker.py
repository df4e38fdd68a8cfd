"""Broker: SQL in, ResultTable out — compile, route, scatter, reduce.

Reference parity: pinot-broker/.../requesthandler/
BaseSingleStageBrokerRequestHandler.java (compile :256, optimize :492-521,
route :560-577) + SingleConnectionBrokerRequestHandler.java:141-151
(scatter-gather + reduce) + BrokerRequestHandlerDelegate (engine pick) +
query options (QueryOptionsUtils: timeoutMs, trace, skipUpsert) + EXPLAIN.
In-process execution over local TableDataManagers; the HTTP cluster roles
(cluster/broker_node.py) reuse the same reduce over remote partials, and
ICI collectives (parallel/distributed.py) replace the Netty data plane for
mesh-resident tables.
"""
from __future__ import annotations

import time
import uuid
from typing import Any, Dict, List, Optional

from ..engine.executor import execute_plan
from ..engine.reduce import ResultTable, reduce_partials
from ..engine.setops import combine_setop, order_limit_rows
from ..query.context import build_query_context
from ..query.planner import SegmentPlanner, _truthy
from ..query.sql import (Comparison, CteDef, DdlStmt, Exists, InList,
                         InSubquery, Literal, ScalarSubquery, SelectStmt,
                         SetOpStmt, SqlError, map_expr, parse_sql)
from ..server.data_manager import TableDataManager
from ..utils import phases as ph
from ..utils.metrics import global_metrics
from ..utils.trace import Tracing

DEFAULT_TIMEOUT_MS = 10_000


def _cte_table(name: str, columns: List[str], rows: List[tuple],
               tmpdirs: List[str]) -> TableDataManager:
    """Materialize a CTE result as a single-segment table. Types are
    inferred per column (all-int -> LONG, numeric -> DOUBLE, else
    STRING); an empty result registers a segment-less manager."""
    import tempfile

    import numpy as np

    from ..segment import SegmentBuilder
    from ..spi import DataType, FieldSpec, FieldType, Schema, TableConfig

    dm = TableDataManager(name)
    if not rows:
        return dm
    cols: Dict[str, Any] = {}
    fields: List[FieldSpec] = []
    for j, cname in enumerate(columns):
        vals = [r[j] for r in rows]
        if any(v is None for v in vals):
            raise SqlError(f"CTE {name!r} column {cname!r} produced NULL "
                           "values; filter them in the CTE query")
        if all(isinstance(v, (int, np.integer))
               and not isinstance(v, (bool, np.bool_)) for v in vals):
            cols[cname] = np.asarray(vals, dtype=np.int64)
            dt = DataType.LONG
        elif all(isinstance(v, (int, float, np.integer, np.floating))
                 and not isinstance(v, (bool, np.bool_)) for v in vals):
            cols[cname] = np.asarray(vals, dtype=np.float64)
            dt = DataType.DOUBLE
        else:
            cols[cname] = np.asarray([str(v) for v in vals])
            dt = DataType.STRING
        fields.append(FieldSpec(cname, dt, FieldType.DIMENSION))
    out = tempfile.mkdtemp(prefix="ptpu_cte_")
    tmpdirs.append(out)
    seg_dir = SegmentBuilder(Schema(name, fields),
                             TableConfig(name)).build(cols, out, "cte_0")
    dm.add_segment_dir(seg_dir)
    return dm


class QueryTimeoutError(SqlError):
    pass


class Broker:
    def __init__(self, trace_ratio: Optional[float] = None,
                 trace_ledger_path: Optional[str] = None,
                 micro_batch: Optional[bool] = None,
                 micro_batch_window_ms: Optional[float] = None):
        from .quota import QueryQuotaManager
        self._tables: Dict[str, TableDataManager] = {}
        # cross-query micro-batching (PR 8): concurrent queries sharing
        # a plan structure fuse into one ragged device dispatch
        # (engine/ragged.py). The dispatcher is engine-global (fusion
        # happens below the broker), so the flag configures the shared
        # batcher; None leaves the PINOT_MICROBATCH env default alone.
        if micro_batch is not None or micro_batch_window_ms is not None:
            from ..engine.ragged import global_batcher
            global_batcher.configure(enabled=micro_batch,
                                     window_ms=micro_batch_window_ms)
        # name -> view body statement (CREATE VIEW ... AS <select>);
        # expanded into CTEs at reference time (_expand_views)
        self._views: Dict[str, Any] = {}
        self.quota = QueryQuotaManager()
        # overload protection (ISSUE 12, broker/workload.py): per-tenant
        # budgets + the watermark degradation ladder. Process-global
        # like the accountant — tenant isolation is a per-process
        # property, and in-process clusters run several broker roles in
        # one interpreter
        from .workload import global_workload
        self.workload = global_workload
        # traceRatio production sampling (round 12): constructor wins,
        # then PINOT_TRACE_RATIO, then off (the shared
        # forensics.default_trace_ratio chain). OPTION(traceRatio=...)
        # overrides per query; sampled queries land validated
        # query_trace ledger records without EXPLAIN ANALYZE.
        from ..cluster.forensics import default_trace_ratio
        self._trace_ratio = default_trace_ratio(trace_ratio)
        self._trace_ledger_path = trace_ledger_path
        # compile-plane forensics (ISSUE 15): a broker with a trace
        # ledger and no explicit PINOT_COMPILE_LEDGER lands compile
        # events in the same file, so span_diff captures double as
        # warmup-debt corpora (tools/warmup_report.py --gate)
        if trace_ledger_path:
            from ..utils.compileplane import global_compile_log
            global_compile_log.configure_path_if_unset(trace_ledger_path)

    # -- table registry (ideal-state analog) -------------------------------
    def register_table(self, dm: TableDataManager) -> None:
        self._tables[dm.table_name] = dm
        cfg = getattr(dm, "table_config", None)
        if cfg is not None and getattr(cfg, "quota_qps", None):
            self.quota.set_quota(dm.table_name, cfg.quota_qps)
        if cfg is not None:
            # workload tenant from the TableConfig tenant field; tables
            # without one charge the default tenant
            self.workload.set_table_tenant(
                dm.table_name, getattr(cfg, "tenant", None))

    def table(self, name: str) -> TableDataManager:
        if name not in self._tables:
            raise SqlError(f"table {name!r} not found; "
                           f"have {list(self._tables)}")
        return self._tables[name]

    @property
    def table_names(self) -> List[str]:
        return list(self._tables)

    # -- query path --------------------------------------------------------
    def query(self, sql: str) -> ResultTable:
        global_metrics.count("broker_queries")
        try:
            return self._query(sql)
        except SqlError:
            global_metrics.count("broker_query_exceptions")
            raise

    def _query(self, sql: str) -> ResultTable:
        t0 = time.perf_counter()
        stmt = parse_sql(sql)
        if isinstance(stmt, DdlStmt):
            return self._execute_ddl(stmt, t0)
        stmt._raw_sql = sql  # for the EXPLAIN ANALYZE ledger record
        opts = getattr(stmt, "options", {}) or {}
        # OPTION(queryId=...) lets replicas/retries of the same logical
        # query agree on the sampling AND shed decisions; otherwise a
        # fresh uuid draws independently per broker
        qid = str(opts.get("queryId") or uuid.uuid4().hex[:12])[:64]
        # overload admission (broker/workload.py), once per USER query —
        # nested CTE/subquery/set-op statements recurse through
        # _execute_stmt under this ticket. Plan-only EXPLAIN never
        # admits (no execution to protect); EXPLAIN ANALYZE does.
        # A shed raises the 429-shaped OverloadShedError here, before
        # any planning/dispatch work.
        from .workload import (clamp_brownout, leaf_table,
                               parse_retry_attempt)
        ticket = None
        if not getattr(stmt, "explain", False) or \
                getattr(stmt, "analyze", False):
            ticket = self.workload.admit(
                qid, leaf_table(stmt),
                retry_attempt=parse_retry_attempt(opts))
            if ticket.brownout:
                # rung-3 brownout: clamp to the floor deadline and
                # force partial-result semantics — degraded answers
                # beat a metastable queue
                clamp_brownout(stmt.options, DEFAULT_TIMEOUT_MS)
        try:
            # traceRatio production sampling: plan-only (EXPLAIN) and
            # analyze statements never sample; the decision is
            # deterministic in the query id (utils/spans.
            # sample_decision) and costs nothing when unsampled. Rung
            # >= 1 sheds this speculative work entirely.
            if not getattr(stmt, "analyze", False) and \
                    not getattr(stmt, "explain", False) and \
                    not (ticket is not None and ticket.degraded):
                from ..cluster.forensics import parse_trace_ratio
                ratio = parse_trace_ratio(opts, self._trace_ratio)
                if ratio > 0:
                    from ..utils.spans import sample_decision
                    if sample_decision(qid, ratio):
                        return self._execute_sampled(stmt, sql, t0, qid)
            return self._execute_stmt(stmt, t0)
        finally:
            self.workload.release(ticket)

    def _execute_sampled(self, stmt, sql: str, t0: float,
                         qid: str) -> ResultTable:
        """A traceRatio-sampled production query: execute under the span
        tracer (the EXPLAIN ANALYZE machinery, minus the rendered rows)
        and append a validated ``query_trace`` ledger record cross-linked
        by qid. Subqueries/CTEs/set-op branches recurse through
        _execute_stmt, so the whole statement lands in ONE tree."""
        from ..utils.spans import span_tracer
        root = span_tracer.start(ph.QUERY,
                                 table=getattr(stmt, "table", None),
                                 query_id=qid, sampled=True)
        try:
            try:
                result = self._execute_stmt(stmt, t0)
            finally:
                root = span_tracer.stop() or root
        except SqlError as e:
            # a failed sampled query still lands its (partial) tree —
            # those are exactly the spans forensics wants
            root.annotate(error=str(e)[:200])
            self._append_trace(root, stmt, sql, qid)
            raise
        root.annotate(rows=len(result.rows))
        self._append_trace(root, stmt, sql, qid)
        return result

    def _append_trace(self, root, stmt, sql: str, qid: str) -> None:
        global_metrics.count("sampled_traces")
        import os

        from ..utils import ledger as uledger
        # explicit-ledger-only, like QueryForensics.record_trace: no
        # configured path means the trace is counted but not persisted —
        # an implicit write to the default capture log would pollute it
        # (and the span-diff gate reading it) with traces from whatever
        # code version happens to be running
        path = (getattr(stmt, "options", {}).get("ledgerPath")
                or self._trace_ledger_path
                or os.environ.get("PINOT_TPU_LEDGER_PATH"))
        if not path:
            return
        try:
            uledger.append_record(
                uledger.trace_record(root, sql, qid=qid, sampled=True),
                path)
        except OSError:
            # observability must never fail the data path
            global_metrics.count("query_trace_write_errors")

    # -- views (QueryEnvironment view catalog analog) ----------------------
    def _execute_ddl(self, stmt: DdlStmt, t0: float) -> ResultTable:
        if stmt.kind == "create_view":
            if stmt.name in self._tables or self._is_hybrid(stmt.name):
                raise SqlError(
                    f"cannot create view {stmt.name!r}: a table with "
                    "that name exists")
            if stmt.name in self._views and not stmt.or_replace:
                raise SqlError(
                    f"view {stmt.name!r} already exists; use CREATE OR "
                    "REPLACE VIEW")
            self._views[stmt.name] = stmt.stmt
            status = "CREATED"
        else:
            if stmt.name not in self._views:
                if stmt.if_exists:
                    status = "NOT_FOUND"
                else:
                    raise SqlError(f"view {stmt.name!r} not found; "
                                   f"have {sorted(self._views)}")
            else:
                del self._views[stmt.name]
                status = "DROPPED"
        result = ResultTable(["view", "status"], [(stmt.name, status)])
        result.time_ms = (time.perf_counter() - t0) * 1e3
        return result

    @property
    def view_names(self) -> List[str]:
        return sorted(self._views)

    def _referenced_tables(self, stmt, out: set) -> None:
        """Every table name a statement tree references (main, joins,
        set-op branches, subqueries, CTE bodies)."""
        from ..query.sql import ast_children

        if isinstance(stmt, SetOpStmt):
            self._referenced_tables(stmt.left, out)
            self._referenced_tables(stmt.right, out)
            return
        out.add(stmt.table)
        for j in stmt.joins:
            out.add(j.table.name)
        for cte in getattr(stmt, "ctes", []) or []:
            self._referenced_tables(cte.stmt, out)

        def walk_expr(e):
            if isinstance(e, (InSubquery, Exists, ScalarSubquery)):
                self._referenced_tables(e.stmt, out)
            for c in ast_children(e):
                walk_expr(c)

        for e in (stmt.where, stmt.having):
            if e is not None:
                walk_expr(e)

    def _expand_views(self, stmt):
        """Prepend referenced views (transitively, dependencies first) as
        CTEs — the CTE machinery then materializes and scopes them. Names
        already registered as tables (including a scoped CTE broker's)
        or defined as explicit CTEs are never expanded."""
        if not self._views or isinstance(stmt, DdlStmt):
            return stmt
        defined = {c.name for c in getattr(stmt, "ctes", []) or []}
        order: List[str] = []

        def visit(name: str, stack: tuple) -> None:
            if name in defined or name in self._tables or name in order \
                    or name not in self._views:
                return
            if name in stack:
                raise SqlError(
                    "view cycle: " + " -> ".join(stack + (name,)))
            refs: set = set()
            self._referenced_tables(self._views[name], refs)
            for r in sorted(refs):
                visit(r, stack + (name,))
            order.append(name)

        refs: set = set()
        self._referenced_tables(stmt, refs)
        for r in sorted(refs):
            visit(r, ())
        if not order:
            return stmt
        import copy
        new_ctes = [CteDef(n, None, copy.deepcopy(self._views[n]))
                    for n in order]
        stmt.ctes = new_ctes + (stmt.ctes or [])
        return stmt

    def _is_hybrid(self, table: str) -> bool:
        return table not in self._tables and \
            f"{table}_OFFLINE" in self._tables and \
            f"{table}_REALTIME" in self._tables

    def _execute_stmt(self, stmt, t0: float) -> ResultTable:
        if getattr(stmt, "analyze", False):
            return self._execute_analyze(stmt, t0)
        stmt = self._expand_views(stmt)
        if getattr(stmt, "ctes", None):
            return self._execute_with_ctes(stmt, t0)
        if isinstance(stmt, SetOpStmt):
            return self._execute_setop(stmt, t0)
        stmt = self._resolve_subqueries(stmt)
        from ..engine.accounting import global_accountant
        from ..multistage.window import has_window
        # OPTION(queryId=...) names the accountant registration too (not
        # just the round-12 sampling decision): chaos tooling needs the
        # per-query fault streams (utils/faults.py) keyed by a
        # DETERMINISTIC id so same-seed runs reproduce p<1 draws.
        # Collisions are the caller's contract — two concurrent queries
        # sharing a name would share accounting and fault streams.
        query_id = str(getattr(stmt, "options", {}).get("queryId")
                       or uuid.uuid4().hex[:12])[:64]
        timeout_ms = int(stmt.options.get("timeoutMs", DEFAULT_TIMEOUT_MS))
        deadline = t0 + timeout_ms / 1e3
        # tenant attribution rides the accountant registration: the
        # watcher's tier-aware kill ordering and the post-paid tenant
        # budgets (workload.observe at unregister) both read it there
        tenant, tier = self.workload.resolve(stmt.table)
        if self._is_hybrid(stmt.table):
            if stmt.joins or has_window(stmt):
                raise SqlError("joins/window functions over hybrid "
                               "tables are not supported yet; query the "
                               "_OFFLINE/_REALTIME tables directly")
            global_accountant.register(query_id, deadline=deadline,
                                       tenant=tenant, tier=tier,
                                       sql=getattr(stmt, "_raw_sql", None))
            try:
                return self._execute_hybrid(stmt, t0, query_id)
            finally:
                global_accountant.unregister(query_id)
        self.quota.check(stmt.table)
        if stmt.joins or has_window(stmt):
            # v2 engine (BrokerRequestHandlerDelegate picks the multi-stage
            # handler when the query needs it); registered with the
            # accountant like any query so kills/deadlines reach its leaf
            # scans' sample points
            from ..multistage import execute_multistage
            from ..multistage.executor import explain_multistage
            if stmt.explain:
                return explain_multistage(self, stmt)
            global_accountant.register(query_id, deadline=deadline,
                                       tenant=tenant, tier=tier,
                                       sql=getattr(stmt, "_raw_sql", None))
            try:
                return execute_multistage(self, stmt)
            finally:
                global_accountant.unregister(query_id)
        ctx = build_query_context(stmt)
        trace_on = _truthy(ctx.options.get("trace"))
        scope = Tracing.register(query_id, trace_on)
        global_accountant.register(query_id, deadline=deadline,
                                   tenant=tenant, tier=tier,
                                   sql=getattr(stmt, "_raw_sql", None))
        try:
            result = self._execute_ctx(ctx, stmt, t0, deadline,
                                       query_id=query_id)
        finally:
            global_accountant.unregister(query_id)
            Tracing.unregister()
        if trace_on:
            result.trace = scope.to_dict()
        return result

    # -- EXPLAIN ANALYZE (round-7 observability tentpole) ------------------
    def _execute_analyze(self, stmt, t0: float) -> ResultTable:
        """Execute the statement for real under the span tracer and
        return the rendered span tree: per-phase wall ms (planning /
        kernel build / device execute / transfer / reduce), the
        cost-model strategy trace, plan-cache hit/miss + retrace flags,
        and estimated vs measured selectivity. OPTION(ledgerTrace=true)
        additionally appends the tree as a v2 ``query_trace`` ledger
        record (utils/ledger.py)."""
        from ..ops.plan_cache import global_plan_cache
        from ..query.explain import finalize_analyze
        from ..utils.spans import span_tracer

        stmt.analyze = False  # the re-entrant call executes normally
        cache0 = global_plan_cache.stats()
        root = span_tracer.start(ph.QUERY,
                                 table=getattr(stmt, "table", None))
        try:
            inner = self._execute_stmt(stmt, t0)
        finally:
            root = span_tracer.stop() or root
        cache1 = global_plan_cache.stats()
        root.annotate(
            rows=len(inner.rows),
            num_segments=inner.num_segments,
            num_docs_scanned=inner.num_docs_scanned,
            cache_hits=cache1["hits"] - cache0["hits"],
            cache_misses=cache1["misses"] - cache0["misses"],
            retraces=cache1["retraces"] - cache0["retraces"])
        # finalize_analyze attaches the explicit broker_overhead
        # self-time child (context build, quota, accountant
        # registration) so phase timings sum to the query's wall time —
        # shared with the cluster broker's _query_analyze
        cols, rows, trace = finalize_analyze(root)
        result = ResultTable(cols, rows,
                             num_segments=inner.num_segments,
                             num_docs_scanned=inner.num_docs_scanned)
        result.trace = trace
        if _truthy(stmt.options.get("ledgerTrace")):
            from ..utils import ledger as uledger
            path = (stmt.options.get("ledgerPath")
                    or uledger.default_capture_log())
            uledger.append_record(uledger.trace_record(
                root, getattr(stmt, "_raw_sql", str(stmt.table))), path)
        result.time_ms = (time.perf_counter() - t0) * 1e3
        return result

    # -- hybrid offline+realtime tables (TimeBoundaryManager analog) -------
    def _execute_hybrid(self, stmt: SelectStmt, t0: float,
                        query_id: str = "") -> ResultTable:
        """Logical table = T_OFFLINE + T_REALTIME: the offline side answers
        time <= boundary, the realtime side time > boundary, partials merge
        in one reduce (BaseBrokerRequestHandler hybrid scatter)."""
        from ..engine.accounting import QueryKilledError
        from ..engine.serving import execute_planned, plan_segments
        from .routing import (resolve_time_column, split_hybrid,
                              time_boundary)
        logical = stmt.table
        off_dm = self.table(f"{logical}_OFFLINE")

        cfg = getattr(off_dm, "table_config", None)
        time_col = resolve_time_column(
            {"timeColumn": getattr(cfg, "time_column", None)}
            if cfg is not None else None, off_dm.schema)
        if time_col is None:
            raise SqlError(
                f"hybrid table {logical!r} needs a timeColumn in its "
                f"config or a DATE_TIME schema field")

        boundary = time_boundary(
            {seg.name: {"columns": {time_col: {
                "max": getattr(seg.columns.get(time_col), "max", None)}}}
             for seg in off_dm.acquire_segments()}, time_col)
        if boundary is None:
            raise SqlError(
                f"hybrid table {logical!r}: no offline segments, or "
                f"offline segments lack {time_col!r} metadata for the "
                f"time boundary")

        off_stmt, rt_stmt = split_hybrid(stmt, time_col, boundary)
        if stmt.explain:
            # _execute_stmt charges the quota for the explain itself
            return self._execute_stmt(off_stmt, t0)
        self.quota.check(f"{logical}_OFFLINE")
        partials: List[Any] = []
        n_segments = pruned = docs = 0
        try:
            for part_stmt in (off_stmt, rt_stmt):
                ctx_p = build_query_context(part_stmt)
                dm = self.table(ctx_p.table)
                segments = dm.acquire_segments()
                ex = plan_segments(ctx_p, segments, use_rollups=True)
                partials.extend(execute_planned(ex))
                n_segments += len(segments)
                pruned += ex.pruned
                docs += ex.docs_scanned
        except QueryKilledError as e:
            if e.is_deadline:
                global_metrics.count("broker_query_timeouts")
                raise QueryTimeoutError(str(e)) from None
            raise
        result = reduce_partials(build_query_context(off_stmt), partials)
        result.num_segments = n_segments
        result.num_segments_pruned = pruned
        result.num_docs_scanned = docs
        result.time_ms = (time.perf_counter() - t0) * 1e3
        return result

    # -- set operations (v2 set operators; combine at the broker) ----------
    _BRANCH_LIMIT = 1 << 31  # branches run unlimited; compound LIMIT caps

    def _execute_setop(self, stmt: SetOpStmt, t0: float) -> ResultTable:
        if stmt.explain:
            return self._explain_setop(stmt)
        left = self._run_branch(stmt.left, stmt.options)
        right = self._run_branch(stmt.right, stmt.options)
        result = combine_setop(stmt.op, stmt.all, left, right)
        from ..engine.reduce import DEFAULT_LIMIT
        limit = stmt.limit if stmt.limit is not None else DEFAULT_LIMIT
        result = order_limit_rows(result, stmt.order_by, limit, stmt.offset)
        result.time_ms = (time.perf_counter() - t0) * 1e3
        return result

    def _run_branch(self, stmt, options: Optional[dict] = None
                    ) -> ResultTable:
        if isinstance(stmt, SetOpStmt):
            left = self._run_branch(stmt.left, options)
            right = self._run_branch(stmt.right, options)
            return combine_setop(stmt.op, stmt.all, left, right)
        if options:
            # compound-level OPTION(...) applies to every branch
            # (branch-specific keys win)
            stmt.options = {**options, **stmt.options}
        if stmt.limit is None:
            stmt.limit = self._BRANCH_LIMIT
        return self._execute_stmt(stmt, time.perf_counter())

    def _explain_setop(self, stmt: SetOpStmt) -> ResultTable:
        rows: List[tuple] = []

        def emit(node, parent: int) -> None:
            rid = len(rows)
            if isinstance(node, SetOpStmt):
                tag = node.op.upper() + ("_ALL" if node.all else "")
                rows.append((f"SET_OP({tag})", rid, parent))
                emit(node.left, rid)
                emit(node.right, rid)
            else:
                rows.append((f"SELECT({node.table})", rid, parent))

        rows.append(("BROKER_REDUCE", 0, -1))
        emit(stmt, 0)
        return ResultTable(["Operator", "Operator_Id", "Parent_Id"], rows)

    # -- WITH / common table expressions -----------------------------------
    def _execute_with_ctes(self, stmt, t0: float) -> ResultTable:
        """Materialize each CTE (in order — later CTEs may reference
        earlier ones) into an in-memory segment registered under a
        SCOPED broker copy, then run the main statement against it.
        The scope shadows real tables for this query only and is torn
        down afterwards. Reference:
        pinot-query-planner/.../QueryEnvironment.java:126 (Calcite CTE
        planning); materialization-first is the TPU-friendly stance —
        the CTE result becomes a real segment every engine path (joins,
        windows, group-by kernels) already handles."""
        import copy
        import dataclasses
        import shutil

        scoped = copy.copy(self)
        scoped._tables = dict(self._tables)
        tmpdirs: List[str] = []
        try:
            cap = int(stmt.options.get("cteLimit", 1_000_000))
            for cte in stmt.ctes:
                if stmt.explain and not stmt.joins:
                    # EXPLAIN must not execute CTE/view bodies (same
                    # contract as _resolve_subqueries): register a
                    # zero-row placeholder carrying the output columns
                    # so the outer plan still builds. SELECT * bodies
                    # have no static column list, and the multistage
                    # join path needs real (typed) segments —
                    # materialize those the normal way.
                    names = self._static_output_columns(cte.stmt)
                    if names is not None:
                        if cte.columns and \
                                len(cte.columns) != len(names):
                            raise SqlError(
                                f"CTE {cte.name!r} declares "
                                f"{len(cte.columns)} columns but its "
                                f"query produces {len(names)}")
                        scoped._tables[cte.name] = _cte_table(
                            cte.name, list(cte.columns or names), [],
                            tmpdirs)
                        continue
                # keep the body's OWN ctes (a view defined with a WITH
                # clause): the recursive _execute_stmt materializes them
                # in a further scope; replace() still copies the node so
                # option/limit mutations never touch the stored body
                sub = dataclasses.replace(cte.stmt)
                if "timeoutMs" in stmt.options:
                    sub.options.setdefault("timeoutMs",
                                           stmt.options["timeoutMs"])
                # a CTE materializes its FULL result (no engine default
                # LIMIT 10), bounded by the cteLimit resource guard the
                # same way IN-subqueries are: an explicit LIMIT within
                # the cap is honored, anything else gets the cap+1
                # probe + error so the guard stays enforceable
                user_limit = sub.limit
                honored = user_limit is not None and user_limit <= cap
                if not honored:
                    sub.limit = cap + 1
                res = scoped._execute_stmt(sub, time.perf_counter())
                if not honored and len(res.rows) > cap:
                    over = (f" (its LIMIT {user_limit} exceeds the cap "
                            "and was not applied)"
                            if user_limit is not None else "")
                    raise SqlError(
                        f"CTE {cte.name!r} produced more than {cap} "
                        f"rows{over}; add a LIMIT <= {cap} or raise "
                        "OPTION(cteLimit=...)")
                names = cte.columns or res.columns
                if len(names) != len(res.columns):
                    raise SqlError(
                        f"CTE {cte.name!r} declares {len(cte.columns)} "
                        f"columns but its query produces "
                        f"{len(res.columns)}")
                scoped._tables[cte.name] = _cte_table(
                    cte.name, list(names), res.rows, tmpdirs)
            inner = dataclasses.replace(stmt, ctes=[])
            return scoped._execute_stmt(inner, t0)
        finally:
            for d in tmpdirs:
                shutil.rmtree(d, ignore_errors=True)

    @staticmethod
    def _static_output_columns(stmt) -> Optional[List[str]]:
        """Output column names of a statement WITHOUT executing it, or
        None when they aren't statically known (SELECT *)."""
        if isinstance(stmt, SetOpStmt):
            return Broker._static_output_columns(stmt.left)
        try:
            labels = build_query_context(stmt).labels
        except SqlError:
            return None
        if any(lb == "*" for lb in labels):
            return None
        return list(labels)

    # -- subqueries (IN_SUBQUERY / scalar / EXISTS rewrite at the broker) --
    _TRUE = Comparison("==", Literal(1), Literal(1))
    _FALSE = Comparison("==", Literal(1), Literal(0))

    def _decorrelate_exists(self, e: "Exists", stmt: SelectStmt):
        """Rewrite EXISTS to something the existing machinery executes.

        Uncorrelated: the subquery runs once with LIMIT 1 and folds to a
        constant predicate. Equality-correlated (the decorrelatable
        class Calcite's SubQueryRemoveRule handles as a semi-join):
        exactly one top-level AND-ed `inner.col = outer.col` conjunct —
        rewritten to `outer.col IN (SELECT inner.col FROM ... WHERE
        <remaining conjuncts>)`, which the IN-subquery (IdSet) path then
        materializes. Returns the replacement predicate node, or raises
        SqlError for correlation shapes outside that class."""
        import dataclasses

        from ..query.sql import BoolAnd, Comparison as Cmp, Identifier, \
            IsNull, SelectItem, collect_identifiers

        sub = e.stmt
        # standard SQL scoping: an alias REPLACES the table name as the
        # qualifier (so a self-table subquery with an alias still sees
        # the outer name as a correlation, not as itself)
        outer_labels = {(stmt.table_alias or stmt.table).lower()}
        inner_labels = {(sub.table_alias or sub.table).lower()}

        def cols_of(table: str) -> set:
            # tolerant: hybrid logical names (ev -> ev_OFFLINE/_REALTIME)
            # aren't in _tables; qualified correlation still classifies
            # by label, and a misjudged bare identifier surfaces as an
            # unknown-column error at execution, never a wrong result
            try:
                schema = self.table(table).schema
            except SqlError:
                return set()
            return {f.name for f in schema.fields} if schema else set()

        outer_cols = cols_of(stmt.table)
        inner_cols = cols_of(sub.table)

        def side(ident: str):
            """'inner' | 'outer' for an identifier in the subquery."""
            if "." in ident:
                qual, col = ident.split(".", 1)
                if qual.lower() in inner_labels:
                    return "inner", col
                if qual.lower() in outer_labels:
                    return "outer", col
                raise SqlError(
                    f"unknown qualifier {qual!r} in EXISTS subquery "
                    f"(tables in scope: {sorted(inner_labels)} inner, "
                    f"{sorted(outer_labels)} outer)")
            if ident in inner_cols:
                return "inner", ident
            if ident in outer_cols:
                return "outer", ident
            return "inner", ident   # let execution raise unknown-column

        conjuncts = (list(sub.where.children)
                     if isinstance(sub.where, BoolAnd)
                     else [sub.where] if sub.where is not None else [])
        corr, local = [], []
        for c in conjuncts:
            sides = {side(i)[0] for i in collect_identifiers(c)}
            (corr if "outer" in sides else local).append(c)
        if not corr:
            probe = dataclasses.replace(
                sub, limit=1, ctes=[],
                options={**stmt.options, **sub.options})
            res = self._execute_stmt(probe, time.perf_counter())
            return self._TRUE if res.rows else self._FALSE

        if len(corr) != 1 or sub.joins or sub.group_by or sub.having:
            raise SqlError(
                "correlated EXISTS is supported with exactly one "
                "top-level `inner.col = outer.col` equality and no "
                "joins/GROUP BY/HAVING in the subquery; rewrite the "
                "query as an explicit JOIN instead")
        c = corr[0]
        if not (isinstance(c, Cmp) and c.op == "=="
                and isinstance(c.lhs, Identifier)
                and isinstance(c.rhs, Identifier)):
            raise SqlError(
                "correlated EXISTS predicate must be a plain equality "
                f"between one inner and one outer column, got "
                f"{type(c).__name__}")
        s1, col1 = side(c.lhs.name)
        s2, col2 = side(c.rhs.name)
        if {s1, s2} != {"inner", "outer"}:
            raise SqlError(
                "correlated EXISTS equality must reference exactly one "
                "inner and one outer column")
        inner_col = col1 if s1 == "inner" else col2
        outer_col = col2 if s1 == "inner" else col1

        def strip(expr):
            from ..query.sql import map_expr

            def unqualify(x):
                if isinstance(x, Identifier) and "." in x.name:
                    qual, col = x.name.split(".", 1)
                    if qual.lower() in inner_labels:
                        return Identifier(col)
                return x
            return map_expr(expr, unqualify)

        remaining = [strip(x) for x in local]
        # inner NULLs can never witness the equality; filtering them keeps
        # the materialized IN list clean for the NOT EXISTS (BoolNot) form
        remaining.append(IsNull(Identifier(inner_col), negated=True))
        where = remaining[0] if len(remaining) == 1 \
            else BoolAnd(tuple(remaining))
        sub2 = dataclasses.replace(
            sub, select=[SelectItem(Identifier(inner_col))],
            distinct=True, where=where, limit=None, order_by=[],
            table_alias=None,
            options={**stmt.options, **sub.options})
        return InSubquery(Identifier(outer_col), sub2, negated=False)

    def _resolve_subqueries(self, stmt: SelectStmt) -> SelectStmt:
        if stmt.explain:
            # EXPLAIN must not execute the subquery scan; substitute
            # placeholder shapes so the plan still builds
            def placeholder(e):
                if isinstance(e, InSubquery):
                    return InList(e.expr, (Literal(0),), e.negated)
                if isinstance(e, ScalarSubquery):
                    return Literal(0)
                if isinstance(e, Exists):
                    return self._TRUE
                return e
            if stmt.where is not None:
                stmt.where = map_expr(stmt.where, placeholder)
            if stmt.having is not None:
                stmt.having = map_expr(stmt.having, placeholder)
            return stmt

        def rw(e):
            if isinstance(e, Exists):
                # decorrelate/fold, then resolve the InSubquery it may
                # produce through the same materialization below
                return rw(self._decorrelate_exists(e, stmt))
            if isinstance(e, InSubquery):
                # bounded materialization (VERDICT r3 weak #7; the
                # reference bounds IdSet size the same way): the broker
                # fetches cap+1 rows and ERRORS past the cap instead of
                # silently truncating to a wrong answer
                cap = int(stmt.options.get("inSubqueryLimit", 100_000))
                sub = e.stmt
                # an explicit user LIMIT within the cap is honored as-is
                # (bounded materialization with the documented
                # deterministic-truncation LIMIT contract); anything else
                # — no LIMIT, or a LIMIT above the cap — keeps the cap+1
                # probe + error so the resource guard stays enforceable
                user_limit = sub.limit
                honored = user_limit is not None and user_limit <= cap
                if not honored:
                    sub.limit = cap + 1
                res = self._execute_stmt(sub, time.perf_counter())
                if len(res.columns) != 1:
                    raise SqlError(
                        f"IN subquery must select exactly 1 column, "
                        f"got {len(res.columns)}")
                if not honored and len(res.rows) > cap:
                    over = (f" (its LIMIT {user_limit} exceeds the cap "
                            "and was not applied)"
                            if user_limit is not None else "")
                    raise SqlError(
                        f"IN subquery produced more than {cap} rows"
                        f"{over}; add a LIMIT <= {cap}, narrow it, or "
                        "raise OPTION(inSubqueryLimit=...)")
                vals = tuple(Literal(r[0].item() if hasattr(r[0], "item")
                                     else r[0]) for r in res.rows)
                return InList(e.expr, vals, e.negated)
            if isinstance(e, ScalarSubquery):
                res = self._execute_stmt(e.stmt, time.perf_counter())
                if len(res.rows) != 1 or len(res.rows[0]) != 1:
                    raise SqlError(
                        f"scalar subquery must return 1 row x 1 column, "
                        f"got {len(res.rows)} rows")
                v = res.rows[0][0]
                return Literal(v.item() if hasattr(v, "item") else v)
            return e

        if stmt.where is not None:
            stmt.where = map_expr(stmt.where, rw)
        if stmt.having is not None:
            stmt.having = map_expr(stmt.having, rw)
        return stmt

    def _execute_ctx(self, ctx, stmt, t0: float, deadline: float,
                     query_id: str = "") -> ResultTable:
        dm = self.table(ctx.table)
        segments = dm.acquire_segments()

        # mesh-resident table: one shard_map program + ICI combine replaces
        # the per-segment scatter-gather entirely
        from ..utils.spans import phase
        dist = dm.distributed
        if dist is not None and not stmt.explain:
            from ..engine.serving import execute_on_mesh
            partial = execute_on_mesh(ctx, dist)
            if partial is not None:
                result = reduce_partials(ctx, [partial])
                result.num_segments = len(dist.segments)
                result.num_docs_scanned = sum(
                    s.n_docs for s in dist.segments)
                result.time_ms = (time.perf_counter() - t0) * 1e3
                return result

        # shared plan + rollup + batched-dispatch loop (engine/serving.py)
        from ..engine.serving import execute_planned, plan_segments
        ex = plan_segments(ctx, segments, use_rollups=not stmt.explain)

        if stmt.explain:
            from ..query.explain import explain_rows
            cols, rows = explain_rows(ctx, ex.real_plans, ex.rollup_segments)
            return ResultTable(cols, rows, num_segments=len(segments))

        # Planning includes XLA compilation on a cold chip (20-40s once,
        # cached thereafter) — exclude it from the query budget, which
        # covers execution + reduce, or every cold-start query would blow
        # the default 10s timeout (ServerQueryExecutorV1Impl's timeout
        # covers execution; Java has no compile phase to exclude).
        plan_elapsed = time.perf_counter() - t0
        deadline += plan_elapsed
        from ..engine.accounting import global_accountant
        global_accountant.set_deadline(query_id, deadline)

        Tracing.count("numSegmentsQueried", len(segments))
        Tracing.count("numSegmentsPruned", ex.pruned)
        Tracing.count("numDocsScanned", ex.docs_scanned)

        from ..engine.accounting import QueryKilledError
        try:
            partials = execute_planned(ex)
        except QueryKilledError as e:
            if e.is_deadline:
                global_metrics.count("broker_query_timeouts")
                raise QueryTimeoutError(str(e)) from None
            raise

        if time.perf_counter() > deadline:
            global_metrics.count("broker_query_timeouts")
            raise QueryTimeoutError(
                f"query timed out (>{int((deadline - t0) * 1e3)}ms)")

        with phase(ph.REDUCE, partials=len(partials)):
            result = reduce_partials(ctx, partials)
        result.num_segments = len(segments)
        result.num_segments_pruned = ex.pruned
        result.num_docs_scanned = ex.docs_scanned
        result.time_ms = (time.perf_counter() - t0) * 1e3
        return result


class Connection:
    """Client-facing handle (pinot-clients java-client analog)."""

    def __init__(self, broker: Broker):
        self.broker = broker

    def execute(self, sql: str) -> ResultTable:
        return self.broker.query(sql)

    __call__ = execute


def connect(broker: Broker) -> Connection:
    return Connection(broker)
