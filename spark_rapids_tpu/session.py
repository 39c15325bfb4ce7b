"""User-facing session + DataFrame API.

Plays the combined role of SparkSession and the plugin lifecycle (reference:
SQLPlugin -> RapidsDriverPlugin/RapidsExecutorPlugin, Plugin.scala:426/496):
constructing a session initializes the device runtime (device manager, buffer
catalog, semaphore) and installs the plan-rewrite rule; every action re-reads
the conf and applies TpuOverrides to the CPU plan (reference re-reads SQLConf
per query, GpuOverrides.scala:4564).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Union

from spark_rapids_tpu import types as T
from spark_rapids_tpu import config as C
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.columnar.batch import (HostColumnarBatch,
                                             batch_from_arrow,
                                             batch_from_pydict)
from spark_rapids_tpu.expressions.base import (Alias, AttributeReference,
                                               Expression, Literal,
                                               bind_references, col, lit)
from spark_rapids_tpu.plan.base import Exec
from spark_rapids_tpu.plan.overrides import TpuOverrides


class TpuSession:
    _active: Optional["TpuSession"] = None

    def __init__(self, conf: Optional[Union[TpuConf, Dict]] = None,
                 init_device: bool = True):
        if isinstance(conf, dict):
            conf = TpuConf(conf)
        self.conf = conf or C.default_conf()
        if init_device and self.conf.is_sql_enabled:
            from spark_rapids_tpu.memory.device_manager import initialize
            self.runtime = initialize(self.conf)
        else:
            self.runtime = None
        from spark_rapids_tpu.shuffle.env import init_shuffle_env
        self.shuffle_env = init_shuffle_env(self.conf)
        # chaos layer: arm/disarm fault points from spark.rapids.chaos.*
        # at session construction (overrides.apply re-syncs per action)
        from spark_rapids_tpu.aux.faults import arm_from_conf
        arm_from_conf(self.conf)
        # live resource sampler (spark.rapids.sample.*): start/stop the
        # process singleton to match this session's conf
        from spark_rapids_tpu.aux.sampler import sync_from_conf
        sync_from_conf(self.conf)
        # hung-query watchdog (spark.rapids.watchdog.*): same singleton
        # lifecycle — dumps + escalates tasks that stop making progress
        from spark_rapids_tpu.memory.arbiter import sync_watchdog_from_conf
        sync_watchdog_from_conf(self.conf)
        # runtime lock-order validator (spark.rapids.debug.lockOrder)
        from spark_rapids_tpu.aux.lockorder import sync_from_conf \
            as sync_lockorder
        sync_lockorder(self.conf)
        # host-transition ledger (spark.rapids.sql.transitions.*): arm
        # the instrumented sync/transfer gateway
        from spark_rapids_tpu.aux.transitions import sync_from_conf \
            as sync_transitions
        sync_transitions(self.conf)
        # device mesh (spark.rapids.mesh.*): validate + activate from the
        # conf, emitting a meshTopology event; a bad shape fails HERE,
        # not at the first collective
        from spark_rapids_tpu.parallel.mesh import sync_from_conf \
            as sync_mesh
        sync_mesh(self.conf)
        # live engine console (spark.rapids.console.*): the HTTP
        # metrics/status endpoint, same process-singleton lifecycle
        from spark_rapids_tpu.aux.console import sync_from_conf \
            as sync_console
        sync_console(self.conf)
        #: temp views for the SQL front-end (name -> DataFrame)
        self._views: Dict[str, "DataFrame"] = {}
        #: row-based Hive UDF passthrough (name -> (fn, return_type));
        #: reference: rowBasedHiveUDFs.scala wraps metastore-registered
        #: UDFs for row-at-a-time CPU evaluation
        self._hive_udfs: Dict[str, tuple] = {}
        TpuSession._active = self

    # -- conf ---------------------------------------------------------------
    def set_conf(self, key: str, value) -> "TpuSession":
        """Sets one conf key.  Registered keys validate here (converter +
        checker run in the TpuConf rebuild — a bad
        ``spark.rapids.shuffle.fetch.timeoutMs`` or malformed chaos spec
        raises immediately, not mid-query); ``spark.rapids.chaos.*`` keys
        additionally re-arm the fault registry so chaos takes effect for
        the very next action."""
        self.conf = self.conf.set(key, value)
        if key.startswith("spark.rapids.chaos."):
            from spark_rapids_tpu.aux.faults import arm_from_conf
            arm_from_conf(self.conf)
        elif key.startswith(("spark.rapids.shuffle.fetch.",
                             "spark.rapids.shuffle.transport.")):
            self.shuffle_env.update_fetch_retry(self.conf)
        elif key.startswith(("spark.rapids.sample.",
                             "spark.rapids.sql.eventLog.")):
            # the sampler singleton tracks both its own knobs and the
            # event-log destination it mirrors samples into
            from spark_rapids_tpu.aux.sampler import sync_from_conf
            sync_from_conf(self.conf)
        elif key.startswith("spark.rapids.watchdog."):
            from spark_rapids_tpu.memory.arbiter import \
                sync_watchdog_from_conf
            sync_watchdog_from_conf(self.conf)
        elif key.startswith("spark.rapids.debug."):
            from spark_rapids_tpu.aux.lockorder import sync_from_conf \
                as sync_lockorder
            sync_lockorder(self.conf)
        elif key.startswith("spark.rapids.sql.transitions."):
            from spark_rapids_tpu.aux.transitions import sync_from_conf \
                as sync_transitions
            sync_transitions(self.conf)
        elif key.startswith("spark.rapids.mesh."):
            from spark_rapids_tpu.parallel.mesh import sync_from_conf \
                as sync_mesh
            sync_mesh(self.conf, allow_disable=True)
        elif key.startswith("spark.rapids.console."):
            from spark_rapids_tpu.aux.console import sync_from_conf \
                as sync_console
            sync_console(self.conf)
        return self

    # -- SQL ----------------------------------------------------------------
    def sql(self, text: str) -> "DataFrame":
        """Executes SQL text against registered temp views (the reference
        accepts arbitrary Spark SQL via Catalyst; here sql/ carries the
        parser + analyzer for the TPC-DS-class dialect)."""
        from spark_rapids_tpu.aux.tracing import timed_span
        from spark_rapids_tpu.sql.analyzer import Analyzer
        from spark_rapids_tpu.sql.parser import parse
        # no query runs yet: the spans go to the profiler's trace at once,
        # and the DataFrame keeps the intervals for the query that runs it
        planned: list = []
        with timed_span("plan.parse", planned):
            tree = parse(text)
        with timed_span("plan.analyze", planned):
            df = Analyzer(self).plan(tree)
        df._planned = tuple(planned)
        return df

    def create_or_replace_temp_view(self, name: str, df: "DataFrame") -> None:
        self._views[name.lower()] = df

    def register_hive_udf(self, name: str, fn, return_type) -> None:
        """Registers a row-based UDF callable from SQL by name — the
        Hive-UDF passthrough analog (reference: rowBasedHiveUDFs.scala:
        GpuRowBasedHiveSimpleUDF wraps the jar's function for CPU
        row-at-a-time eval; here the python callable plays that role and
        runs on the host tier with honest fallback tagging)."""
        self._hive_udfs[name.lower()] = (fn, return_type)

    createOrReplaceTempView = create_or_replace_temp_view

    def table(self, name: str) -> "DataFrame":
        df = self.catalog_lookup(name)
        if df is None:
            raise ValueError(f"table or view not found: {name}")
        return df

    def catalog_lookup(self, name: str) -> Optional["DataFrame"]:
        return self._views.get(name.lower())

    # -- dataframe constructors --------------------------------------------
    def create_dataframe(self, data, schema: Optional[T.StructType] = None,
                         num_partitions: int = 1) -> "DataFrame":
        import pyarrow as pa
        from spark_rapids_tpu.exec.basic import CpuInMemoryScanExec
        if isinstance(data, dict):
            hb = batch_from_pydict(data, schema)
        elif isinstance(data, (pa.Table, pa.RecordBatch)):
            hb = batch_from_arrow(data)
        elif isinstance(data, HostColumnarBatch):
            hb = data
        else:
            try:
                import pandas as pd
                if isinstance(data, pd.DataFrame):
                    hb = batch_from_arrow(pa.Table.from_pandas(data))
                else:
                    raise TypeError
            except TypeError:
                raise TypeError(f"cannot create DataFrame from {type(data)}")
        n = hb.row_count
        per = -(-n // num_partitions) if n else 1
        parts = [[hb.slice(i * per, min(per, n - i * per))]
                 for i in range(num_partitions) if i * per < n] or [[hb]]
        return DataFrame(CpuInMemoryScanExec(parts, hb.schema), self)

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_partitions: int = 1) -> "DataFrame":
        from spark_rapids_tpu.exec.basic import CpuRangeExec
        if end is None:
            start, end = 0, start
        return DataFrame(CpuRangeExec(start, end, step, num_partitions), self)

    class _Reader:
        """``session.read.option(...).csv(path)`` (DataFrameReader analog).

        Reader strategy + thread count come from the session conf
        (reference: RapidsConf READER_TYPE / MULTITHREAD_READ_NUM_THREADS)."""

        def __init__(self, session):
            self._s = session
            self._schema = None
            self._options = {}

        def schema(self, s) -> "TpuSession._Reader":
            self._schema = s
            return self

        def option(self, key, value) -> "TpuSession._Reader":
            self._options[key] = value
            return self

        def _common(self, type_entry):
            conf = self._s.conf
            return dict(
                reader_type=conf.get(type_entry.key),
                batch_rows=conf.get(C.MAX_READER_BATCH_SIZE_ROWS.key),
                num_threads=conf.get(C.MULTITHREADED_READ_NUM_THREADS.key))

        def parquet(self, *paths, columns=None) -> "DataFrame":
            from spark_rapids_tpu.io.parquet import CpuParquetScanExec
            return DataFrame(
                CpuParquetScanExec(list(paths), columns,
                                   **self._common(C.READER_TYPE)), self._s)

        def csv(self, *paths, columns=None) -> "DataFrame":
            from spark_rapids_tpu.io.text import CpuCsvScanExec
            opts = {k: v for k, v in self._options.items()
                    if k in ("header", "sep", "quote", "escape", "comment",
                             "null_value")}
            return DataFrame(CpuCsvScanExec(
                list(paths), user_schema=self._schema, columns=columns,
                **opts, **self._common(C.CSV_READER_TYPE)), self._s)

        def json(self, *paths, columns=None) -> "DataFrame":
            from spark_rapids_tpu.io.text import CpuJsonScanExec
            return DataFrame(CpuJsonScanExec(
                list(paths), user_schema=self._schema, columns=columns,
                **self._common(C.JSON_READER_TYPE)), self._s)

        def orc(self, *paths, columns=None) -> "DataFrame":
            from spark_rapids_tpu.io.orc import CpuOrcScanExec
            return DataFrame(
                CpuOrcScanExec(list(paths), columns=columns,
                               **self._common(C.ORC_READER_TYPE)), self._s)

        def text(self, *paths) -> "DataFrame":
            from spark_rapids_tpu.io.text import CpuTextScanExec
            return DataFrame(
                CpuTextScanExec(list(paths),
                                **self._common(C.READER_TYPE)), self._s)

        def hive_text(self, *paths, schema=None, serde=None,
                      columns=None) -> "DataFrame":
            """Hive text table (LazySimpleSerDe subset; reference:
            GpuHiveTableScanExec).  ``schema`` is required — the metastore
            provides it in Spark; ``serde`` = {field.delim,
            serialization.null.format, escape.delim}."""
            from spark_rapids_tpu.hive.table import CpuHiveTextScanExec
            sch = schema or self._schema
            if sch is None:
                raise ValueError("hive_text requires a schema (the "
                                 "metastore's role)")
            return DataFrame(
                CpuHiveTextScanExec(list(paths), sch, serde=serde,
                                    columns=columns,
                                    **self._common(C.READER_TYPE)),
                self._s)

        def avro(self, *paths, columns=None) -> "DataFrame":
            from spark_rapids_tpu.io.avro import CpuAvroScanExec
            return DataFrame(
                CpuAvroScanExec(list(paths), columns=columns,
                                **self._common(C.READER_TYPE)), self._s)

    @property
    def read(self) -> "_Reader":
        return TpuSession._Reader(self)

    def stop(self):
        from spark_rapids_tpu.aux.console import stop_console
        stop_console()
        from spark_rapids_tpu.aux.sampler import stop_sampler
        stop_sampler()
        from spark_rapids_tpu.memory.arbiter import stop_watchdog
        stop_watchdog()
        from spark_rapids_tpu.memory.device_manager import shutdown
        shutdown()
        if self.shuffle_env is not None:
            self.shuffle_env.shutdown()
        if TpuSession._active is self:
            TpuSession._active = None


def _to_expr(e) -> Expression:
    if isinstance(e, Expression):
        return e
    if isinstance(e, str):
        return col(e)
    return lit(e)


def rows_from_host_batch(batch) -> List[dict]:
    """List-of-dict rows from a HostColumnarBatch — THE collect row
    shape, shared by ``DataFrame.collect`` and the serving layer's
    ``Submission.result`` so served rows can never drift from
    DataFrame rows."""
    from spark_rapids_tpu.aux.tracing import span
    with span("result.rows"):
        return _rows(batch)


def _rows(batch) -> List[dict]:
    d = batch.to_pydict()
    names = list(d.keys())
    return [dict(zip(names, row)) for row in zip(*d.values())] \
        if names else []


def collect_with_speculation(conf, plan_factory) -> HostColumnarBatch:
    """THE speculative-sizing collect discipline, shared by DataFrame
    actions and the serving layer: run under a speculation scope, check
    every overflow flag with one sync, and replay the whole action in
    exact mode if any fired.  ``plan_factory()`` returns the prepared
    physical plan — called again for the replay so the factory can
    re-arm per-execution state (CTE epochs) or re-plan."""
    from spark_rapids_tpu import config as C
    from spark_rapids_tpu.aux import tracing
    from spark_rapids_tpu.ops.speculation import (SpeculationOverflow,
                                                  no_speculation,
                                                  speculation_scope)

    def collect():
        plan = plan_factory()
        with tracing.run_span(plan):
            return plan.collect_host()

    if not conf.get(C.SPECULATIVE_SIZING_ENABLED.key):
        with no_speculation():
            return collect()
    try:
        with speculation_scope() as ctx:
            out = collect()
            if ctx is not None:
                ctx.check()   # one sync over every overflow flag
            return out
    except SpeculationOverflow:
        # a speculative output bucket was too small somewhere: replay
        # the whole action with exact (sync-per-decision) sizing.  A
        # query that ran twice says so: the span and the counter
        tracing.add_count("speculation_replays")
        with tracing.span("exec.replay"), no_speculation():
            return collect()


class DataFrame:
    """Lazy plan builder over CPU physical execs; actions run the rewrite."""

    def __init__(self, plan: Exec, session: TpuSession):
        self._plan = plan
        self._session = session
        #: the closed ``plan.parse``/``plan.analyze`` intervals of the
        #: text this DataFrame came from (``TpuSession.sql``), until the
        #: first query that runs it adopts them
        self._planned = ()

    @property
    def schema(self) -> T.StructType:
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return self._plan.schema.names

    # -- transformations ----------------------------------------------------
    def select(self, *exprs) -> "DataFrame":
        from spark_rapids_tpu.exec.basic import CpuProjectExec
        bound = [bind_references(_to_expr(e), self.schema) for e in exprs]
        plan, bound = self._plan_windows(bound)
        plan, bound = self._plan_pandas_udfs(plan, bound)
        return DataFrame(CpuProjectExec(bound, plan), self._session)

    def _plan_pandas_udfs(self, plan, bound_exprs):
        """Extracts PandasUDFCalls from a projection into one
        CpuArrowEvalPythonExec appending their result columns, then
        rewrites the projection to reference them (reference:
        GpuArrowEvalPythonExec extraction of PythonUDF)."""
        from spark_rapids_tpu.exec.python_execs import CpuArrowEvalPythonExec
        from spark_rapids_tpu.expressions.base import BoundReference
        from spark_rapids_tpu.expressions.python_udf import PandasUDFCall
        calls = []
        for e in bound_exprs:
            calls.extend(e.collect(lambda x: isinstance(x, PandasUDFCall)))
        if not calls:
            return plan, bound_exprs
        base = len(plan.schema.fields)
        udfs = []
        replacement = {}
        for i, c in enumerate(calls):
            udfs.append((f"__pudf{base + i}", c.fn, list(c.children),
                         c.data_type))
            replacement[id(c)] = BoundReference(base + i, c.data_type, True)
        plan = CpuArrowEvalPythonExec(udfs, plan)

        def rewrite(e):
            if id(e) in replacement:
                return replacement[id(e)]
            if not e.children:
                return e
            return e.with_children([rewrite(ch) for ch in e.children])

        return plan, [rewrite(e) for e in bound_exprs]

    def _plan_windows(self, bound_exprs):
        """Extracts WindowExpressions from a projection: one CpuWindowExec
        per (partition, order) spec group appending columns, then rewrites
        the projection to reference them (Spark's ExtractWindowExpressions
        + the reference's GpuWindowExecMeta grouping)."""
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.exec.window import CpuWindowExec
        from spark_rapids_tpu.expressions.base import BoundReference
        from spark_rapids_tpu.expressions.window_exprs import WindowExpression
        from spark_rapids_tpu.plan.partitioning import (HashPartitioning,
                                                        SinglePartitioning)
        wexprs = []
        for e in bound_exprs:
            wexprs.extend(e.collect(
                lambda x: isinstance(x, WindowExpression)))
        if not wexprs:
            return self._plan, bound_exprs
        groups = {}
        for w in wexprs:
            groups.setdefault(w.spec.group_key(), []).append(w)
        plan = self._plan
        replacement = {}
        for key, ws in groups.items():
            spec = ws[0].spec
            if plan.num_partitions > 1:
                if spec.partition_exprs:
                    part = HashPartitioning(spec.partition_exprs,
                                            plan.num_partitions)
                else:
                    part = SinglePartitioning()
                plan = CpuShuffleExchangeExec(
                    part, plan, shuffle_env=self._session.shuffle_env)
            base = len(plan.schema.fields)
            cols = [(f"_w{base + i}", w) for i, w in enumerate(ws)]
            plan = CpuWindowExec(cols, plan)
            for i, w in enumerate(ws):
                f = plan.schema.fields[base + i]
                replacement[id(w)] = BoundReference(base + i, f.data_type,
                                                    f.nullable)

        def rewrite(e):
            # top-down identity rewrite (transform_up copies nodes before
            # visiting, which would defeat the id() lookup)
            if id(e) in replacement:
                return replacement[id(e)]
            if not e.children:
                return e
            return e.with_children([rewrite(c) for c in e.children])

        return plan, [rewrite(e) for e in bound_exprs]

    @staticmethod
    def _no_windows(expr, where: str):
        from spark_rapids_tpu.expressions.window_exprs import WindowExpression
        if expr.collect(lambda x: isinstance(x, WindowExpression)):
            raise ValueError(
                f"window expressions are not allowed in {where}; compute "
                "them in a select()/with_column() first")
        return expr

    def filter(self, condition) -> "DataFrame":
        from spark_rapids_tpu.exec.basic import CpuFilterExec
        cond = bind_references(_to_expr(condition), self.schema)
        self._no_windows(cond, "filter()")
        return DataFrame(CpuFilterExec(cond, self._plan), self._session)

    where = filter

    def with_column(self, name: str, expr) -> "DataFrame":
        from spark_rapids_tpu.exec.basic import CpuProjectExec
        exprs = []
        replaced = False
        for f in self.schema.fields:
            if f.name == name:
                exprs.append(Alias(_to_expr(expr), name))
                replaced = True
            else:
                exprs.append(col(f.name))
        if not replaced:
            exprs.append(Alias(_to_expr(expr), name))
        bound = [bind_references(e, self.schema) for e in exprs]
        plan, bound = self._plan_windows(bound)
        return DataFrame(CpuProjectExec(bound, plan), self._session)

    def drop(self, *cols) -> "DataFrame":
        names = {str(c) for c in cols}
        keep = [col(f.name) for f in self.schema.fields
                if f.name not in names]
        if len(keep) == len(self.schema.fields):
            return self
        return self.select(*keep)

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        if old not in self.columns:
            return self
        return self.select(*[
            Alias(col(f.name), new if f.name == old else f.name)
            for f in self.schema.fields])

    @property
    def na(self) -> "DataFrameNaFunctions":
        return DataFrameNaFunctions(self)

    def intersect(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows present in both (Spark INTERSECT).  NOTE: columns
        match BY NAME here (engine restriction), not positionally as in
        Spark SQL set operations.  The right side needs no distinct: a
        left-semi join ignores duplicate matches."""
        on = list(self.columns)
        return self.distinct().join(other, on=on,
                                    how="left_semi", null_safe=True)

    def except_distinct(self, other: "DataFrame") -> "DataFrame":
        """Distinct rows of self absent from other (Spark EXCEPT
        [DISTINCT]; there is intentionally no exceptAll alias — multiset
        semantics are not implemented).  Columns match BY NAME."""
        on = list(self.columns)
        return self.distinct().join(other, on=on, how="left_anti",
                                    null_safe=True)

    # back-compat for the earlier name
    except_all_distinct = except_distinct

    def limit(self, n: int) -> "DataFrame":
        from spark_rapids_tpu.exec.basic import (CpuGlobalLimitExec,
                                                 CpuLimitExec)
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.exec.expand import CpuTakeOrderedAndProjectExec
        from spark_rapids_tpu.exec.sort import CpuSortExec
        from spark_rapids_tpu.plan.partitioning import RangePartitioning
        plan = self._plan
        if isinstance(plan, CpuSortExec) and plan.global_sort:
            # ORDER BY + LIMIT collapses to TakeOrderedAndProject: local
            # top-K replaces the range-partition exchange entirely
            # (reference: the TakeOrderedAndProjectExec rule in GpuOverrides)
            child = plan.children[0]
            if isinstance(child, CpuShuffleExchangeExec) and \
                    isinstance(child.partitioning, RangePartitioning):
                child = child.children[0]
            return DataFrame(
                CpuTakeOrderedAndProjectExec(n, plan.specs, child),
                self._session)
        plan = CpuLimitExec(n, plan)  # local limit per partition
        if self._plan.num_partitions > 1:
            plan = CpuGlobalLimitExec(n, plan)
        return DataFrame(plan, self._session)

    def union(self, other: "DataFrame") -> "DataFrame":
        from spark_rapids_tpu.exec.basic import CpuUnionExec
        return DataFrame(CpuUnionExec([self._plan, other._plan]),
                         self._session)

    def sample(self, fraction: float, seed: int = 0) -> "DataFrame":
        from spark_rapids_tpu.exec.basic import CpuSampleExec
        return DataFrame(CpuSampleExec(fraction, seed, self._plan),
                         self._session)

    def explode(self, column, alias: str = "col", outer: bool = False,
                position: bool = False) -> "DataFrame":
        """One output row per array element; other columns repeat.  With
        ``position`` adds the element ordinal (posexplode); ``outer`` keeps
        null/empty rows (explode_outer)."""
        from spark_rapids_tpu.exec.generate import CpuGenerateExec
        gen = bind_references(_to_expr(column), self.schema)
        self._no_windows(gen, "explode")
        return DataFrame(CpuGenerateExec(gen, self._plan, outer=outer,
                                         position=position,
                                         element_name=alias),
                         self._session)

    def posexplode(self, column, alias: str = "col",
                   outer: bool = False) -> "DataFrame":
        return self.explode(column, alias, outer, position=True)

    def repartition(self, n: int, *cols) -> "DataFrame":
        """Round-robin repartition, or hash repartition when keys given."""
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.plan.partitioning import (HashPartitioning,
                                                        RoundRobinPartitioning)
        if cols:
            keys = [bind_references(_to_expr(c), self.schema) for c in cols]
            part = HashPartitioning(keys, n)
        else:
            part = RoundRobinPartitioning(n)
        return DataFrame(
            CpuShuffleExchangeExec(part, self._plan,
                                   shuffle_env=self._session.shuffle_env),
            self._session)

    def coalesce(self, n: int) -> "DataFrame":
        """Shuffle-free partition merge (Spark coalesce contract)."""
        from spark_rapids_tpu.exec.basic import CpuCoalescePartitionsExec
        return DataFrame(CpuCoalescePartitionsExec(n, self._plan),
                         self._session)

    def _sort_specs(self, cols, kw_ascending):
        from spark_rapids_tpu.exec.sort import SortSpec
        specs = []
        for c in cols:
            if isinstance(c, SortSpec):
                specs.append(SortSpec(
                    bind_references(c.expr, self.schema), c.ascending,
                    c.nulls_first))
            else:
                specs.append(SortSpec(
                    bind_references(_to_expr(c), self.schema), kw_ascending))
        for s in specs:
            self._no_windows(s.expr, "sort keys")
        return specs

    def order_by(self, *cols, ascending: bool = True) -> "DataFrame":
        """Global total-order sort: range-partition then per-partition sort
        (Spark SortExec(global=true) over RangePartitioning)."""
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.exec.sort import CpuSortExec
        from spark_rapids_tpu.plan.partitioning import RangePartitioning
        specs = self._sort_specs(cols, ascending)
        plan = self._plan
        if plan.num_partitions > 1:
            def _is_array(e):
                try:
                    return isinstance(e.data_type, T.ArrayType)
                except Exception:    # noqa: BLE001
                    return False
            if any(_is_array(s.expr) for s in specs):
                # no range-partitioner for array keys (either engine):
                # global sort collapses to one partition instead
                from spark_rapids_tpu.exec.basic import \
                    CpuCoalescePartitionsExec
                plan = CpuCoalescePartitionsExec(1, plan)
            else:
                plan = CpuShuffleExchangeExec(
                    RangePartitioning(specs, plan.num_partitions), plan,
                    shuffle_env=self._session.shuffle_env)
        return DataFrame(CpuSortExec(specs, plan, global_sort=True),
                         self._session)

    sort = order_by

    def sort_within_partitions(self, *cols, ascending: bool = True
                               ) -> "DataFrame":
        from spark_rapids_tpu.exec.sort import CpuSortExec
        return DataFrame(CpuSortExec(self._sort_specs(cols, ascending),
                                     self._plan), self._session)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             condition=None, null_safe: bool = False) -> "DataFrame":
        """Equi-join on column names (USING semantics: key columns emitted
        once), with an optional extra non-equi ``condition`` over the
        combined row; ``on=None`` with a condition = nested-loop join.
        Wrap the right side in functions.broadcast() to force a broadcast
        hash join (reference: GpuBroadcastHashJoinExec rule)."""
        from spark_rapids_tpu.exec.joins import (
            CpuBroadcastNestedLoopJoinExec, _normalize_how)
        from spark_rapids_tpu.expressions.base import BoundReference
        from spark_rapids_tpu.expressions.conditional import Coalesce
        from spark_rapids_tpu.plan.join_selection import plan_equi_join
        import spark_rapids_tpu.ops.join_ops as J
        jt = _normalize_how(how)
        lplan, rplan = self._plan, other._plan
        lschema, rschema = lplan.schema, rplan.schema
        combined = T.StructType(list(lschema.fields) + list(rschema.fields))
        cond = None
        if condition is not None:
            cond = bind_references(_to_expr(condition), combined)
        if on is None or jt == J.CROSS:
            if jt in (J.RIGHT_OUTER, J.FULL_OUTER):
                raise NotImplementedError(
                    f"{jt} without equi-join keys is not supported; "
                    "provide `on` columns")
            plan = CpuBroadcastNestedLoopJoinExec([], [], jt, cond, lplan,
                                                  rplan)
            return DataFrame(plan, self._session)
        names = [on] if isinstance(on, str) else list(on)
        lkeys = [bind_references(col(n), lschema) for n in names]
        rkeys = [bind_references(col(n), rschema) for n in names]
        ns = [null_safe] * len(names)
        plan = plan_equi_join(
            self._session, lplan, rplan, lkeys, rkeys, jt, cond, ns,
            broadcast_right_hint=getattr(other, "_broadcast_hint", False))
        df = DataFrame(plan, self._session)
        if jt in (J.LEFT_SEMI, J.LEFT_ANTI):
            return df
        # USING projection: key cols once (left / right / coalesced per join
        # type, Spark semantics), then remaining left cols, then right cols
        nl = len(lschema.fields)
        out_schema = plan.schema
        key_l = {lschema.field_index(n) for n in names}
        key_r = {rschema.field_index(n) for n in names}
        exprs = []
        for n in names:
            li = lschema.field_index(n)
            ri = nl + rschema.field_index(n)
            lf = out_schema.fields[li]
            rf = out_schema.fields[ri]
            lref = BoundReference(li, lf.data_type, lf.nullable)
            rref = BoundReference(ri, rf.data_type, rf.nullable)
            if jt == J.FULL_OUTER:
                exprs.append(Alias(Coalesce(lref, rref), n))
            elif jt == J.RIGHT_OUTER:
                exprs.append(Alias(rref, n))
            else:
                exprs.append(Alias(lref, n))
        for i, f in enumerate(lschema.fields):
            if i not in key_l:
                of = out_schema.fields[i]
                exprs.append(Alias(
                    BoundReference(i, of.data_type, of.nullable), f.name))
        for i, f in enumerate(rschema.fields):
            if i not in key_r:
                of = out_schema.fields[nl + i]
                exprs.append(Alias(
                    BoundReference(nl + i, of.data_type, of.nullable),
                    f.name))
        from spark_rapids_tpu.exec.basic import CpuProjectExec
        return DataFrame(CpuProjectExec(exprs, plan), self._session)

    def cross_join(self, other: "DataFrame") -> "DataFrame":
        return self.join(other, on=None, how="cross")

    crossJoin = cross_join

    def group_by(self, *cols) -> "GroupedData":
        keys = [self._no_windows(bind_references(_to_expr(c), self.schema),
                                 "grouping keys") for c in cols]
        return GroupedData(self, keys)

    groupBy = group_by

    def rollup(self, *cols) -> "GroupedData":
        """GROUP BY ROLLUP(k1..kn): grouping sets (k1..kn), (k1..kn-1), …, ().
        Physical plan: Expand fan-out + grouping-id key (Spark's lowering)."""
        keys = [self._no_windows(bind_references(_to_expr(c), self.schema),
                                 "grouping keys") for c in cols]
        sets = [tuple(range(i)) for i in range(len(keys), -1, -1)]
        return GroupedData(self, keys, grouping_sets=sets,
                           key_names=[str(c) for c in cols])

    def cube(self, *cols) -> "GroupedData":
        """GROUP BY CUBE(k1..kn): all 2^n grouping sets."""
        import itertools
        keys = [self._no_windows(bind_references(_to_expr(c), self.schema),
                                 "grouping keys") for c in cols]
        idx = range(len(keys))
        sets = []
        for r in range(len(keys), -1, -1):
            sets.extend(itertools.combinations(idx, r))
        return GroupedData(self, keys, grouping_sets=sets,
                           key_names=[str(c) for c in cols])

    def grouping_sets(self, cols, sets) -> "GroupedData":
        """Explicit GROUPING SETS over named key columns; ``sets`` is a list
        of tuples of key names."""
        keys = [self._no_windows(bind_references(_to_expr(c), self.schema),
                                 "grouping keys") for c in cols]
        name_to_idx = {str(c): i for i, c in enumerate(cols)}
        idx_sets = [tuple(sorted(name_to_idx[n] for n in s)) for s in sets]
        return GroupedData(self, keys, grouping_sets=idx_sets,
                           key_names=[str(c) for c in cols])

    def agg(self, *agg_exprs) -> "DataFrame":
        """Global aggregation (no grouping keys)."""
        return GroupedData(self, []).agg(*agg_exprs)

    def distinct(self) -> "DataFrame":
        return self.group_by(*self.columns).agg()

    def map_in_pandas(self, fn, schema: T.StructType) -> "DataFrame":
        """Vectorized python: fn(pandas.DataFrame) -> pandas.DataFrame per
        batch (reference GpuMapInPandasExec; host tier)."""
        from spark_rapids_tpu.exec.python_execs import CpuMapInPandasExec
        return DataFrame(CpuMapInPandasExec(fn, schema, self._plan),
                         self._session)

    def cache(self) -> "DataFrame":
        """Materializes this plan once into compressed parquet-encoded host
        batches (reference: ParquetCachedBatchSerializer); later actions
        scan the cache."""
        from spark_rapids_tpu.io.cache_serializer import CpuCachedScanExec
        executed = self._executed_plan()
        scan = CpuCachedScanExec(self.schema, executed.num_partitions)
        scan.materialize(executed)
        return DataFrame(scan, self._session)

    drop_duplicates = distinct

    # -- actions ------------------------------------------------------------
    def _executed_plan(self) -> Exec:
        overrides = TpuOverrides(self._session.conf)
        plan = overrides.apply(self._plan)
        # an active QueryExecution mirrors the plan it is about to run as
        # its span tree (re-attaching on a speculation replay is fine)
        from spark_rapids_tpu.aux import events as EV
        q = EV.active_query()
        if q is not None:
            q.attach_plan(plan)
        return plan

    def _query_scope(self, description: str):
        """The action's query; the first one adopts the text's planning
        spans."""
        from spark_rapids_tpu.aux.tracing import query_scope
        planned, self._planned = self._planned, ()
        return query_scope(self._session.conf, description, planned)

    def _collect_as(self, convert):
        """Collects and converts (``result.rows``) inside one query, so
        the query's duration is what the client waited."""
        from spark_rapids_tpu.aux.tracing import span
        with self._query_scope("collect"):
            batch = collect_with_speculation(self._session.conf,
                                             self._executed_plan)
            if convert is None:
                return batch
            with span("result.rows"):
                return convert(batch)

    def collect_batch(self) -> HostColumnarBatch:
        return self._collect_as(None)

    def to_pydict(self) -> Dict[str, list]:
        return self._collect_as(lambda batch: batch.to_pydict())

    def to_arrow(self):
        import pyarrow as pa
        return self._collect_as(
            lambda batch: pa.Table.from_batches([batch.to_arrow()]))

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def collect(self) -> List[dict]:
        return self._collect_as(_rows)

    def count(self) -> int:
        from spark_rapids_tpu.aux.tracing import run_span
        from spark_rapids_tpu.columnar.column import sum_counts
        from spark_rapids_tpu.plan.pruning import prune_columns
        # count needs row counts only: prune every column the plan's own
        # filters/keys don't reference, then sum deferred device counts
        # with ONE sync total
        plan = self._plan
        if self._session.conf.get(C.COLUMN_PRUNING_ENABLED.key, True):
            plan = prune_columns(plan, required=set())
        overrides = TpuOverrides(self._session.conf)
        with self._query_scope("count"):
            # already pruned above (with the tighter empty required-set);
            # don't pay a second tree walk inside apply()
            executed = overrides.apply(plan, skip_pruning=True)
            with run_span(executed):
                return sum_counts([b.row_count
                                   for b in executed.execute_all()])

    def write_parquet(self, path: str) -> None:
        from spark_rapids_tpu.aux.tracing import run_span
        from spark_rapids_tpu.io.parquet import write_parquet
        with self._query_scope("write_parquet"):
            plan = self._executed_plan()
            with run_span(plan):
                write_parquet(plan.execute_all(), path, self.schema)

    def write_hive_text(self, path: str, serde=None) -> None:
        """Hive text table write (reference: GpuHiveTextFileFormat)."""
        from spark_rapids_tpu.aux.tracing import run_span
        from spark_rapids_tpu.hive.table import write_hive_text
        with self._query_scope("write_hive_text"):
            plan = self._executed_plan()
            with run_span(plan):
                write_hive_text(plan.execute_all(), path, self.schema,
                                serde=serde)

    @property
    def write(self):
        """Directory-style writer: ``df.write.mode("overwrite").parquet(p)``."""
        from spark_rapids_tpu.io.writer import DataFrameWriter
        return DataFrameWriter(self)

    # -- introspection ------------------------------------------------------
    def explain(self, mode: str = "formatted",
                analyze: bool = False) -> str:
        """Shows CPU plan, TPU-rewritten plan, and fallback reasons
        (reference: ExplainPlan.explainPotentialGpuPlan).

        ``analyze=True`` (Spark's EXPLAIN ANALYZE) EXECUTES the plan under
        a QueryExecution trace and renders the tree annotated with
        per-node rows/batches/opTime plus attributed spill/retry, and the
        query-level task-metric summary."""
        if analyze:
            from spark_rapids_tpu.aux.tracing import QueryExecution
            qe = QueryExecution.from_conf(self._session.conf,
                                          "explain(analyze=True)")
            with qe:
                # joins this QueryExecution via query_scope's
                # already-active path; attach happens in _executed_plan
                self.collect_batch()
            return qe.render_tree()
        overrides = TpuOverrides(self._session.conf)
        final = overrides.apply(self._plan, for_explain=True)
        reasons = overrides.last_meta.explain(all_nodes=True) \
            if overrides.last_meta else ""
        out = (f"== Physical Plan (input) ==\n{self._plan.tree_string()}\n"
               f"== TPU Plan ==\n{final.tree_string()}\n"
               f"== Placement ==\n{reasons}")
        elided = overrides.last_elided
        out += (f"\n== Distribution ==\nexchangeElided={len(elided)}"
                + "".join(f"\n  - {e.desc()}" for e in elided))
        cost = self._cost_section(final)
        if cost:
            out += f"\n{cost}"
        return out

    def _cost_section(self, final: Exec) -> str:
        """Report-only ``== Cost ==`` explain section from the calibrated
        machine profile (``spark.rapids.history.machineProfilePath``,
        produced by ``tools history calibrate``).  Empty string when no
        profile is configured/loadable — explain never fails over it."""
        conf = self._session.conf
        path = conf.get(C.HISTORY_MACHINE_PROFILE_PATH.key)
        if not path or not conf.get(C.HISTORY_COST_MODEL_ENABLED.key):
            return ""
        from spark_rapids_tpu.plan.cost import (load_machine_profile,
                                                predict_plan_costs,
                                                render_cost_section)
        profile = load_machine_profile(path)
        if profile is None:
            return f"== Cost ==\nmachine profile unreadable: {path}"
        try:
            rows = predict_plan_costs(final, profile)
            return render_cost_section(rows, profile)
        except Exception as exc:    # noqa: BLE001 - report-only section
            return f"== Cost ==\nprediction failed: {exc}"

    def __repr__(self):
        return f"DataFrame[{self.schema.simple_name}]"


class DataFrameNaFunctions:
    """df.na.fill / df.na.drop (Spark DataFrameNaFunctions)."""

    def __init__(self, df: DataFrame):
        self._df = df

    def fill(self, value, subset=None) -> DataFrame:
        from spark_rapids_tpu.expressions.conditional import Coalesce
        names = set(subset) if subset is not None else None
        proj = []
        for f in self._df.schema.fields:
            use = names is None or f.name in names
            # bool is an int subclass: check it FIRST so fill(True) only
            # touches boolean columns (Spark semantics)
            if isinstance(value, bool):
                compatible = isinstance(f.data_type, T.BooleanType)
            elif isinstance(value, (int, float)):
                compatible = f.data_type.is_numeric
            elif isinstance(value, str):
                compatible = isinstance(f.data_type, T.StringType)
            else:
                compatible = False
            if use and compatible:
                proj.append(Alias(Coalesce(col(f.name),
                                           lit(value, f.data_type)),
                                  f.name))
            else:
                proj.append(col(f.name))
        return self._df.select(*proj)

    def drop(self, how: str = "any", subset=None) -> DataFrame:
        from spark_rapids_tpu.expressions.conditional import AtLeastNNonNulls
        if how not in ("any", "all"):
            raise ValueError(f"how must be 'any' or 'all', got {how!r}")
        names = list(subset) if subset is not None else self._df.columns
        need = len(names) if how == "any" else 1
        return self._df.filter(
            AtLeastNNonNulls(need, *[col(n) for n in names]))


class GroupedData:
    """df.group_by(keys) -> .agg(...); assembles the two-stage physical
    aggregation (partial -> hash exchange -> final), Spark's
    EnsureRequirements pattern for aggregation."""

    def __init__(self, df: DataFrame, keys, grouping_sets=None,
                 key_names=None):
        self._df = df
        self._keys = keys
        self._grouping_sets = grouping_sets  # list of tuples of key indices
        self._key_names = key_names
        self._pivot = None
        #: expose __grouping_id as the LAST output column (grouping())
        self._keep_gid = False

    def _expand_for_grouping_sets(self):
        """Lowers ROLLUP/CUBE/GROUPING SETS to Expand + regular group-by
        (Spark's rewrite): one projection per grouping set emitting
        [k1-or-null, …, kn-or-null, grouping_id, *child columns]; the
        grouping id joins the keys so a null produced by the rollup never
        merges with a genuine null key from another set."""
        from spark_rapids_tpu.exec.expand import CpuExpandExec
        from spark_rapids_tpu.expressions.base import (BoundReference,
                                                       Literal)
        child = self._df._plan
        schema = child.schema
        nk = len(self._keys)
        key_names = self._key_names or [f"k{i}" for i in range(nk)]
        child_refs = [BoundReference(i, f.data_type, f.nullable, f.name)
                      for i, f in enumerate(schema.fields)]
        projections = []
        for s in self._grouping_sets:
            gid = 0  # Spark semantics: bit i set when key i is NOT grouped
            for i in range(nk):
                if i not in s:
                    gid |= 1 << (nk - 1 - i)
            proj = [self._keys[i] if i in s
                    else Literal(None, self._keys[i].data_type)
                    for i in range(nk)]
            proj.append(Literal(gid, T.LONG))
            proj.extend(child_refs)
            projections.append(proj)
        names = (key_names + ["__grouping_id"]
                 + [f.name for f in schema.fields])
        expand = CpuExpandExec(projections, names, child)
        # re-key on the expanded columns: keys + grouping id
        new_keys = [_bound_ref(i, expand.schema) for i in range(nk + 1)]
        # aggregate inputs shift past the nk+1 key columns
        shift = nk + 1

        def rebind(e):
            def fix(node):
                if isinstance(node, BoundReference):
                    return BoundReference(node.ordinal + shift,
                                          node.data_type, node.nullable,
                                          node.ref_name)
                return node
            return e.transform_up(fix)
        return expand, new_keys, rebind, nk

    def agg(self, *agg_exprs) -> "DataFrame":
        from spark_rapids_tpu.exec.aggregate import (COMPLETE, FINAL,
                                                     PARTIAL,
                                                     CpuHashAggregateExec)
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.expressions.aggregates import (
            AggregateExpression, AggregateFunction)
        from spark_rapids_tpu.expressions.python_udf import PandasUDFCall
        from spark_rapids_tpu.plan.partitioning import (HashPartitioning,
                                                        SinglePartitioning)
        schema = self._df.schema
        pandas_calls = [e for e in agg_exprs if isinstance(
            e.children[0] if isinstance(e, Alias) else e, PandasUDFCall)]
        if pandas_calls:
            if len(pandas_calls) != len(agg_exprs):
                raise TypeError("pandas-UDF aggregations cannot mix with "
                                "builtin aggregates in one agg()")
            return self._agg_in_pandas(agg_exprs)
        raw = []
        for e in agg_exprs:
            name = None
            if isinstance(e, Alias):
                name, e = e.alias_name, e.children[0]
            if not isinstance(e, AggregateFunction):
                raise TypeError(f"not an aggregate expression: {e}")
            e = bind_references(e, schema)
            DataFrame._no_windows(e, "aggregations")
            raw.append((e, name))
        if self._pivot is not None:
            # pivot lowering: one conditional aggregate per (value, agg) —
            # agg inputs null out where the pivot column != value
            from spark_rapids_tpu.expressions.conditional import If
            from spark_rapids_tpu.expressions.predicates import EqualTo
            pc, values = self._pivot
            pivoted = []
            for v in values:
                cond = EqualTo(pc, lit(v))
                for e, name in raw:
                    import copy
                    pe = copy.copy(e)
                    pe.children = [
                        If(cond, c, Literal(None, c.data_type))
                        for c in e.children]
                    label = f"{v}" if len(raw) == 1 else                         f"{v}_{name or e.sql()}"
                    pivoted.append((pe, label))
            raw = pivoted
        aggs = [AggregateExpression(e, name or e.sql())
                for e, name in raw]
        child = self._df._plan
        if self._grouping_sets is not None:
            return self._agg_grouping_sets(aggs)
        if any(a.func.requires_complete for a in aggs):
            # variable-length-state aggregates (collect/percentile): hash
            # shuffle the RAW rows by key, then one COMPLETE pass per
            # partition (Spark's ObjectHashAggregate pattern)
            nk = len(self._keys)
            if child.num_partitions > 1 and nk:
                part = HashPartitioning(self._keys, child.num_partitions)
                child = CpuShuffleExchangeExec(
                    part, child, shuffle_env=self._df._session.shuffle_env)
            elif child.num_partitions > 1:
                from spark_rapids_tpu.exec.basic import \
                    CpuCoalescePartitionsExec
                child = CpuCoalescePartitionsExec(1, child)
            return DataFrame(
                CpuHashAggregateExec(self._keys, aggs, COMPLETE, child),
                self._df._session)
        if child.num_partitions == 1:
            plan = CpuHashAggregateExec(self._keys, aggs, COMPLETE, child)
        else:
            partial = CpuHashAggregateExec(self._keys, aggs, PARTIAL, child)
            nk = len(self._keys)
            if nk:
                key_refs = [_bound_ref(i, partial.schema) for i in range(nk)]
                part = HashPartitioning(key_refs, child.num_partitions)
            else:
                part = SinglePartitioning()
            exchange = CpuShuffleExchangeExec(
                part, partial, shuffle_env=self._df._session.shuffle_env)
            final_keys = [_bound_ref(i, partial.schema) for i in range(nk)]
            plan = CpuHashAggregateExec(final_keys, aggs, FINAL, exchange)
        return DataFrame(plan, self._df._session)

    def _agg_grouping_sets(self, aggs) -> "DataFrame":
        from spark_rapids_tpu.exec.aggregate import (COMPLETE, FINAL,
                                                     PARTIAL,
                                                     CpuHashAggregateExec)
        from spark_rapids_tpu.exec.basic import CpuProjectExec
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.expressions.aggregates import AggregateExpression
        from spark_rapids_tpu.plan.partitioning import HashPartitioning
        expand, new_keys, rebind, nk = self._expand_for_grouping_sets()
        aggs = [AggregateExpression(rebind(a.func), a.out_name)
                for a in aggs]
        if expand.num_partitions == 1:
            plan = CpuHashAggregateExec(new_keys, aggs, COMPLETE, expand)
        else:
            partial = CpuHashAggregateExec(new_keys, aggs, PARTIAL, expand)
            key_refs = [_bound_ref(i, partial.schema)
                        for i in range(len(new_keys))]
            exchange = CpuShuffleExchangeExec(
                HashPartitioning(key_refs, expand.num_partitions), partial,
                shuffle_env=self._df._session.shuffle_env)
            final_keys = [_bound_ref(i, partial.schema)
                          for i in range(len(new_keys))]
            plan = CpuHashAggregateExec(final_keys, aggs, FINAL, exchange)
        # drop the internal grouping id: keys, then agg outputs — unless
        # grouping() needs it, in which case it rides LAST so key/agg
        # ordinal math stays unchanged
        out = [_bound_ref(i, plan.schema) for i in range(nk)]
        out += [_bound_ref(i, plan.schema)
                for i in range(nk + 1, len(plan.schema.fields))]
        if self._keep_gid:
            out.append(Alias(_bound_ref(nk, plan.schema), "__grouping_id"))
        return DataFrame(CpuProjectExec(out, plan), self._df._session)

    def pivot(self, pivot_col, values) -> "GroupedData":
        """df.group_by(k).pivot(c, [v1, v2]).agg(sum(x)): each pivot value
        becomes a column via conditional aggregation (Spark's pivot
        lowering: agg(expr WHERE c == v) per value)."""
        if self._grouping_sets is not None:
            raise ValueError("pivot cannot follow rollup/cube")
        pc = bind_references(_to_expr(pivot_col), self._df.schema)
        out = GroupedData(self._df, self._keys)
        out._pivot = (pc, list(values))
        return out

    _pivot = None

    def _shuffled_child(self):
        """Child hash-partitioned by the grouping keys (the raw-row
        shuffle every grouped pandas exec needs)."""
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.plan.partitioning import HashPartitioning
        child = self._df._plan
        if child.num_partitions > 1 and self._keys:
            child = CpuShuffleExchangeExec(
                HashPartitioning(self._keys, child.num_partitions), child,
                shuffle_env=self._df._session.shuffle_env)
        return child

    def _grouping_key_names(self):
        """Plain-column key names; grouped pandas execs group the pandas
        frame BY NAME, so expression keys cannot be honored (clean
        planning-time error instead of a KeyError mid-execution)."""
        names = []
        for k in self._keys:
            name = getattr(k, "ref_name", None)
            if not name:
                raise ValueError(
                    f"grouped pandas operations require plain column "
                    f"grouping keys, got expression {k.sql()!r}; project "
                    "it into a column first")
            names.append(name)
        return names

    def _pandas_udf_specs(self, agg_exprs):
        """[(out_name, fn, bound input exprs, dtype)] from
        Alias(PandasUDFCall)/PandasUDFCall aggregates."""
        from spark_rapids_tpu.expressions.python_udf import PandasUDFCall
        schema = self._df.schema
        udfs = []
        for i, e in enumerate(agg_exprs):
            name = None
            if isinstance(e, Alias):
                name, e = e.alias_name, e.children[0]
            assert isinstance(e, PandasUDFCall)
            bound = bind_references(e, schema)
            udfs.append((name or bound.sql(), bound.fn,
                         list(bound.children), bound.data_type))
        return udfs

    def _agg_in_pandas(self, agg_exprs) -> "DataFrame":
        """group_by(keys).agg(pandas_udf(...)(col)): one output row per
        group (reference GpuAggregateInPandasExec)."""
        from spark_rapids_tpu.exec.python_execs import \
            CpuAggregateInPandasExec
        if self._grouping_sets is not None:
            raise ValueError("pandas-UDF aggregation cannot follow "
                             "rollup/cube")
        return DataFrame(
            CpuAggregateInPandasExec(self._grouping_key_names(),
                                     self._pandas_udf_specs(agg_exprs),
                                     self._shuffled_child()),
            self._df._session)

    def window_in_pandas(self, *agg_exprs) -> "DataFrame":
        """Whole-partition pandas UDFs appended as columns, one value per
        group broadcast to its rows (reference GpuWindowInPandasExec's
        unbounded-frame shape)."""
        from spark_rapids_tpu.exec.python_execs import CpuWindowInPandasExec
        if self._grouping_sets is not None:
            raise ValueError("window_in_pandas cannot follow rollup/cube")
        return DataFrame(
            CpuWindowInPandasExec(self._grouping_key_names(),
                                  self._pandas_udf_specs(agg_exprs),
                                  self._shuffled_child()),
            self._df._session)

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """pyspark parity: df.group_by(k).cogroup(df2.group_by(k))
        .apply_in_pandas(fn, schema)."""
        return CoGroupedData(self, other)

    def apply_in_pandas(self, fn, schema: T.StructType) -> "DataFrame":
        """Grouped pandas apply: shuffle raw rows by the keys, then
        fn(group_pdf) -> pdf per group (reference
        GpuFlatMapGroupsInPandasExec)."""
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.exec.python_execs import \
            CpuFlatMapGroupsInPandasExec
        from spark_rapids_tpu.plan.partitioning import HashPartitioning
        if self._grouping_sets is not None:
            raise ValueError("apply_in_pandas cannot follow rollup/cube")
        child = self._df._plan
        key_names = self._grouping_key_names()
        if child.num_partitions > 1 and self._keys:
            child = CpuShuffleExchangeExec(
                HashPartitioning(self._keys, child.num_partitions), child,
                shuffle_env=self._df._session.shuffle_env)
        return DataFrame(
            CpuFlatMapGroupsInPandasExec(key_names, fn, schema, child),
            self._df._session)

    # sugar
    def count(self) -> "DataFrame":
        from spark_rapids_tpu.expressions.aggregates import Count
        return self.agg(Alias(Count(lit(1)), "count"))

    def sum(self, *cols) -> "DataFrame":
        from spark_rapids_tpu.expressions.aggregates import Sum
        return self.agg(*[Alias(Sum(_to_expr(c)), f"sum({c})")
                          for c in cols])

    def avg(self, *cols) -> "DataFrame":
        from spark_rapids_tpu.expressions.aggregates import Average
        return self.agg(*[Alias(Average(_to_expr(c)), f"avg({c})")
                          for c in cols])

    def min(self, *cols) -> "DataFrame":
        from spark_rapids_tpu.expressions.aggregates import Min
        return self.agg(*[Alias(Min(_to_expr(c)), f"min({c})")
                          for c in cols])

    def max(self, *cols) -> "DataFrame":
        from spark_rapids_tpu.expressions.aggregates import Max
        return self.agg(*[Alias(Max(_to_expr(c)), f"max({c})")
                          for c in cols])


class CoGroupedData:
    """Two grouped frames co-grouped by their keys (reference:
    GpuFlatMapCoGroupsInPandasExec)."""

    def __init__(self, left: "GroupedData", right: "GroupedData"):
        if len(left._keys) != len(right._keys):
            raise ValueError("cogroup requires the same number of keys on "
                             "both sides")
        self._left = left
        self._right = right

    def apply_in_pandas(self, fn, schema: T.StructType) -> "DataFrame":
        from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
        from spark_rapids_tpu.exec.python_execs import \
            CpuFlatMapCoGroupsInPandasExec
        from spark_rapids_tpu.plan.partitioning import HashPartitioning
        lplan = self._left._df._plan
        rplan = self._right._df._plan
        n = max(lplan.num_partitions, rplan.num_partitions)
        senv = self._left._df._session.shuffle_env
        if n > 1:
            lplan = CpuShuffleExchangeExec(
                HashPartitioning(self._left._keys, n), lplan,
                shuffle_env=senv)
            rplan = CpuShuffleExchangeExec(
                HashPartitioning(self._right._keys, n), rplan,
                shuffle_env=senv)
        return DataFrame(
            CpuFlatMapCoGroupsInPandasExec(
                self._left._grouping_key_names(),
                self._right._grouping_key_names(),
                fn, schema, lplan, rplan),
            self._left._df._session)


def _bound_ref(i: int, schema: T.StructType):
    f = schema.fields[i]
    from spark_rapids_tpu.expressions.base import BoundReference
    return Alias(BoundReference(i, f.data_type, f.nullable), f.name)
