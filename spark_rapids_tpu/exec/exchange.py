"""Shuffle exchange.

Reference: GpuShuffleExchangeExecBase.scala (device-side partition slicing,
GpuPartitioning.scala:37) + RapidsShuffleInternalManagerBase.scala (writer
materializes per-reduce-partition blocks; reader fetches + concatenates) +
ShuffleBufferCatalog (shuffle payloads tracked spillable).

In-process redesign: the "transport" collapses to a per-exec shuffle store.
The host exchange keeps spillable host batches grouped by reduce partition
(host-staged shuffle = the reference's default mode, which serializes
batches to host via JCudfSerialization).  The device exchange keeps ONE
piece a map batch in HBM, its rows stable-sorted by reduce partition id
with the counts a partition beside it (Spark's sort shuffle: one data
file a map task and an index of offsets), and a reduce read gathers a
row range of each piece into one batch at the bucket of what it holds.
The multi-node design (ICI all-to-all within a slice, host-staged DCN
across) plugs in behind the same exec via the parallel/ package.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from spark_rapids_tpu.aux.tracing import add_count, span, span_pulls
from spark_rapids_tpu.columnar.batch import ColumnarBatch, HostColumnarBatch
from spark_rapids_tpu.plan.base import Exec, UnaryExec
from spark_rapids_tpu.plan.partitioning import (Partitioning,
                                                RangePartitioning,
                                                RoundRobinPartitioning)


def _sample_bounds(part: RangePartitioning, sample_rows, to_host_batch):
    """Computes n-1 range bounds from sampled key rows (reference:
    GpuRangePartitioner.createRangeBounds — sample, sort, pick evenly)."""
    from spark_rapids_tpu.exec.sort import CpuSortExec
    from spark_rapids_tpu.exec.basic import CpuInMemoryScanExec
    from spark_rapids_tpu.columnar.batch import concat_host_batches
    n = part.num_partitions
    if not sample_rows:
        return HostColumnarBatch([], 0, [])
    sample = concat_host_batches(sample_rows)
    # sort the sample by the specs over the *key* columns (already projected)
    from spark_rapids_tpu.exec.sort import SortSpec
    from spark_rapids_tpu.expressions.base import BoundReference
    key_specs = [SortSpec(BoundReference(i, sample.columns[i].data_type, True),
                          s.ascending, s.effective_nulls_first)
                 for i, s in enumerate(part.specs)]
    scan = CpuInMemoryScanExec([[sample]], sample.schema)
    sorted_sample = next(iter(CpuSortExec(key_specs, scan)
                              .execute_partition(0)))
    cnt = sorted_sample.row_count
    idx = [min(cnt - 1, (j + 1) * cnt // n) for j in range(n - 1)]
    # dedupe equal bounds is unnecessary: equal bounds just yield empty parts
    rows = [sorted_sample.slice(i, 1) for i in idx]
    from spark_rapids_tpu.columnar.batch import concat_host_batches as cc
    return cc(rows) if rows else HostColumnarBatch([], 0, [])


def _note_written(pieces: int, rows: int, padded: int) -> None:
    """What a map task stored or staged, on the active query's summary:
    pieces, their live rows, and their rows with the padding."""
    add_count("exchange_pieces", pieces)
    add_count("exchange_rows", rows)
    add_count("exchange_rows_padded", padded)


def _batch_sig(b: ColumnarBatch) -> tuple:
    from spark_rapids_tpu.ops.batch_ops import _col_sig
    return tuple((str(c.data_type),) + _col_sig(c) for c in b.columns)


def _planes(b: ColumnarBatch) -> list:
    return [(c.data, c.validity, c.lengths, c.elem_valid) for c in b.columns]


def partition_ids(part: Partitioning, batch: ColumnarBatch, n: int):
    """int32 reduce partition id of every row of ``batch`` as ONE program
    (kind ``exchange.pid``).  Padding rows carry the id ``n``."""
    from spark_rapids_tpu.columnar.column import (DeferredCount,
                                                  DeviceColumn, _jnp,
                                                  rc_traceable)
    from spark_rapids_tpu.columnar.encoding import (batch_has_encoded,
                                                    materialize_batch)
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    from spark_rapids_tpu.plan.pruning import _refs
    jnp = _jnp()
    if batch_has_encoded(batch):
        # the ids hash values, not codes: decode the columns they read
        refs: set = set()
        for e in part.exprs:
            _refs(e, refs)
        batch = materialize_batch(batch, ordinals=sorted(refs),
                                  site="operator")
    src, more = part.pid_inputs(batch, "exchange.pid")

    def rebuilt(like_types, arrs, rc):
        cols = [DeviceColumn(d, v, rc, dt, lengths=ln, elem_valid=ev)
                for (d, v, ln, ev), dt in zip(arrs, like_types)]
        return ColumnarBatch(cols, rc)

    types = [c.data_type for c in src.columns]
    more_types = [[c.data_type for c in m.columns] for m in more]
    more_rows = [int(m.row_count) for m in more]    # host batches uploaded
    key = (part.program_key(), n, _batch_sig(src),
           tuple((r, _batch_sig(m)) for r, m in zip(more_rows, more)))

    def build():
        def run(arrs, rc, more_arrs):
            return part.pids_from(
                rebuilt(types, arrs, DeferredCount(rc)),
                *[rebuilt(t, a, r)
                  for t, a, r in zip(more_types, more_arrs, more_rows)])
        return run

    fn = get_or_build("exchange.pid", key, build)
    return fn(_planes(src), jnp.asarray(rc_traceable(src.row_count)),
              [_planes(m) for m in more])


def sort_by_partition(batch: ColumnarBatch, pids, n: int):
    """``batch``'s rows ordered by reduce partition id, stable (a
    partition's rows keep the map batch's order; padding rows, id ``n``,
    come last), at the batch's bucket, and the ``n + 1`` counts of the ids
    as a device array: ONE program (kind ``exchange.sort``) of one packed
    32-bit sort of (id, row position) and one gather a plane.  The layout
    is Spark's sort shuffle's: one data file a map task ordered by reduce
    partition, and an index of offsets.  Dictionary code planes move like
    any int plane; RLE materializes first."""
    from spark_rapids_tpu.columnar.column import _jnp
    from spark_rapids_tpu.columnar.encoding import (materialize_rle_batch,
                                                    rewrap_like)
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    from spark_rapids_tpu.ops.sort_ops import lex_sort_perm
    jnp = _jnp()
    batch = materialize_rle_batch(batch)
    bucket = int(pids.shape[0])

    def build():
        def run(arrs, pids):
            perm = lex_sort_perm(
                [(pids.astype(np.uint32), n.bit_length())], bucket, jnp)

            def move(plane):
                return None if plane is None else \
                    jnp.take(plane, perm, axis=0)

            # counted by comparison: a ``bincount`` is a scatter-add, 57 ns
            # an id on the chip (1.86 ms a 32,768-row batch; PERF.md
            # section 6, PR 38)
            ids = jnp.arange(n + 1, dtype=pids.dtype)
            counts = jnp.sum(pids[:, None] == ids[None, :], axis=0,
                             dtype=np.int32)
            return ([tuple(move(x) for x in planes) for planes in arrs],
                    counts)
        return run

    fn = get_or_build("exchange.sort", (n, bucket, _batch_sig(batch)), build)
    outs, counts = fn(_planes(batch), pids)
    rc = batch.row_count
    cols = [rewrap_like(c, d, v, rc, ln, ev)
            for (d, v, ln, ev), c in zip(outs, batch.columns)]
    return ColumnarBatch(cols, rc, batch.names), counts


#: pieces one read program takes; a map side of more is read in groups
#: whose outputs, sorted pieces themselves, are read again
_READ_GROUP = 32


@dataclasses.dataclass
class _SortedPiece:
    """A map batch as the exchange keeps it: rows ordered by reduce
    partition id.  ``batch`` is on the device at the map batch's bucket, or
    on the host at its live rows (the staged fallback); ``counts`` are its
    rows a reduce partition, on the device until the map side's one fetch
    (:meth:`_SortedStore.learn`); ``starts``, their running sum from 0
    (``n + 1`` entries), is what the host reads ranges by from then on."""
    batch: object
    counts: object = None
    starts: Optional[np.ndarray] = None

    def settle(self, counts, n: int) -> None:
        """Takes the fetched counts (of the ids 0 to ``n``: the last is
        the padding's)."""
        self.counts = None
        self.starts = np.concatenate(
            ([0], np.cumsum(np.asarray(counts[:n], dtype=np.int64))))

    @property
    def on_device(self) -> bool:
        return isinstance(self.batch, ColumnarBatch)

    def rows(self, start: int = 0, end: Optional[int] = None) -> int:
        """Live rows of the reduce partitions ``[start, end)``."""
        end = len(self.starts) - 1 if end is None else end
        return int(self.starts[end] - self.starts[start])

    def row_bytes(self) -> float:
        rows = self.batch.bucket if self.on_device else self.batch.row_count
        return self.batch.nbytes() / max(rows, 1)


class _SortedStore:
    """The device shuffle's store: ONE piece a map batch, in map order.  A
    reduce read is a row range of each piece."""

    def __init__(self, n: int, pieces: List[_SortedPiece]):
        self.n = n
        self.pieces = pieces

    def learn(self) -> None:
        """The map side's ONE fetch: the count vectors of all device
        pieces together (the staged ones' came with their download).
        Teaches every map batch its row count, brings the pieces to one
        device and one encoding a column for the reads (a mesh's shards
        hand on batches committed to their own devices), and notes what
        was stored."""
        from spark_rapids_tpu.columnar.column import (fetch_stacked,
                                                      learn_count)
        from spark_rapids_tpu.columnar.encoding import align_batches
        from spark_rapids_tpu.ops.batch_ops import _align_batch_devices
        device = [p for p in self.pieces if p.on_device]
        got = fetch_stacked([p.counts for p in device],
                            site="count-force-batch")
        for p, counts in zip(device, got):
            p.settle(counts, self.n)
            learn_count(p.batch.row_count, p.rows())
        together = align_batches(
            _align_batch_devices([p.batch for p in device]), site="exchange")
        for p, b in zip(device, together):
            p.batch = b
        _note_written(len(device), sum(p.rows() for p in device),
                      sum(p.batch.bucket for p in device))

    # -- what the adaptive reader sizes its specs by -------------------------
    def partition_sizes(self) -> List[int]:
        """Live bytes a reduce partition."""
        sizes = np.zeros(self.n)
        for p in self.pieces:
            sizes += np.diff(p.starts) * p.row_bytes()
        return [int(x) for x in sizes]

    def piece_sizes(self, pidx: int) -> List[int]:
        """Live bytes of reduce partition ``pidx`` a piece, in map order."""
        return [int(p.rows(pidx, pidx + 1) * p.row_bytes())
                for p in self.pieces]

    # -- reduce side ---------------------------------------------------------
    def read(self, start: int, end: int, pieces=None):
        """The rows of the reduce partitions ``[start, end)`` (of the
        pieces ``pieces[0]`` up to ``pieces[1]`` alone, a skewed
        partition's run of map batches): what the device pieces hold as
        ONE batch at the bucket of its rows, partition by partition and
        within a partition in map order, then a slice of each staged piece,
        uploaded.  A range that holds no row yields nothing."""
        from spark_rapids_tpu.exec.basic import upload_batches
        chosen = self.pieces if pieces is None else \
            self.pieces[pieces[0]:pieces[1]]
        device = [p for p in chosen if p.on_device]
        while len(device) > _READ_GROUP:
            groups = [device[i:i + _READ_GROUP]
                      for i in range(0, len(device), _READ_GROUP)]
            device = [m for m in (_read_rows(g, start, end, self.n)
                                  for g in groups) if m is not None]
        got = _read_rows(device, start, end, self.n) if device else None
        if got is not None:
            yield got.batch
        staged = [p.batch.slice(int(p.starts[start]), p.rows(start, end))
                  for p in chosen
                  if not p.on_device and p.rows(start, end)]
        if staged:
            yield from upload_batches(staged)


def _read_rows(pieces: List[_SortedPiece], start: int, end: int,
               n: int) -> Optional[_SortedPiece]:
    """The rows that device ``pieces`` hold of the reduce partitions
    ``[start, end)``, as one sorted piece at the bucket of those rows: ONE
    program (kind ``exchange.read``).  Its key is shapes alone (the
    pieces' signatures, ``n``, the output bucket); the pieces' starts and
    the range are run-time arguments.  A run is one (partition, piece)
    pair's rows; the runs are laid partition by partition, so a read of
    ``[start, end)`` is the single-partition reads one after another.  For
    output row r the run is found from the runs' offsets by a histogram
    and its running sum (``expand_positions``), the source row is the
    run's first row plus r less the run's offset, and every plane is
    gathered once, at the output bucket, from the pieces' planes laid end
    to end: the cost follows what is read, not what was stored."""
    from spark_rapids_tpu.columnar.column import bucket_rows
    total = sum(p.rows(start, end) for p in pieces)
    if total == 0:
        return None
    out_bucket = bucket_rows(total)
    add_count("exchange_read_rows_padded", out_bucket)
    first = pieces[0].batch
    merged = _SortedPiece(
        _gather_rows(pieces, start, end, n, total, out_bucket)
        if first.columns else ColumnarBatch([], total, first.names))
    live = np.zeros(n, dtype=np.int64)
    for p in pieces:
        live[start:end] += np.diff(p.starts)[start:end]
    merged.settle(live, n)
    return merged


def _gather_rows(pieces: List[_SortedPiece], start: int, end: int, n: int,
                 total: int, out_bucket: int) -> ColumnarBatch:
    """:func:`_read_rows`' program and its call: the ``total`` rows of
    ``[start, end)`` at ``out_bucket``."""
    from spark_rapids_tpu.columnar.column import _jnp
    from spark_rapids_tpu.columnar.encoding import rewrap_like
    from spark_rapids_tpu.exec.stage_compiler import get_or_build
    from spark_rapids_tpu.ops.batch_ops import expand_positions, prefix_sum
    first = pieces[0].batch
    jnp = _jnp()
    k = len(pieces)
    buckets = [p.batch.bucket for p in pieces]
    #: a piece's first row in the planes laid end to end; a row there is
    #: addressed in 32 bits wherever the pieces' buckets allow it
    index = np.int32 if sum(buckets) < 1 << 31 else np.int64
    bases = np.concatenate(([0], np.cumsum(buckets)[:-1])).astype(index)
    ncols = len(first.columns)
    # per-column max string/array width across pieces
    widths = [max((p.batch.columns[ci].data.shape[1] for p in pieces
                   if p.batch.columns[ci].lengths is not None), default=0)
              for ci in range(ncols)]

    def build():
        def run(all_arrs, starts, lo, hi):
            # starts: int32[k, n + 1]; a run (partition p, piece i) sits at
            # p * k + i
            part = jnp.arange(n, dtype=np.int32)
            wanted = (part >= lo) & (part < hi)
            counts = jnp.where(wanted[None, :],
                               starts[:, 1:] - starts[:, :-1], 0)
            counts = counts.T.reshape(n * k)
            offsets = prefix_sum(counts, jnp) - counts
            source = (starts[:, :-1] + bases[:, None]).T.reshape(n * k)
            r = jnp.arange(out_bucket, dtype=np.int32)
            run_of = expand_positions(offsets, out_bucket, jnp)
            alive = r < jnp.sum(counts)
            src = jnp.where(
                alive, r + jnp.take(source - offsets, run_of, axis=0),
                0).astype(index)

            def move(planes, width=None):
                if planes[0] is None:
                    return None
                if width is not None:
                    planes = [jnp.pad(x, ((0, 0), (0, width - x.shape[1])))
                              if x.shape[1] < width else x for x in planes]
                return jnp.take(jnp.concatenate(planes, axis=0), src,
                                axis=0)

            outs = []
            for ci in range(ncols):
                d, v, ln, ev = zip(*(arrs[ci] for arrs in all_arrs))
                w = widths[ci] if ln[0] is not None else None
                outs.append((move(d, w), move(v) & alive, move(ln),
                             move(ev, w)))
            return outs
        return run

    fn = get_or_build(
        "exchange.read",
        (n, out_bucket, tuple(_batch_sig(p.batch) for p in pieces)), build)
    starts = np.stack([p.starts for p in pieces]).astype(np.int32)
    outs = fn([_planes(p.batch) for p in pieces], starts, np.int32(start),
              np.int32(end))
    return ColumnarBatch(
        [rewrap_like(c, d, v, total, ln, ev)
         for (d, v, ln, ev), c in zip(outs, first.columns)],
        total, first.names)


#: defaults for the round-5 shuffle knobs; the convert-time conf values
#: travel on each exchange INSTANCE (per-query conf must ride the plan,
#: not the process — concurrent sessions share this module)
SHRINK_THRESHOLD_BYTES = 64 << 20
RANGE_BOUNDS_SAMPLE_ROWS = 1024
COLLECTIVE_ENABLED = True


class _LazyPartitions:
    """Reduce-side view over mode-specific storage: partitions fetch on
    first access (the reduce task's fetch) and cache for re-execution.
    Distinct partitions fetch CONCURRENTLY (the lock guards only the
    bookkeeping, never the fetch itself — serializing fetches would undo
    the task pool's host-I/O overlap); a duplicate request for an
    in-flight partition waits for the first fetch instead of repeating
    it."""

    def __init__(self, n: int, fetch):
        import threading
        self._n = n
        self._fetch = fetch
        self._cache: Dict[int, List] = {}
        self._inflight: Dict[int, "threading.Event"] = {}
        self._lock = threading.Lock()
        self._bg = None

    #: optional callback fired once every partition has been fetched
    #: (storage can be released; results stay in the cache)
    on_all_fetched = None

    def __getitem__(self, pidx: int):
        import threading
        with self._lock:
            if pidx in self._cache:
                return self._cache[pidx]
            ev = self._inflight.get(pidx)
            if ev is None:
                ev = self._inflight[pidx] = threading.Event()
            else:
                ev = (ev, "waiter")
        if isinstance(ev, tuple):
            # blocking on another thread's in-flight fetch: drop device
            # admission first (the fetcher may be a bare warm thread whose
            # CACHED-mode map re-run needs a permit — holding ours while
            # waiting on it would deadlock the semaphore); re-acquired
            # lazily at the next device section / spool dequeue
            from spark_rapids_tpu.plan.base import \
                release_semaphore_for_wait
            release_semaphore_for_wait()
            ev[0].wait()
            return self[pidx]   # cached now; re-fetches if the owner failed
        try:
            res = self._fetch(pidx)
        except BaseException:
            with self._lock:       # let a later caller retry the fetch
                self._inflight.pop(pidx, None)
            ev.set()
            raise
        cb = None
        with self._lock:
            self._cache[pidx] = res
            self._inflight.pop(pidx, None)
            if len(self._cache) == self._n and \
                    self.on_all_fetched is not None:
                cb, self.on_all_fetched = self.on_all_fetched, None
        ev.set()
        if cb is not None:
            cb()
        return res

    def __len__(self):
        return self._n

    def prefetch(self, pidx: int) -> None:
        """Asynchronously warms ``pidx`` (pipelined shuffle read: the next
        reduce partition's frames fetch/deserialize while the current one
        is joined/aggregated).  At most ONE background fetch runs per
        store; errors are swallowed — the consumer's own access retries
        through the normal failure path, so a failed warm can neither
        poison the cache nor double-report a fault."""
        import contextvars
        import threading
        if pidx < 0 or pidx >= self._n:
            return
        with self._lock:
            if pidx in self._cache or pidx in self._inflight:
                return
            bg = self._bg
            if bg is not None and bg.is_alive():
                return

            def warm():
                try:
                    self[pidx]
                except BaseException:   # noqa: BLE001 - see docstring
                    pass
                finally:
                    # a CACHED-mode short fetch re-runs map tasks whose
                    # device sections acquire admission under THIS
                    # thread's identity; no task-completion listener
                    # covers a warm thread, so drop any hold ourselves
                    # (a leaked holder entry would pin a permit forever)
                    from spark_rapids_tpu.memory.device_manager import \
                        get_runtime
                    rt = get_runtime()
                    if rt is not None:
                        rt.semaphore.release_all()

            # carry the active query context so fetch events attribute
            ctx = contextvars.copy_context()
            t = threading.Thread(target=ctx.run, args=(warm,),
                                 name="tpu-prefetch-shuffle", daemon=True)
            self._bg = t
            # started INSIDE the lock: a not-yet-started thread reads as
            # not alive, and a concurrent prefetch would slip past the
            # single-flight guard (the warm itself blocks on this lock
            # only momentarily at its own bookkeeping)
            t.start()


class CpuShuffleExchangeExec(UnaryExec):
    """Host shuffle: materializes the map side once into a store of host
    batches grouped by reduce partition.  The storage/fetch path is chosen
    by ``spark.rapids.shuffle.mode`` (GpuShuffleEnv analog): DEFAULT
    in-memory store, MULTITHREADED spill-file writer/reader pools, CACHED
    catalog + client/server transport."""

    def __init__(self, partitioning: Partitioning, child: Exec,
                 shuffle_env=None):
        super().__init__(child)
        self.partitioning = partitioning
        #: the owning session's ShuffleEnv; None falls back to the
        #: process-wide env (standalone plan construction)
        self.shuffle_env = shuffle_env
        self._store: Optional[List[List]] = None

    @property
    def num_partitions(self):
        return self.partitioning.num_partitions

    # -- map side -----------------------------------------------------------
    def _split_pairs(self, hb: HostColumnarBatch, pids: np.ndarray, n: int):
        """Splits one batch into (reduce_partition, sub_batch) pairs."""
        import pyarrow as pa
        from spark_rapids_tpu.columnar.batch import batch_from_arrow
        order = np.argsort(pids, kind="stable")
        counts = np.bincount(pids, minlength=n)
        tab = pa.Table.from_batches([hb.to_arrow()]).take(pa.array(order))
        off = 0
        out = []
        for p in range(n):
            if counts[p]:
                out.append((p, batch_from_arrow(tab.slice(off, counts[p]))))
            off += counts[p]
        return out

    def _map_pairs(self, mp: int, n: int):
        from spark_rapids_tpu.plan.base import closing_source
        part = self.partitioning
        if isinstance(part, RoundRobinPartitioning):
            part = RoundRobinPartitioning(n, start=mp)
        # early exit (a stopped map task) must close the child chain
        # deterministically — queued spillables/prefetch threads upstream
        # release now, not at GC
        with closing_source(self.child.execute_partition(mp)) as it:
            for hb in it:
                with span("exchange.write", map=mp):
                    pids = part.partition_ids_cpu(hb)
                    pairs = self._split_pairs(hb, pids, n)
                    rows = sum(sub.row_count for _, sub in pairs)
                    _note_written(len(pairs), rows, rows)
                yield from pairs

    def _materialize(self):
        if self._store is not None:
            return
        add_count("exchanges", 1)
        part = self.partitioning
        n = part.num_partitions
        if isinstance(part, RangePartitioning) and part.bounds is None:
            self._compute_bounds()
        from spark_rapids_tpu.shuffle.env import get_shuffle_env
        env = self.shuffle_env or get_shuffle_env()
        mode = env.mode if env is not None else "DEFAULT"
        if mode == "MULTITHREADED":
            self._store = self._materialize_multithreaded(env, n)
            return
        if mode == "CACHED":
            self._store = self._materialize_cached(env, n)
            return
        from spark_rapids_tpu.plan.base import (iter_partition_tasks,
                                                run_task_iter)
        store: List[List] = [[] for _ in range(n)]
        # map side: one task per map partition on the task pool (the
        # multithreaded shuffle writer analog); pairs come back in map
        # order so the store stays deterministic
        for p, sub in iter_partition_tasks(
                lambda mp: run_task_iter(
                    lambda m: self._map_pairs(m, n), mp),
                self.child.num_partitions):
            store[p].append(sub)
        self._store = store

    def _materialize_multithreaded(self, env, n: int):
        """MULTITHREADED mode (reference RapidsShuffleThreadedWriterBase):
        pool-parallel serialization into per-map spill files, read back
        per reduce partition on the reader pool."""
        from spark_rapids_tpu.shuffle.threaded import (ThreadedShuffleReader,
                                                       ThreadedShuffleWriter)
        sid = env.next_shuffle_id()
        outputs = []
        for mp in range(self.child.num_partitions):
            writer = ThreadedShuffleWriter(sid, mp, n, env.writer_pool,
                                           directory=env.shuffle_dir,
                                           codec=env.codec)
            outputs.append(writer.write(list(self._map_pairs(mp, n))))
        reader = ThreadedShuffleReader(env.reader_pool)
        lazy = _LazyPartitions(
            n, lambda pidx: list(reader.read(outputs, pidx)))

        def cleanup():
            import os
            for o in outputs:
                try:
                    os.unlink(o.path)
                except OSError:
                    pass
        lazy.on_all_fetched = cleanup
        return lazy

    def _materialize_cached(self, env, n: int):
        """CACHED mode (reference UCX shuffle): map output registered in
        the ShuffleBufferCatalog, reduce side fetches through the
        client/server state machines over the transport.

        Resilient reduce side: the exchange remembers which blocks each
        reduce partition expects (lineage metadata).  A fetch that comes
        back short — the producing executor died and heartbeat expiry
        invalidated its blocks — RE-RUNS the producing map tasks to
        regenerate exactly the missing blocks, then refetches (the
        FetchFailed -> stage-retry story, scoped to the lost maps)."""
        from spark_rapids_tpu.shuffle.catalog import ShuffleBlockId
        from spark_rapids_tpu.shuffle.client_server import \
            ShuffleFetchFailed
        catalog, client, server = env.cached_machinery()
        sid = env.next_shuffle_id()
        written: Dict[int, set] = {p: set() for p in range(n)}

        def write_map(mp: int, only_pidx: Optional[int] = None) -> None:
            for p, sub in self._map_pairs(mp, n):
                if only_pidx is not None and p != only_pidx:
                    continue
                blk = ShuffleBlockId(sid, mp, p)
                catalog.add_batch(blk, sub, owner=server.executor_id)
                written[p].add(blk)

        for mp in range(self.child.num_partitions):
            write_map(mp)

        def fetch(pidx):
            from spark_rapids_tpu.aux.events import emit
            from spark_rapids_tpu.aux.faults import note_recovery
            expected = written[pidx]
            if not expected:
                return []
            # up to 3 passes: a transport-only failure earns one clean
            # refetch, and ONE lineage re-run is attempted for missing
            # blocks whenever the loss is detected (pass 0 or later)
            reran = False
            last_cause = "fetch kept failing with intact blocks"
            for attempt in range(3):
                try:
                    blocks = client.do_fetch(server, sid, pidx)
                    missing = expected - set(blocks)
                except ShuffleFetchFailed as e:
                    if attempt == 2:
                        raise
                    # a transport-level failure does NOT mean the blocks
                    # are gone: only regenerate what the catalog actually
                    # lost, else re-adding frames to intact blocks would
                    # DOUBLE their rows on the refetch
                    blocks = []
                    missing = expected - \
                        set(catalog.block_ids(sid, pidx))
                    last_cause = e.cause
                if not missing:
                    if not blocks:
                        # blocks intact, fetch failed anyway (transport):
                        # one more fetch pass, then surface the failure
                        continue
                    out = []
                    for b in blocks:
                        out.extend(client.received.read_batches(b))
                        client.received.drop(b)
                    # the fetched partition is cached by _LazyPartitions;
                    # release the map-side frames (reference:
                    # unregisterShuffle on consume)
                    catalog.drop_partition(sid, pidx)
                    return out
                if reran:
                    # give up — but not before releasing the frames this
                    # attempt DID fetch (the env-lifetime received
                    # catalog outlives the query; leaking here pins host
                    # memory until process exit)
                    for b in blocks:
                        client.received.drop(b)
                    raise ShuffleFetchFailed(
                        sid, pidx, server.executor_id,
                        f"{len(missing)} blocks missing after map re-run")
                # blocks invalidated (dead executor): re-run the
                # producing map tasks; write_map regenerates only this
                # partition's blocks (absent from the catalog, so the
                # re-add cannot duplicate frames)
                for b in expected:    # drop partial frames: refetch is
                    client.received.drop(b)   # all-or-nothing
                lost_maps = sorted({b.map_id for b in missing})
                note_recovery("map_reruns", len(lost_maps))
                emit("mapRerun", shuffle_id=sid, partition=pidx,
                     maps=len(lost_maps),
                     missing_blocks=len(missing))
                for mp in lost_maps:
                    write_map(mp, only_pidx=pidx)
                reran = True
            raise ShuffleFetchFailed(sid, pidx, server.executor_id,
                                     last_cause)
        return _LazyPartitions(n, fetch)

    def _compute_bounds(self):
        """Extra pass sampling key rows (the reference runs a sample job)."""
        part = self.partitioning
        samples = []
        rng = np.random.default_rng(0)
        for mp in range(self.child.num_partitions):
            for hb in self.child.execute_partition(mp):
                keys = part._key_batch_cpu(hb)
                k = min(hb.row_count, 1000)
                if k == 0:
                    continue
                take = np.sort(rng.choice(hb.row_count, size=k,
                                          replace=False))
                import pyarrow as pa
                tab = pa.Table.from_batches([keys.to_arrow()]) \
                    .take(pa.array(take))
                from spark_rapids_tpu.columnar.batch import batch_from_arrow
                samples.append(batch_from_arrow(tab))
        part.bounds = _sample_bounds(part, samples, None)

    # -- reduce side --------------------------------------------------------
    def execute_partition(self, pidx):
        yield from self._read(pidx, pidx + 1)

    def read_range(self, start, end, pieces=None):
        """The reduce partitions ``[start, end)`` as one read (the
        adaptive reader's coalesced spec); with ``pieces`` only the
        map-side pieces ``pieces[0]`` up to ``pieces[1]`` (its skew
        split).  Recorded on the node's metrics as ``execute_partition``
        is (``aux/metrics.py``)."""
        yield from self._read(start, end, pieces)

    def _read(self, start, end, pieces=None):
        self._materialized()
        for p in range(start, end):
            self._prefetch_next(p)
            yield from span_pulls("exchange.read", self._stored(p, pieces),
                                  partition=p)

    def _materialized(self) -> None:
        from spark_rapids_tpu.plan.base import release_semaphore_for_wait
        if self._store is None:
            # drop device admission before blocking on the map side (the
            # map tasks need permits); re-acquired lazily downstream
            release_semaphore_for_wait()
            with self._exec_lock:
                self._materialize()

    def _stored(self, pidx: int, pieces=None):
        """Reduce partition ``pidx``'s batches, one a map-side piece."""
        stored = self._store[pidx]
        return iter(stored if pieces is None
                    else stored[pieces[0]:pieces[1]])

    # -- what the adaptive reader sizes its specs by (exec/adaptive.py) ------
    def partition_sizes(self, target_bytes: Optional[int] = None
                        ) -> List[int]:
        """Materializes the exchange and sizes each reduce partition (the
        AQE 'query stage statistics' step).

        Sync discipline: padded (bucket) sizes are computable WITHOUT a
        device round trip; logical sizes need the deferred counts forced
        (one device sync per exchange).  When the padded total already
        fits ``target_bytes``, the coalesce decision ("merge everything")
        is identical either way — the padded sizes are returned and the
        sync is skipped entirely."""
        def sizes_now():
            return [sum(self.piece_sizes(p))
                    for p in range(self.num_partitions)]

        padded = sizes_now()   # no sync: unforced counts report bucket bytes
        if target_bytes is not None and sum(padded) <= target_bytes:
            return padded
        # above target: the decision needs logical sizes — force the
        # deferred counts in ONE sync so sized_nbytes reports rows-x-width
        # (padded sizes would make every partition look uniformly huge and
        # disable coalesce/skew decisions entirely)
        from spark_rapids_tpu.columnar.column import force_counts
        force_counts([b.row_count
                      for p in range(self.num_partitions)
                      for b in self._store[p]
                      if hasattr(b, "row_count")])
        return sizes_now()

    def piece_sizes(self, pidx: int) -> List[int]:
        """Bytes of reduce partition ``pidx`` a map-side piece (a batch
        whose count is deferred and unforced reports its bucket's)."""
        self._materialize()
        return [b.sized_nbytes() if hasattr(b, "sized_nbytes") else
                (b.nbytes() if hasattr(b, "nbytes") else 0)
                for b in self._store[pidx]]

    def _prefetch_next(self, pidx: int) -> None:
        """Pipelined shuffle read: while this reduce partition streams to
        its consumer, the NEXT one's fetch/deserialize runs in the
        background (lazy stores only — an eager store is already local)."""
        import spark_rapids_tpu.exec.pipeline as _PL
        if _PL.PIPELINE_ENABLED and isinstance(self._store,
                                               _LazyPartitions):
            self._store.prefetch(pidx + 1)

    def node_desc(self):
        return f"Exchange[{self.partitioning.desc()}]"


class TpuShuffleExchangeExec(CpuShuffleExchangeExec):
    """Device shuffle.

    DEFAULT mode within one process keeps the store DEVICE-RESIDENT: map
    output batches never leave HBM (reference: the UCX caching writer keeps
    shuffle output on device in ShuffleBufferCatalog,
    RapidsShuffleInternalManagerBase.scala:1034).  The store holds ONE
    piece a map batch, its rows ordered by reduce partition id with the
    counts a partition beside it (``sort_by_partition``: Spark's sort
    shuffle layout, one data file a map task and an index of offsets);
    nothing on the map side waits for the device.  The exchange is a
    materialization boundary: at the end of its map side the count vectors
    of all pieces are fetched together (ONE sync an exchange), which
    teaches every map batch its row count and the adaptive reader the
    partitions' sizes.  A reduce read, of one partition or of a contiguous
    range of them, is a row range of each piece, gathered by one program
    into one batch at the bucket of what it holds (``_read_rows``).  The
    store is NOT yet catalog-spillable: map output past the free-HBM
    budget is kept on the host in the same layout (the staged fallback),
    and an oversized shuffle should use MULTITHREADED mode (host-staged,
    spill-file backed) via spark.rapids.shuffle.mode.

    MULTITHREADED/CACHED modes keep the host-staged path from the base
    class (process-boundary semantics, spillable storage).
    """

    is_device = True

    #: set when the collective (mesh) path materialized this exchange:
    #: (MeshContext, sharded cols, per-device counts, schema)
    _collective = None

    #: conf-at-convert-time knobs (spark.rapids.shuffle.device.
    #: shrinkThresholdBytes / sql.rangeBounds.sampleRows /
    #: shuffle.collective.enabled / sql.collect.speculativeRows);
    #: ``None`` falls back to the module/transfer defaults so
    #: directly-driven test execs keep working
    shrink_threshold_bytes = None
    range_bounds_sample_rows = None
    collective_enabled = None
    dl_spec_rows = None

    def _collective_eligible(self, part):
        """The mesh path covers hash shuffles whose reduce count equals the
        mesh size and whose columns ride the sharded layout (no nested
        element-validity planes)."""
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.parallel.mesh import active_mesh
        from spark_rapids_tpu.plan.partitioning import HashPartitioning
        ce = self.collective_enabled
        if not (COLLECTIVE_ENABLED if ce is None else ce):
            return None
        ctx = active_mesh()
        if ctx is None or not isinstance(part, HashPartitioning):
            return None
        if part.num_partitions != ctx.num_devices:
            return None
        for f in self.child.schema.fields:
            if isinstance(f.data_type, (T.ArrayType, T.MapType,
                                        T.StructType)):
                return None
        return ctx

    def _materialize_collective(self, ctx):
        """Mesh execution: the whole shuffle is parallel/spmd.py's fused
        in-mesh exchange (shard -> compiled pid program -> one all_to_all
        collective; the UCX RDMA transport + catalogs + heartbeats of the
        reference collapse into the collective).  May raise
        ``SpmdHbmExceeded`` — handled by ``_materialize`` as a fallback
        to the host-staged spill-safe path."""
        from spark_rapids_tpu.parallel import spmd as _SPMD
        from spark_rapids_tpu.parallel.spmd import (check_hbm_budget,
                                                    spmd_hash_exchange)
        schema = self.child.schema
        # incremental HBM check while draining: an input that cannot
        # possibly fit stops pulling as soon as the running total proves
        # it, instead of materializing the rest first.  The host-staged
        # fallback then re-executes the child — the second pull rides
        # the scan cache / already-materialized upstream stores, but is
        # still a real cost, which is why this bails as EARLY as the
        # evidence allows.  The admission model itself lives in ONE
        # place: spmd.check_hbm_budget.
        budget = _SPMD._hbm_budget()
        total = 0
        batches = []
        for mp in range(self.child.num_partitions):
            for b in self.child.execute_partition(mp):
                batches.append(b)
                if budget is not None:
                    total += (b.nbytes() or 0) if hasattr(b, "nbytes") \
                        else 0
                    check_hbm_budget(total // max(1, ctx.num_devices),
                                     budget)
        out_cols, out_counts = spmd_hash_exchange(ctx, batches, schema,
                                                  self.partitioning)
        self._collective = (ctx, out_cols, out_counts, schema)

    def _materialize(self):
        if self._store is not None or self._collective is not None:
            return
        from spark_rapids_tpu.shuffle.env import get_shuffle_env
        env = self.shuffle_env or get_shuffle_env()
        mode = env.mode if env is not None else "DEFAULT"
        part = self.partitioning
        if mode != "DEFAULT":
            super()._materialize()
            return
        add_count("exchanges", 1)
        ctx = self._collective_eligible(part)
        if ctx is not None:
            from spark_rapids_tpu.parallel.spmd import SpmdHbmExceeded
            from spark_rapids_tpu.plan.base import _is_retryable
            try:
                self._materialize_collective(ctx)
                return
            except Exception as e:   # noqa: BLE001 - classified below
                if not (_is_retryable(e) or
                        isinstance(e, SpmdHbmExceeded)):
                    raise
                # per-stage ICI-vs-host choice: a working set that
                # cannot fit per-device HBM (SpmdHbmExceeded) takes
                # the host-staged spillable path; a lost chip fails
                # the whole collective step and degrades the same
                # way (Theseus-style: finish the plan when a
                # participant dies mid-shuffle)
                from spark_rapids_tpu.aux.events import emit
                from spark_rapids_tpu.aux.faults import note_recovery
                note_recovery("collective_fallbacks")
                emit("collectiveFallback",
                     reason=("hbm" if isinstance(e, SpmdHbmExceeded)
                             else "fault"),
                     error=f"{type(e).__name__}: {e}"[:160])
                self._collective = None
        if isinstance(part, RangePartitioning) and part.bounds is None:
            self._compute_bounds()
        n = part.num_partitions
        from spark_rapids_tpu.plan.partitioning import SinglePartitioning
        if isinstance(part, SinglePartitioning) or n == 1:
            # child partitions run as concurrent tasks via execute_all
            self._store = [list(self.child.execute_all())]
            return
        from spark_rapids_tpu.ops.batch_ops import shrink_batch
        from spark_rapids_tpu.plan.base import (iter_partition_tasks,
                                                run_task_iter)
        # HBM guard: the device-resident store keeps one copy of every map
        # batch at its bucket.  When that estimate crosses the free-HBM
        # budget, fall back to the host-staged path automatically instead
        # of OOMing the device (DEFAULT is the default mode; users
        # shouldn't need to know to flip
        # spark.rapids.shuffle.mode=MULTITHREADED).
        budget = self._device_store_budget()
        state = {"stored_estimate": 0, "host_staging": False}
        state_lock = __import__("threading").Lock()

        #: only a batch whose padded footprint is material gets the
        #: padding-shrink (shrink needs the exact count -> a device sync);
        #: below the threshold the piece keeps the input bucket and its
        #: counts stay on the device (sync-free map side)
        shrink_threshold = self.shrink_threshold_bytes \
            if self.shrink_threshold_bytes is not None \
            else SHRINK_THRESHOLD_BYTES

        def map_gen(mp):
            from spark_rapids_tpu.plan.base import closing_source
            p_eff = part
            if isinstance(part, RoundRobinPartitioning):
                p_eff = RoundRobinPartitioning(n, start=mp)
            # STREAMED (materializing the whole partition first would
            # defeat the host-staging fallback below).  closing_source: an
            # abandoned map task stops the chain now, not at GC
            with closing_source(self.child.execute_partition(mp)) as it:
                yield from _map_core(it, mp, p_eff)

        def _map_core(it, mp, p_eff):
            for b in it:
                if b.nbytes() > shrink_threshold:
                    b = shrink_batch(b)
                with state_lock:
                    if not state["host_staging"]:
                        state["stored_estimate"] += b.nbytes()
                        if budget is not None and \
                                state["stored_estimate"] > budget:
                            # auto-fallback: the rest of the map output is
                            # kept on the host; pieces already stored stay
                            # on device (they fit the budget) and a read
                            # handles the mixed store
                            import logging
                            logging.getLogger(__name__).info(
                                "device shuffle store would exceed HBM "
                                "budget (%d > %d bytes); host-staging the "
                                "remainder",
                                state["stored_estimate"], budget)
                            state["host_staging"] = True
                    staging = state["host_staging"]
                if staging:
                    piece = self._staged_piece(b, p_eff, n, mp)
                    _note_written(1, piece.rows(), piece.rows())
                    yield piece
                    continue
                with span("exchange.write", map=mp):
                    piece = _SortedPiece(*sort_by_partition(
                        b, partition_ids(p_eff, b, n), n))
                yield piece

        store = _SortedStore(n, list(iter_partition_tasks(
            lambda mp: run_task_iter(map_gen, mp),
            self.child.num_partitions)))
        # what the pieces hold is learned here with ONE fetch for all of
        # them, so the summary's counters, the map side's own row counts
        # and every reader above (the adaptive reader's sizes) see live
        # rows and not buckets
        store.learn()
        self._store = store

    def _device_store_budget(self):
        """Bytes the device-resident shuffle store may occupy: half the
        remaining device pool, or None when no runtime is initialized
        (tests that drive execs directly)."""
        from spark_rapids_tpu.memory.device_manager import \
            free_device_headroom
        return free_device_headroom(2)

    def _staged_piece(self, b, part, n, mp=None) -> _SortedPiece:
        """One device batch as a piece kept on the host: the device
        store's layout (``sort_by_partition``), downloaded, with its
        counts (one fetch a batch, site ``shuffle-pid-counts``)."""
        from spark_rapids_tpu.aux import transitions as TR
        with span("exchange.write", map=mp, staged="host"):
            shuffled, counts = sort_by_partition(
                b, partition_ids(part, b, n), n)
            counts = TR.fetch(counts, site="shuffle-pid-counts")
            hb = shuffled.to_host(spec_rows=self.dl_spec_rows)
            hb.names = b.names
            add_count("exchange_host_staged_bytes", hb.nbytes())
        piece = _SortedPiece(hb)
        piece.settle(counts, n)
        return piece

    def _stored(self, pidx, pieces=None):
        """A reduce partition's batches: those on the device as they are,
        then the host-staged ones, uploaded."""
        from spark_rapids_tpu.exec.basic import upload_batches
        host_pending = []
        for b in super()._stored(pidx, pieces):
            if isinstance(b, ColumnarBatch):
                yield b
            else:
                host_pending.append(b)
        if host_pending:
            yield from upload_batches(host_pending)

    def _read(self, start, end, pieces=None):
        if self._collective is None:
            self._materialized()
        if self._collective is not None:
            from spark_rapids_tpu.parallel import collective as C
            ctx, cols, counts, schema = self._collective
            for p in range(start, end):
                yield C.shard_to_batch(ctx, cols, counts, schema, p)
        elif isinstance(self._store, _SortedStore):
            yield from span_pulls("exchange.read",
                                  self._store.read(start, end, pieces),
                                  partition=start)
        else:
            yield from super()._read(start, end, pieces)

    def partition_sizes(self, target_bytes=None):
        """The base class's, for a list store; a mesh's shards by their
        fetched counts; the sorted store's live bytes, which need no
        fetch."""
        self._materialize()
        if self._collective is not None:
            # mesh path: partitions are device shards; size = rows * row
            # width
            _ctx, _cols, counts, schema = self._collective
            from spark_rapids_tpu.aux import transitions as TR
            counts_h = TR.fetch(counts, site="aqe-shard-counts")
            row_bytes = sum(
                getattr(f.data_type, "np_dtype", None).itemsize
                if getattr(f.data_type, "np_dtype", None) is not None else 16
                for f in schema.fields) + len(schema.fields)
            return [int(c) * row_bytes for c in counts_h]
        if isinstance(self._store, _SortedStore):
            # the counts came with the map side's one fetch
            return self._store.partition_sizes()
        return super().partition_sizes(target_bytes)

    def piece_sizes(self, pidx):
        self._materialize()
        if isinstance(self._store, _SortedStore):
            return self._store.piece_sizes(pidx)
        return super().piece_sizes(pidx)

    def _map_pairs(self, mp: int, n: int):
        """Device shuffle write of the MULTITHREADED and CACHED modes: a
        map batch sorted by reduce partition id on the device, ONE host
        copy (``_staged_piece``), then a slice a reduce partition."""
        from spark_rapids_tpu.plan.base import closing_source
        part = self.partitioning
        if isinstance(part, RoundRobinPartitioning):
            part = RoundRobinPartitioning(n, start=mp)
        with closing_source(self.child.execute_partition(mp)) as it:
            for b in it:
                piece = self._staged_piece(b, part, n, mp)
                pairs = [(p, piece.batch.slice(int(piece.starts[p]),
                                               piece.rows(p, p + 1)))
                         for p in range(n) if piece.rows(p, p + 1)]
                _note_written(len(pairs), piece.rows(), piece.rows())
                yield from pairs

    def _compute_bounds(self):
        self._compute_bounds_tpu()

    def _compute_bounds_tpu(self):
        """Samples on device, computes bounds on host (small).

        Fully fused: every-step-th row of each batch is gathered on device
        with a DEFERRED sample count, all samples concat on device, and
        ONE download ships them — the old per-batch host download + count
        force cost two host round trips per input batch."""
        from spark_rapids_tpu.columnar.column import (DeferredCount, _jnp,
                                                      rc_traceable)
        from spark_rapids_tpu.ops.batch_ops import concat_batches, \
            gather_batch
        jnp = _jnp()
        part = self.partitioning
        samples = []
        for mp in range(self.child.num_partitions):
            for b in self.child.execute_partition(mp):
                keys = part._key_batch_tpu(b)
                if not keys.columns:
                    continue
                # evenly spaced over the LIVE rows (a stride over the
                # bucket would collapse to ~1 sample for a filtered batch
                # whose count is far below its padding)
                k = self.range_bounds_sample_rows \
                    if self.range_bounds_sample_rows is not None \
                    else RANGE_BOUNDS_SAMPLE_ROWS
                rc_t = jnp.asarray(rc_traceable(b.row_count),
                                   dtype=np.int64)
                j = jnp.arange(k, dtype=np.int64)
                idx = jnp.where(rc_t <= k,
                                jnp.minimum(j, jnp.maximum(rc_t - 1, 0)),
                                (j * rc_t) // k)
                cnt = DeferredCount(jnp.minimum(rc_t, k))
                samples.append(gather_batch(keys, idx, cnt))
        if not samples:
            part.bounds = _sample_bounds(part, [], None)
            return
        from spark_rapids_tpu.ops.batch_ops import _committed_device
        sample_devs = {id(d) for d in
                       (_committed_device(b) for b in samples)
                       if d is not None}
        if len(sample_devs) > 1:
            # mesh shards: sample batches committed to DIFFERENT devices
            # cannot concat in one program — gather per shard and merge
            # on host (bounded: <= RANGE_BOUNDS_SAMPLE_ROWS per shard)
            from spark_rapids_tpu.columnar.batch import concat_host_batches
            hbs = [b.to_host() for b in samples]
            live = [h for h in hbs if h.row_count]
            hb = concat_host_batches(live) if live else hbs[0]
        else:
            hb = concat_batches(samples).to_host()
        part.bounds = _sample_bounds(part, [hb] if hb.row_count else [],
                                     None)

    def node_desc(self):
        return f"TpuExchange[{self.partitioning.desc()}]"


# plan-rewrite registration (reference: ShuffleExchangeExec rule
# GpuOverrides.scala:4023 + GpuShuffleMeta)
from spark_rapids_tpu.plan.overrides import register_exec  # noqa: E402

from spark_rapids_tpu.plan import typechecks as _TS  # noqa: E402

def _convert_exchange(p, m):
    from spark_rapids_tpu import config as C
    out = TpuShuffleExchangeExec(p.partitioning, p.children[0],
                                 shuffle_env=p.shuffle_env)
    # round-5 behavior knobs ride the INSTANCE (set from meta.conf at
    # convert time) — concurrent sessions must not race module globals
    out.shrink_threshold_bytes = C.parse_bytes(
        m.conf.get(C.SHUFFLE_DEVICE_SHRINK_THRESHOLD.key))
    out.range_bounds_sample_rows = int(
        m.conf.get(C.RANGE_BOUNDS_SAMPLE_ROWS.key))
    out.collective_enabled = bool(
        m.conf.get(C.COLLECTIVE_EXCHANGE_ENABLED.key))
    out.dl_spec_rows = int(m.conf.get(C.DOWNLOAD_SPECULATIVE_ROWS.key))
    return out


register_exec(CpuShuffleExchangeExec,
              convert=_convert_exchange,
              sig=_TS.BASIC_WITH_ARRAYS,
              exprs_of=lambda p: list(p.partitioning.exprs),
              extra_tag=lambda m: _TS.no_array_keys(
                  list(m.plan.partitioning.exprs), m,
                  "partitioning expression"),
              desc="shuffle exchange (device partition + host-staged store)")
