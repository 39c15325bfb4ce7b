"""Adaptive (AQE-style) shuffle reader.

Reference: GpuCustomShuffleReaderExec (SURVEY.md §2.9) — consumes the
partition specs Spark's AQE derives from materialized shuffle statistics:
CoalescedPartitionSpec (merge small adjacent reduce partitions) and
PartialReducerPartitionSpec (split skewed ones).  Here the engine IS the
planner, so the reader derives the specs itself from the exchange's
materialized per-partition sizes.

The planner pass applies COALESCING universally (whole-partition merges
preserve hash-grouping and range order).  Skew-split specs are computed by
the same machinery but only applied where duplication is coordinated (the
shuffled-join path), mirroring Spark's own restriction."""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Union

from spark_rapids_tpu.plan.base import Exec, UnaryExec


@dataclasses.dataclass(frozen=True)
class CoalescedPartitionSpec:
    """Read reduce partitions [start, end) as one output partition."""
    start: int
    end: int


@dataclasses.dataclass(frozen=True)
class PartialPartitionSpec:
    """Read one reduce partition's rows of the map-side pieces
    ``[batch_start, batch_end)`` (skew split)."""
    partition: int
    batch_start: int
    batch_end: int


PartitionSpec = Union[CoalescedPartitionSpec, PartialPartitionSpec]


def _balanced_contiguous(sizes: Sequence[int],
                         k: int) -> List[CoalescedPartitionSpec]:
    """Exactly ``k`` contiguous, size-balanced groups covering every
    input partition (each group non-empty)."""
    n = len(sizes)
    k = max(1, min(k, n))
    cum: List[int] = []
    total = 0
    for s in sizes:
        total += s
        cum.append(total)
    specs: List[CoalescedPartitionSpec] = []
    start = 0
    for g in range(k):
        if g == k - 1:
            end = n
        else:
            target = total * (g + 1) / k
            end = start + 1
            # advance while under quota, leaving >= 1 input per
            # remaining group
            while end < n - (k - g - 1) and cum[end - 1] < target:
                end += 1
        specs.append(CoalescedPartitionSpec(start, end))
        start = end
    return specs


def coalesce_specs(sizes: Sequence[int], target_bytes: int,
                   align: int = 1) -> List[CoalescedPartitionSpec]:
    """Greedy adjacent merge up to the advisory size (Spark's
    coalescePartitions algorithm).  With ``align`` > 1 (the mesh size,
    mesh-aware AQE) the output count snaps to the nearest achievable
    MULTIPLE of ``align`` via a balanced contiguous re-split, so
    post-AQE stages keep an even device mapping."""
    specs: List[CoalescedPartitionSpec] = []
    start = 0
    acc = 0
    for i, sz in enumerate(sizes):
        if i > start and acc + sz > target_bytes:
            specs.append(CoalescedPartitionSpec(start, i))
            start, acc = i, 0
        acc += sz
    if start < len(sizes) or not specs:
        specs.append(CoalescedPartitionSpec(start, max(len(sizes), 1)))
    if align > 1 and len(sizes) >= align and len(specs) % align:
        # nearest multiple of align, clamped to what the input count can
        # actually supply: rounding UP past len(sizes) must floor to the
        # largest achievable multiple, never give up (12 inputs on an
        # 8-mesh round to 16 but snap to 8, not stay at 12)
        k = max(align, int(round(len(specs) / align)) * align)
        k = min(k, (len(sizes) // align) * align)
        specs = _balanced_contiguous(sizes, k)
    return specs


def _emit_coalesce_event(before: int, after: int, align: int,
                         ici_active: bool) -> None:
    """One ``aqeCoalesce`` record per AQE decision: the mesh-alignment
    evidence AutoTuner rule 10 cites (``aligned`` is judged against the
    ACTIVE mesh size, not the requested align, so a misaligned count
    with meshAlign disabled still shows up as misaligned)."""
    from spark_rapids_tpu.aux.events import emit
    from spark_rapids_tpu.parallel.mesh import active_mesh
    ctx = active_mesh()
    mesh = ctx.num_devices if ctx is not None else 0
    emit("aqeCoalesce", before=before, after=after, align=align,
         mesh=mesh, ici_active=bool(ici_active),
         aligned=(mesh <= 1 or after % mesh == 0))


def skew_split_specs(exchange, pidx: int,
                     target_bytes: int) -> List[PartialPartitionSpec]:
    """Splits one partition's map-side pieces into roughly target-sized
    runs (PartialReducerPartitionSpec analog)."""
    sizes = exchange.piece_sizes(pidx)
    specs = []
    start = 0
    acc = 0
    for i, sz in enumerate(sizes):
        if i > start and acc + sz > target_bytes:
            specs.append(PartialPartitionSpec(pidx, start, i))
            start, acc = i, 0
        acc += sz
    specs.append(PartialPartitionSpec(pidx, start, len(sizes)))
    return specs


def detect_skew(sizes: Sequence[int], factor: float = 5.0,
                min_bytes: int = 64 << 20) -> List[int]:
    """Skewed partition indexes: > factor * median AND > min size
    (Spark skewJoin detection)."""
    if not sizes:
        return []
    srt = sorted(sizes)
    median = srt[len(srt) // 2]
    return [i for i, s in enumerate(sizes)
            if s > max(median * factor, min_bytes)]


class SharedCoalesceSpecs:
    """ONE coalesce plan for the two sides of a shuffled join: partition i
    of both exchanges must merge identically or the key pairing breaks
    (Spark coordinates AQE shuffle reads across join children the same
    way).  Sizes are summed across sides so the target bound applies to
    the pair."""

    def __init__(self, left_ex, right_ex, target_bytes: int,
                 align: int = 1):
        import threading
        self._exs = (left_ex, right_ex)
        self._target = target_bytes
        self._align = align
        self._specs: Optional[List[PartitionSpec]] = None
        self._lock = threading.Lock()

    def get(self) -> List[PartitionSpec]:
        if self._specs is None:
            from spark_rapids_tpu.plan.base import release_semaphore_for_wait
            release_semaphore_for_wait()
            with self._lock:
                if self._specs is None:
                    # halve the target per side: the padded-fits-target
                    # shortcut must hold for the SUM of both sides
                    lsz = self._exs[0].partition_sizes(self._target // 2)
                    rsz = self._exs[1].partition_sizes(self._target // 2)
                    sizes = [a + b for a, b in zip(lsz, rsz)]
                    # whole-partition coalescing only — a partial split
                    # on one side without the other would break pairing
                    self._specs = coalesce_specs(sizes, self._target,
                                                 self._align)
                    _emit_coalesce_event(
                        len(sizes), len(self._specs), self._align,
                        any(getattr(ex, "_collective", None) is not None
                            for ex in self._exs))
        return self._specs


class AdaptiveShuffleReaderExec(UnaryExec):
    """Reads an exchange through derived partition specs."""

    def __init__(self, exchange, target_bytes: int = 64 << 20,
                 specs: Optional[List[PartitionSpec]] = None,
                 shared: Optional[SharedCoalesceSpecs] = None,
                 align: int = 1):
        super().__init__(exchange)
        self.target_bytes = target_bytes
        self._specs = specs
        #: coordinated specs shared with the sibling join side
        self._shared = shared
        #: snap coalesced counts to multiples of this (the mesh size)
        self._align = align

    @property
    def is_device(self):  # type: ignore[override]
        return self.children[0].is_device

    @property
    def specs(self) -> List[PartitionSpec]:
        if self._specs is None:
            if self._shared is not None:
                self._specs = self._shared.get()
                return self._specs
            # materializes the child exchange: drop device admission and
            # serialize against concurrent tasks (plan/base.py semantics)
            from spark_rapids_tpu.plan.base import release_semaphore_for_wait
            release_semaphore_for_wait()
            with self._exec_lock:
                if self._specs is None:
                    sizes = self.children[0].partition_sizes(
                        self.target_bytes)
                    self._specs = coalesce_specs(sizes, self.target_bytes,
                                                 self._align)
                    _emit_coalesce_event(
                        len(sizes), len(self._specs), self._align,
                        getattr(self.children[0], "_collective", None)
                        is not None)
        return self._specs

    @property
    def num_partitions(self):
        return len(self.specs)

    def execute_partition(self, pidx):
        spec = self.specs[pidx]
        ex = self.children[0]
        if isinstance(spec, CoalescedPartitionSpec):
            yield from ex.read_range(
                spec.start, min(spec.end, ex.num_partitions))
        else:
            yield from ex.read_range(
                spec.partition, spec.partition + 1,
                (spec.batch_start, spec.batch_end))

    def node_desc(self):
        if self._specs is None:
            return "AdaptiveShuffleReader[pending]"
        nc = sum(1 for s in self._specs
                 if isinstance(s, CoalescedPartitionSpec))
        np_ = len(self._specs) - nc
        return (f"AdaptiveShuffleReader[{len(self._specs)}p "
                f"({nc} coalesced, {np_} partial)]")


def _potential_collective(ex) -> bool:
    """True when ``ex`` would take the in-mesh ICI path on materialize
    (hash partitioning at the mesh size, mesh-shardable schema): these
    exchanges map reduce partitions 1:1 onto device shards, and the
    reader must preserve that mapping — coalescing across shards would
    concatenate batches living on different devices into one downstream
    kernel, destroying the locality the collective bought."""
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    return isinstance(ex, TpuShuffleExchangeExec) and \
        ex._collective_eligible(ex.partitioning) is not None


def insert_adaptive_readers(plan: Exec, target_bytes: int,
                            align: int = 1) -> Exec:
    """Planner pass (TOP-down): wrap every shuffle exchange whose parent
    will iterate its reduce partitions (coalescing whole partitions is
    safe: hash groups and range order are preserved).

    Join inputs pair partition i with partition i, so the two sides of a
    shuffled join read through ONE coordinated spec (Spark coordinates
    AQE shuffle reads across join children identically); a join side
    that CANNOT be coordinated gets no reader at all — an independently
    coalesced side would silently mis-pair the join keys.

    Mesh-aware: exchanges riding the in-mesh ICI path keep their 1:1
    shard mapping (no reader); host-staged exchanges under an active
    mesh coalesce to counts that are MULTIPLES of the mesh size
    (``align``, conf spark.rapids.sql.adaptive.meshAlign) so later
    stages stay evenly device-mapped and ICI-eligible."""
    from spark_rapids_tpu.exec.basic import TpuCoalesceBatchesExec
    from spark_rapids_tpu.exec.exchange import CpuShuffleExchangeExec
    from spark_rapids_tpu.plan.base import BinaryExec

    def unwrap(c):
        """(exchange, rewrap) looking through the post-shuffle batch
        coalescer the transition pass inserts."""
        if isinstance(c, CpuShuffleExchangeExec):
            return c, (lambda inner: inner)
        if isinstance(c, TpuCoalesceBatchesExec) and \
                isinstance(c.children[0], CpuShuffleExchangeExec):
            return c.children[0], \
                (lambda inner, outer=c: outer.with_children([inner]))
        return None, None

    #: identity memo: a node shared by several parents (ReuseExchange)
    #: must map to ONE rewritten node, or the sharing silently splits
    #: into per-parent copies that each re-materialize the shuffle
    memo: dict = {}

    def visit(node: Exec, no_wrap: bool = False) -> Exec:
        # an exchange's own rebuild is flag-independent (no_wrap only
        # tells the PARENT not to wrap it) — normalize the key so a
        # shared exchange visited from join and non-join parents stays
        # one instance
        flag = (False if isinstance(node, CpuShuffleExchangeExec)
                else no_wrap)
        key = (id(node), flag)
        if key in memo:
            return memo[key]
        out = _visit(node, no_wrap)
        memo[key] = out
        return out

    def _visit(node: Exec, no_wrap: bool = False) -> Exec:
        if isinstance(node, BinaryExec):
            if no_wrap:
                # a downstream shuffled join relies on THIS subtree's
                # delivered partition count (its own exchange was elided
                # by the distribution pass): nothing below may coalesce,
                # including nested joins' exchange pairs — a 2->1 merge
                # here would leave the downstream join reading partition
                # i against an unrelated (or never-read) partition i
                return node.with_children([visit(c, no_wrap=True)
                                           for c in node.children])
            l, r = node.children
            lex, lwrap = unwrap(l)
            rex, rwrap = unwrap(r)
            if (lex is not None and rex is not None and
                    lex.num_partitions == rex.num_partitions and
                    lex.num_partitions > 1 and
                    not _potential_collective(lex) and
                    not _potential_collective(rex)):
                # rebuild through the memoized visit so an exchange shared
                # with other consumers (ReuseExchange) stays ONE instance
                lex = visit(lex, no_wrap=True)
                rex = visit(rex, no_wrap=True)
                shared = SharedCoalesceSpecs(lex, rex, target_bytes,
                                             align)
                return node.with_children([
                    lwrap(AdaptiveShuffleReaderExec(lex, target_bytes,
                                                    shared=shared,
                                                    align=align)),
                    rwrap(AdaptiveShuffleReaderExec(rex, target_bytes,
                                                    shared=shared,
                                                    align=align))])
            # un-coordinatable (or an ICI pair whose 1:1 shard pairing
            # must survive untouched): children recurse with their
            # top-level exchange left unwrapped
            return node.with_children([visit(c, no_wrap=True)
                                       for c in node.children])
        new_children = []
        for c in node.children:
            # partition-preserving unary nodes (coalescer, project, filter,
            # fused stages...) are transparent to partition pairing: the
            # no-wrap flag must flow through ALL of them down to the next
            # exchange, or a join input reached through e.g. a project
            # would get an independently coalesced reader and silently
            # mis-pair join partitions (ADVICE r4).  Exchanges reset
            # partitioning, so propagation stops there.
            child_no_wrap = (
                no_wrap and isinstance(node, UnaryExec) and
                not isinstance(node, CpuShuffleExchangeExec) and
                node.num_partitions == node.children[0].num_partitions)
            c2 = visit(c, no_wrap=child_no_wrap)
            if isinstance(c2, CpuShuffleExchangeExec) and \
                    not isinstance(node, AdaptiveShuffleReaderExec) and \
                    not child_no_wrap:
                if _potential_collective(c2):
                    # ICI shuffles map reduce partitions 1:1 onto device
                    # shards; coalescing would concatenate batches living
                    # on different devices into one downstream kernel
                    new_children.append(c2)
                    continue
                c2 = AdaptiveShuffleReaderExec(c2, target_bytes,
                                               align=align)
            new_children.append(c2)
        return node.with_children(new_children)

    return visit(plan)
