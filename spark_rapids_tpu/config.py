"""Typed, self-registering configuration system.

Mirrors the reference's ``RapidsConf.scala`` (sql-plugin/src/main/scala/com/
nvidia/spark/rapids/RapidsConf.scala:121 ConfEntry, :260 ConfBuilder, :319
registry): every config is a typed ``ConfEntry`` registered at import time in a
global registry, with startup/commonly-used/internal levels, and user docs
generated from the registry (reference generates docs/configs.md the same way).

Keys use the ``spark.rapids.*`` namespace for drop-in familiarity for users of
the reference plugin; TPU-specific keys live under ``spark.rapids.tpu.*``.
"""

from __future__ import annotations

import dataclasses
import enum
import os
import threading
from typing import Any, Callable, Dict, Generic, List, Optional, TypeVar

T = TypeVar("T")

__all__ = ["ConfEntry", "TpuConf", "registry", "generate_docs", "ConfLevel"]


class ConfLevel(enum.Enum):
    STARTUP = "startup"          # read once at plugin init
    COMMONLY_USED = "common"     # per-query tunables users touch
    INTERNAL = "internal"        # test/debug knobs


_REGISTRY: Dict[str, "ConfEntry"] = {}
_REGISTRY_LOCK = threading.Lock()


def registry() -> Dict[str, "ConfEntry"]:
    return dict(_REGISTRY)


@dataclasses.dataclass
class ConfEntry(Generic[T]):
    key: str
    doc: str
    default: T
    converter: Callable[[str], T]
    level: ConfLevel = ConfLevel.COMMONLY_USED
    checker: Optional[Callable[[T], bool]] = None

    def get(self, conf: "TpuConf") -> T:
        return conf.get(self.key)

    def __post_init__(self):
        with _REGISTRY_LOCK:
            if self.key in _REGISTRY:
                raise ValueError(f"duplicate conf key {self.key}")
            _REGISTRY[self.key] = self


def _to_bool(s: str) -> bool:
    if isinstance(s, bool):
        return s
    v = s.strip().lower()
    if v in ("true", "1", "yes", "on"):
        return True
    if v in ("false", "0", "no", "off"):
        return False
    raise ValueError(f"not a boolean: {s!r}")


def _bytes_conv(s: str) -> int:
    """Parses byte sizes like '512m', '8g' (Spark-style suffixes)."""
    if isinstance(s, int):
        return s
    v = s.strip().lower()
    mult = 1
    for suffix, m in (("k", 1 << 10), ("m", 1 << 20), ("g", 1 << 30), ("t", 1 << 40)):
        if v.endswith(suffix + "b"):
            v, mult = v[:-2], m
            break
        if v.endswith(suffix):
            v, mult = v[:-1], m
            break
    if v.endswith("b"):
        v = v[:-1]
    return int(float(v) * mult)


def conf_bool(key, doc, default, level=ConfLevel.COMMONLY_USED) -> ConfEntry[bool]:
    return ConfEntry(key, doc, default, _to_bool, level)


def conf_int(key, doc, default, level=ConfLevel.COMMONLY_USED,
             checker=None) -> ConfEntry[int]:
    return ConfEntry(key, doc, default, int, level, checker)


def conf_float(key, doc, default, level=ConfLevel.COMMONLY_USED) -> ConfEntry[float]:
    return ConfEntry(key, doc, default, float, level)


def parse_bytes(s) -> int:
    """Public byte-size parser ("512m", "1g", plain ints)."""
    return _bytes_conv(str(s))


def conf_str(key, doc, default, level=ConfLevel.COMMONLY_USED,
             checker=None) -> ConfEntry[str]:
    return ConfEntry(key, doc, default, str, level, checker)


def conf_bytes(key, doc, default, level=ConfLevel.COMMONLY_USED,
               checker=None) -> ConfEntry[int]:
    return ConfEntry(key, doc, default, _bytes_conv, level, checker)


# ---------------------------------------------------------------------------
# Registered entries.  Counterparts cited to reference RapidsConf.scala keys.
# ---------------------------------------------------------------------------

SQL_ENABLED = conf_bool(
    "spark.rapids.sql.enabled",
    "Enable or disable TPU acceleration of SQL plans entirely.",
    True)

SQL_MODE = conf_str(
    "spark.rapids.sql.mode",
    "Operating mode: 'executeOnGPU' runs supported plans on the TPU; "
    "'explainOnly' plans and logs what would run on TPU but executes on CPU "
    "(reference RapidsConf 'spark.rapids.sql.mode').",
    "executeOnGPU")

EXPLAIN = conf_str(
    "spark.rapids.sql.explain",
    "What to log about plan placement: NONE, NOT_ON_GPU, ALL.",
    "NOT_ON_GPU")

TEST_ENABLED = conf_bool(
    "spark.rapids.sql.test.enabled",
    "Test mode: fail if any operator in the plan did not translate to the TPU "
    "(reference 'spark.rapids.sql.test.enabled').",
    False, ConfLevel.INTERNAL)

TEST_ALLOWED_NONGPU = conf_str(
    "spark.rapids.sql.test.allowedNonGpu",
    "Comma-separated exec class names allowed to stay on CPU in test mode.",
    "", ConfLevel.INTERNAL)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
INCOMPATIBLE_OPS = conf_bool(
    "spark.rapids.sql.incompatibleOps.enabled",
    "Enable operators whose TPU results can differ from CPU in documented "
    "ways (float ordering, regex dialect...). Reference "
    "'spark.rapids.sql.incompatibleOps.enabled'.",
    True)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
HAS_NANS = conf_bool(
    "spark.rapids.sql.hasNans",
    "Assume floating point data may contain NaN (affects agg/join tagging).",
    True)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
VARIABLE_FLOAT_AGG = conf_bool(
    "spark.rapids.sql.variableFloatAgg.enabled",
    "Allow float aggregations whose result can vary with evaluation order.",
    True)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
IMPROVED_FLOAT_OPS = conf_bool(
    "spark.rapids.sql.improvedFloatOps.enabled",
    "Use float paths faster than, but not bit-identical to, CPU.",
    True)

BATCH_SIZE_BYTES = conf_bytes(
    "spark.rapids.sql.batchSizeBytes",
    "Target output batch size in bytes (CoalesceGoal TargetSize; reference "
    "'spark.rapids.sql.batchSizeBytes' default 1g; TPU default smaller since "
    "HBM per chip is smaller).",
    512 << 20)

MAX_READER_BATCH_SIZE_ROWS = conf_int(
    "spark.rapids.sql.reader.batchSizeRows",
    "Max rows a file reader produces per batch.",
    1 << 20)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
MAX_READER_BATCH_SIZE_BYTES = conf_bytes(
    "spark.rapids.sql.reader.batchSizeBytes",
    "Soft max bytes a file reader produces per batch.",
    512 << 20)

CONCURRENT_TPU_TASKS = conf_int(
    "spark.rapids.sql.concurrentGpuTasks",
    "Number of tasks that may hold the device concurrently (TpuSemaphore; "
    "reference 'spark.rapids.sql.concurrentGpuTasks', RapidsConf.scala:544).",
    2)

TASK_PARALLELISM = conf_int(
    "spark.rapids.tpu.taskParallelism",
    "Task threads driving plan partitions concurrently (the executor-cores "
    "analog: host I/O and shuffle ser/deser overlap device dispatch, with "
    "device admission still bounded by concurrentGpuTasks). 0 = auto "
    "(min(4, cpu_count)); 1 = serial.",
    0)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
ROW_BUCKET_MIN = conf_int(
    "spark.rapids.tpu.batch.rowBucketMin",
    "Minimum padded row-count bucket for device batches. Device batches are "
    "padded to power-of-two row buckets so XLA compiles once per bucket "
    "rather than once per batch size (TPU-first static-shape discipline).",
    1 << 10, ConfLevel.STARTUP)

DEVICE_POOL_FRACTION = conf_float(
    "spark.rapids.memory.gpu.allocFraction",
    "Fraction of HBM to dedicate to the buffer pool at init "
    "(reference 'spark.rapids.memory.gpu.allocFraction').",
    0.8)

DEVICE_POOL_SIZE = conf_bytes(
    "spark.rapids.tpu.memory.pool.size",
    "Absolute device pool size override for tests; 0 = use allocFraction of "
    "detected HBM.",
    0, ConfLevel.INTERNAL)

HOST_SPILL_STORAGE_SIZE = conf_bytes(
    "spark.rapids.memory.host.spillStorageSize",
    "Bytes of host memory used to spill device buffers before disk "
    "(reference 'spark.rapids.memory.host.spillStorageSize').",
    1 << 30)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
PAGEABLE_POOL_SIZE = conf_bytes(
    "spark.rapids.memory.host.pageablePool.size",
    "Host allocation pool size.",
    1 << 30, ConfLevel.STARTUP)

MEMORY_ARBITRATION_ENABLED = conf_bool(
    "spark.rapids.memory.arbitration.enabled",
    "Cooperative memory arbitration (memory/arbiter.py): a registered "
    "task thread that cannot allocate BLOCKS until concurrent tasks "
    "release memory, and only a detected deadlock (every device-holding "
    "task blocked) wakes one victim with a forced Retry/SplitAndRetry "
    "OOM (reference: the RmmSpark/SparkResourceAdaptor thread-state "
    "machine).  Disabled, reserve() raises RetryOOM on first shortfall "
    "as before.",
    True)

MEMORY_ARBITRATION_MAX_BLOCK_MS = conf_int(
    "spark.rapids.memory.arbitration.maxBlockMs",
    "Liveness backstop: the longest ONE allocation park may wait before "
    "falling back to a plain RetryOOM toward the task's retry frame.  "
    "Validated > 0 at set_conf.",
    10_000,
    checker=lambda v: int(v) > 0)

WATCHDOG_ENABLED = conf_bool(
    "spark.rapids.watchdog.enabled",
    "Hung-query watchdog (memory/arbiter.py): a daemon observing "
    "per-task last-progress timestamps (task-runner heartbeats, spool "
    "progress, alloc/semaphore wait entries).  A task with no progress "
    "for timeoutMs gets a full thread-state + holder-stack dump "
    "(watchdogDump event), then a forced arbitration round, then "
    "cancellation — surfacing as a retryable TaskCancelled the "
    "task-retry/circuit-breaker machinery re-executes or degrades.",
    False)

WATCHDOG_TIMEOUT_MS = conf_int(
    "spark.rapids.watchdog.timeoutMs",
    "Per-task no-progress budget before the watchdog dumps and "
    "escalates.  Validated > 0 at set_conf.",
    60_000,
    checker=lambda v: int(v) > 0)

WATCHDOG_POLL_MS = conf_int(
    "spark.rapids.watchdog.pollMs",
    "Watchdog sweep interval.  Validated > 0 at set_conf.",
    100,
    checker=lambda v: int(v) > 0)

OOM_INJECTION_MODE = conf_str(
    "spark.rapids.sql.test.injectRetryOOM",
    "Deterministic OOM fault injection for tests: 'false', 'true' (first "
    "alloc of each task), or '<n>' to fault the n-th tracked allocation "
    "(reference RapidsConf.scala:1541 TEST_RETRY_OOM_INJECTION_MODE).",
    "false", ConfLevel.INTERNAL)

FORCE_MERGE_REPARTITION_DEPTH = conf_int(
    "spark.rapids.sql.test.agg.forceMergeRepartitionDepth",
    "Test hook: force the aggregate merge's hash re-partition fallback "
    "while recursion depth < N (0 = only under real pressure; reference "
    "pattern: the spark.rapids.sql.test.* fault knobs).",
    0, ConfLevel.INTERNAL)

FORCE_OOC_SORT = conf_bool(
    "spark.rapids.sql.test.sort.forceOutOfCore",
    "Test hook: force the external (sorted-runs + merge) sort path "
    "regardless of memory pressure.",
    False, ConfLevel.INTERNAL)

FORCE_RUNNING_WINDOW = conf_bool(
    "spark.rapids.sql.test.window.forceRunning",
    "Test hook: force the batched running-window path for eligible specs "
    "regardless of memory pressure.",
    False, ConfLevel.INTERNAL)

FORCE_BOUNDED_WINDOW = conf_bool(
    "spark.rapids.sql.test.window.forceBoundedBatched",
    "Test hook: force the chunked bounded-frame window path (tail-carry "
    "between batches) regardless of memory pressure.",
    False, ConfLevel.INTERNAL)

BOUNDED_WINDOW_MAX_SPAN = conf_int(
    "spark.rapids.sql.window.batched.bounded.rowLimit",
    "Largest preceding+following ROWS span the chunked bounded-window "
    "path carries between batches; wider frames concatenate the whole "
    "partition (reference: spark.rapids.sql.window.batched.bounded."
    "row.max).",
    4096, ConfLevel.COMMONLY_USED)

JOIN_BUILD_SWAP_ENABLED = conf_bool(
    "spark.rapids.sql.join.buildSideSwap.enabled",
    "Runtime build-side choice for inner equi-joins: build on the "
    "smaller side regardless of SQL order (reference: "
    "GpuShuffledHashJoinExec build-side selection).",
    True)

JOIN_BUILD_SWAP_MAX_BYTES = conf_bytes(
    "spark.rapids.sql.join.buildSideSwap.maxBuildBytes",
    "Largest build side for which the swap comparison materializes the "
    "probe partition; above it the probe streams unswapped.",
    "256m")

SPECULATIVE_SIZING_ENABLED = conf_bool(
    "spark.rapids.sql.join.speculativeSizing.enabled",
    "Size the pair table of a hash join whose probe batch is small (a "
    "bucket of 32,768 rows or fewer) optimistically by the probe bucket "
    "and check overflow flags at the collect sync (replay exact on "
    "overflow) instead of paying a device round trip per join.  A larger "
    "probe batch always fetches its candidate total (one scalar) and is "
    "sized by it.  false = every join fetches its exact size.",
    True)

SHUFFLE_DEVICE_SHRINK_THRESHOLD = conf_bytes(
    "spark.rapids.shuffle.deviceStore.shrinkThresholdBytes",
    "A map batch whose padded footprint exceeds this is padding-shrunk "
    "(costs one count sync) before the device-resident shuffle store "
    "sorts and keeps its one copy of it.",
    "64m")

DOWNLOAD_SPECULATIVE_ROWS = conf_int(
    "spark.rapids.sql.collect.speculativeRows",
    "Row cap for single-round-trip result downloads while the row count "
    "is still deferred; larger results pay one extra round trip.  "
    "Applies to the result-download path (the device->host plan "
    "boundary and host-staged shuffle downloads); internal spill/"
    "sampling downloads keep the built-in default.  Validated >= 1 at "
    "set_conf.",
    8192,
    checker=lambda v: int(v) >= 1)

CTE_REUSE_ENABLED = conf_bool(
    "spark.rapids.sql.cteReuse.enabled",
    "Materialize a CTE referenced more than once exactly once and share "
    "the batches (Spark WithCTE/ReusedExchange analog).",
    True)

RANGE_BOUNDS_SAMPLE_ROWS = conf_int(
    "spark.rapids.sql.rangePartitioning.sampleRowsPerBatch",
    "Rows sampled per input batch (device-gathered, one download total) "
    "when computing range-partition bounds.",
    1024)

COLLECT_AGG_ENABLED = conf_bool(
    "spark.rapids.sql.agg.collectOnDevice.enabled",
    "Device collect_list/collect_set/count-distinct sets via padded "
    "[group, max_len] array planes (COMPLETE mode, fixed-width values); "
    "disabled falls back to the host collect tier.",
    True)

LIMIT_DEFERRED_FORCE_INTERVAL = conf_int(
    "spark.rapids.sql.limit.deferredForceInterval",
    "Deferred-count limit budget is forced to host every N batches so a "
    "satisfied limit stops pulling its child (amortized early exit).  "
    "Validated >= 1 at set_conf.",
    8,
    checker=lambda v: int(v) >= 1)

COLLECTIVE_EXCHANGE_ENABLED = conf_bool(
    "spark.rapids.shuffle.collective.enabled",
    "Mesh shuffles lower to ONE fused ICI all-to-all when the reduce "
    "count matches the device count (multi-chip path).",
    True)

DISTRIBUTION_ENABLED = conf_bool(
    "spark.rapids.sql.distribution.enabled",
    "Partition-aware planning: propagate delivered distributions "
    "(hash/range/single, with a mesh-axis binding) through the plan and "
    "ELIDE every shuffle exchange whose child is already partitioned as "
    "required — co-partitioned joins and aggregates above joins skip "
    "their re-shuffle entirely (plan/distribution.py; the "
    "EnsureRequirements dual).  Disabled reproduces the eager-exchange "
    "plans exactly.",
    True)


def _mesh_shape_ok(v: str) -> bool:
    # THE parser (parallel/mesh.py) is the one validity definition; the
    # checker just runs it so set_conf and session init cannot diverge
    from spark_rapids_tpu.parallel.mesh import parse_mesh_shape
    try:
        parse_mesh_shape(v)
        return True
    except ValueError:
        return False


def _mesh_axes_ok(v: str) -> bool:
    from spark_rapids_tpu.parallel.mesh import parse_mesh_axes
    try:
        parse_mesh_axes(v)
        return True
    except ValueError:
        return False


MESH_ENABLED = conf_bool(
    "spark.rapids.mesh.enabled",
    "Build and activate the device mesh from spark.rapids.mesh.* at "
    "session init (parallel/mesh.py); shuffle exchanges then lower to "
    "the in-mesh ICI path where eligible.  Off leaves mesh activation "
    "to explicit set_active_mesh() calls.",
    False)

MESH_SHAPE = conf_str(
    "spark.rapids.mesh.shape",
    "Mesh shape as comma-separated positive extents (e.g. '8' or '2,4'); "
    "empty uses all visible devices in one data-parallel dimension.  The "
    "product must divide the visible device count — validated at "
    "set_conf/session init, not at the first collective.",
    "", checker=_mesh_shape_ok)

MESH_AXES = conf_str(
    "spark.rapids.mesh.axes",
    "Comma-separated mesh axis names, one per shape dimension, "
    "non-empty and unique; the FIRST axis is the data axis partition "
    "parallelism shards over (the NamedSharding binding the planner's "
    "distribution pass records).",
    "data", checker=_mesh_axes_ok)

SCAN_CACHE_ENABLED = conf_bool(
    "spark.rapids.sql.scanCache.enabled",
    "Keep decoded (host) and uploaded (device) scan batches resident for "
    "repeated queries over static files (the file-cache + device-resident "
    "catalog analog, filecache.scala).  Unbounded residency: intended for "
    "benchmark/repeat-query sessions.  Process-sticky once enabled "
    "(interleaved default-conf sessions do not clear it); release with "
    "io.multifile.enable_scan_cache(False).",
    False)

SPILL_TO_DISK_DIR = conf_str(
    "spark.rapids.tpu.spill.dir",
    "Directory for the disk tier of the buffer catalog.",
    "", ConfLevel.STARTUP)

SHUFFLE_MANAGER_MODE = conf_str(
    "spark.rapids.shuffle.mode",
    "Shuffle mode: DEFAULT (in-memory host store) | MULTITHREADED "
    "(pooled writer/reader over spill files) | CACHED (alias CACHE_ONLY: "
    "buffer catalog + client/server transport) "
    "(reference RapidsShuffleManagerMode UCX|CACHE_ONLY|MULTITHREADED).",
    "DEFAULT")

SHUFFLE_WRITER_THREADS = conf_int(
    "spark.rapids.shuffle.multiThreaded.writer.threads",
    "Thread pool size for multithreaded shuffle writes.",
    8)

SHUFFLE_READER_THREADS = conf_int(
    "spark.rapids.shuffle.multiThreaded.reader.threads",
    "Thread pool size for multithreaded shuffle reads.",
    8)

def _chaos_spec_ok(v) -> bool:
    from spark_rapids_tpu.aux.faults import chaos_spec_ok
    return chaos_spec_ok(v)


SHUFFLE_TRANSPORT_TIMEOUT_MS = conf_int(
    "spark.rapids.shuffle.transport.timeoutMs",
    "Default bound for otherwise-unbounded transport waits: "
    "Transaction.wait(None) and bounce-buffer acquire(None) resolve to "
    "this, so a dead peer surfaces as a retryable TimeoutError through "
    "the fetch-retry policy instead of pinning a sender thread forever.  "
    "Validated > 0 at set_conf.",
    120_000,
    checker=lambda v: int(v) > 0)

SHUFFLE_FETCH_TIMEOUT_MS = conf_int(
    "spark.rapids.shuffle.fetch.timeoutMs",
    "Per-attempt wait for in-flight shuffle data frames after a transfer "
    "ack (replaces the old hardcoded 30s client timeout; validated > 0 at "
    "set_conf).",
    30_000,
    checker=lambda v: int(v) > 0)

SHUFFLE_FETCH_MAX_RETRIES = conf_int(
    "spark.rapids.shuffle.fetch.maxRetries",
    "Fetch attempts per peer beyond the first before giving up on that "
    "peer (then failing over to an alternate replica if one is known; "
    "reference: lost UCX peers surface as fetch failures -> retry).",
    3,
    checker=lambda v: int(v) >= 0)

SHUFFLE_FETCH_RETRY_WAIT_MS = conf_int(
    "spark.rapids.shuffle.fetch.retryWaitMs",
    "Base backoff between fetch retries; doubles per attempt with "
    "deterministic jitter, capped at retryMaxWaitMs.",
    50,
    checker=lambda v: int(v) >= 0)

SHUFFLE_FETCH_RETRY_MAX_WAIT_MS = conf_int(
    "spark.rapids.shuffle.fetch.retryMaxWaitMs",
    "Backoff ceiling for fetch retries.",
    2_000,
    checker=lambda v: int(v) >= 0)

TASK_MAX_FAILURES = conf_int(
    "spark.rapids.task.maxFailures",
    "Attempts per task before its failure propagates (the "
    "spark.task.maxFailures analog).  Only failures that strike BEFORE a "
    "task yields output are retried — a partially-consumed task cannot "
    "re-run without duplicating rows.",
    2,
    checker=lambda v: int(v) >= 1)

TASK_BREAKER_THRESHOLD = conf_int(
    "spark.rapids.task.breaker.threshold",
    "Task failures within one stage that trip the circuit breaker: the "
    "rest of the stage degrades to single-threaded inline execution "
    "instead of failing the query.  0 disables the breaker.",
    3,
    checker=lambda v: int(v) >= 0)

CHAOS_SHUFFLE_FETCH = conf_str(
    "spark.rapids.chaos.shuffle.fetch",
    "Deterministic fault injection at the shuffle-fetch point: 'n' or "
    "'n:skip' raises ConnectionError on the n triggers after skipping "
    "skip (generalizes spark.rapids.sql.test.injectRetryOOM to the "
    "shuffle layer; empty disables).",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_SHUFFLE_SEND = conf_str(
    "spark.rapids.chaos.shuffle.send",
    "Fault injection at the server block-send point ('n' or 'n:skip').",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_SHUFFLE_CONNECT = conf_str(
    "spark.rapids.chaos.shuffle.connect",
    "Fault injection at transport connection setup ('n' or 'n:skip').",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_TASK_RUN = conf_str(
    "spark.rapids.chaos.task.run",
    "Fault injection at task start in the parallel runner ('n' or "
    "'n:skip'); exercises task-level retry + the stage circuit breaker.",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_PARALLEL_COLLECTIVE = conf_str(
    "spark.rapids.chaos.parallel.collective",
    "Fault injection at the mesh collective shuffle ('n' or 'n:skip'); "
    "exercises the fallback to the host-staged exchange path.",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

PIPELINE_ENABLED = conf_bool(
    "spark.rapids.pipeline.enabled",
    "Pipelined execution: the planner inserts bounded-depth, thread-backed "
    "prefetch boundaries (exec/pipeline.py) so host decode, host<->device "
    "transfer and TPU compute overlap instead of serializing per batch.",
    True)

PIPELINE_DEPTH = conf_int(
    "spark.rapids.pipeline.depth",
    "Batches buffered per pipeline boundary (the prefetch spool's queue "
    "depth).  Validated >= 1 at set_conf.",
    2,
    checker=lambda v: int(v) >= 1)

PIPELINE_MAX_IN_FLIGHT_BYTES = conf_bytes(
    "spark.rapids.pipeline.maxInFlightBytes",
    "Byte budget for in-flight prefetched batches per boundary; a "
    "producer blocks (releasing device admission) once queued bytes "
    "exceed it.  Queued device batches also register with the spill "
    "framework, so they count against — and can be evicted from — the "
    "device-store budget.",
    "256m",
    checker=lambda v: int(v) >= 1)

CHAOS_PIPELINE_PREFETCH = conf_str(
    "spark.rapids.chaos.pipeline.prefetch",
    "Fault injection at prefetch-spool start ('n' or 'n:skip'); exercises "
    "producer-thread failure re-raise at the consumer and the task-retry "
    "recovery path over pipelined plans.",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_MEMORY_ALLOC = conf_str(
    "spark.rapids.chaos.memory.alloc",
    "Fault injection at tracked allocation points: raises RetryOOM "
    "through the shared chaos mechanism ('n' or 'n:skip'); the thread-"
    "scoped spark.rapids.sql.test.injectRetryOOM remains for framed "
    "per-task injection.",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_MEMORY_BLOCK = conf_str(
    "spark.rapids.chaos.memory.block",
    "Fault injection at the allocation admission point ('n' or "
    "'n:skip'): an injected NEVER-RELEASING allocation hold — the task "
    "parks arbitration-immune until the hung-query watchdog dumps, "
    "escalates and cancels it.  Exercises the hang-detection path "
    "deterministically.",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

CHAOS_WATCHDOG_SWEEP = conf_str(
    "spark.rapids.chaos.watchdog.sweep",
    "Fault injection inside the watchdog's sweep loop ('n' or "
    "'n:skip'); exercises the daemon's survive-a-bad-sweep discipline.",
    "", ConfLevel.INTERNAL,
    checker=_chaos_spec_ok)

SHUFFLE_COMPRESSION_CODEC = conf_str(
    "spark.rapids.shuffle.compression.codec",
    "Codec for shuffle payloads: none | lz4 | zlib (reference nvcomp "
    "LZ4/ZSTD; here the libtpucol LZ4 block codec or zlib).",
    "lz4")

JOIN_SUBPARTITION_THRESHOLD = conf_bytes(
    "spark.rapids.sql.join.subPartitionThresholdBytes",
    "Build sides larger than this re-partition into hash buckets joined "
    "independently (reference: GpuSubPartitionHashJoin.scala).",
    "1g")

JOIN_NUM_SUBPARTITIONS = conf_int(
    "spark.rapids.sql.join.numSubPartitions",
    "Bucket count for oversized-join sub-partitioning.",
    16)

EXCHANGE_REUSE_ENABLED = conf_bool(
    "spark.sql.exchange.reuse",
    "Collapse structurally identical exchange subtrees to one instance "
    "so repeated subquery pipelines shuffle once (Spark's ReuseExchange; "
    "the reference re-tags reused exchanges in updateForAdaptivePlan, "
    "GpuOverrides.scala:4589).",
    True)

AUTO_BROADCAST_JOIN_THRESHOLD = conf_bytes(
    "spark.sql.autoBroadcastJoinThreshold",
    "Largest estimated size of a join side that is broadcast to every "
    "task of the other side instead of hash-exchanging both (Spark's key "
    "and default; -1 plans no broadcast join by size).  The estimate is "
    "the bytes of an in-memory relation's referenced columns or a file "
    "scan's file sizes, unchanged through a filter and scaled by row width "
    "through a projection (plan/join_selection.py, docs/distributed.md).",
    "10485760")

ADAPTIVE_COALESCE_ENABLED = conf_bool(
    "spark.sql.adaptive.coalescePartitions.enabled",
    "Post-shuffle adaptive partition coalescing from materialized sizes "
    "(reference: GpuCustomShuffleReaderExec consuming AQE specs).",
    True)

ADVISORY_PARTITION_BYTES = conf_bytes(
    "spark.sql.adaptive.advisoryPartitionSizeInBytes",
    "Target size for adaptive partition coalescing.",
    "64m")

ADAPTIVE_MESH_ALIGN = conf_bool(
    "spark.rapids.sql.adaptive.meshAlign",
    "With an active device mesh, adaptive coalescing picks partition "
    "counts that are MULTIPLES of the mesh size (balanced contiguous "
    "merge), so post-AQE stages keep an even device mapping and later "
    "exchanges stay eligible for the in-mesh ICI path.",
    True)

FILECACHE_ENABLED = conf_bool(
    "spark.rapids.filecache.enabled",
    "Cache remote file ranges on local disk (reference: the closed-source "
    "FileCache reimplemented open, SURVEY.md §2.7).",
    False)

FILECACHE_MAX_BYTES = conf_bytes(
    "spark.rapids.filecache.maxBytes",
    "Local disk budget for the file cache.",
    "1g", ConfLevel.STARTUP)

METRICS_LEVEL = conf_str(
    "spark.rapids.sql.metrics.level",
    "Metric verbosity: ESSENTIAL | MODERATE | DEBUG (reference GpuExec.scala:36).",
    "MODERATE",
    checker=lambda v: str(v).strip().upper() in ("ESSENTIAL", "MODERATE",
                                                 "DEBUG"))

TRACING_ENABLED = conf_bool(
    "spark.rapids.tpu.tracing.enabled",
    "Wrap every DataFrame action in a QueryExecution trace: a span tree "
    "mirroring the physical plan that funnels operator metrics, task "
    "metrics and spill/retry/semaphore/shuffle events into one query "
    "summary (explain(analyze=True), event log, bench attribution).",
    True)

TRANSITIONS_ENABLED = conf_bool(
    "spark.rapids.sql.transitions.enabled",
    "Host-transition & device-sync ledger (aux/transitions.py): time and "
    "count every H2D upload, D2H download and blocking device sync "
    "through the instrumented gateway, aggregated per query into the "
    "summary/explain(analyze=True) ledger and the transitions/sync "
    "buckets of tools profile.  Off = wrappers degrade to the raw "
    "operations (results are bit-identical either way).",
    True)

TRANSITIONS_EVENTS = conf_bool(
    "spark.rapids.sql.transitions.events",
    "Emit one hostTransition/deviceSync event per boundary crossing "
    "(schema v4) into the event bus for timeline tools (tools trace).  "
    "Requires spark.rapids.sql.transitions.enabled; off keeps the "
    "aggregate ledger but skips per-crossing events on hot paths.",
    True)

EVENT_LOG_PATH = conf_str(
    "spark.rapids.sql.eventLog.path",
    "When set, every traced query appends its events to this JSONL file "
    "(Spark event-log analog): one JSON object per line carrying the "
    "event kind, query_id, span_id and a monotonic timestamp.",
    "")

EVENT_LOG_MAX_BYTES = conf_bytes(
    "spark.rapids.sql.eventLog.maxBytes",
    "Size-based event-log rotation: once the JSONL file crosses this many "
    "bytes it renames to <path>.N (N increasing, oldest smallest) and a "
    "fresh file (with a schema-version header) takes its place; the "
    "offline profiler reads the rotated set in order.  0 = never rotate.",
    0,
    checker=lambda v: int(v) >= 0)

EVENT_LOG_COMPRESS = conf_bool(
    "spark.rapids.sql.eventLog.compress",
    "Gzip-compress the event log: each write batch lands as one complete "
    "gzip member, preserving line atomicity; readers sniff the gzip magic "
    "(no extension requirement).  Do not mix compressed and plain sinks "
    "on one path.",
    False)

SAMPLE_ENABLED = conf_bool(
    "spark.rapids.sample.enabled",
    "Background resource sampler (aux/sampler.py): a low-overhead daemon "
    "thread periodically emits resourceSample events (memory pool "
    "used/watermark, spillable bytes, semaphore holders/waiters, prefetch "
    "spool depth, active tasks) into the event bus so offline timelines "
    "have a continuous signal between query events (reference: the "
    "always-on ProfilerOnExecutor).",
    False)

SAMPLE_INTERVAL_MS = conf_int(
    "spark.rapids.sample.intervalMs",
    "Milliseconds between resource samples.  Validated > 0 at set_conf.",
    100,
    checker=lambda v: int(v) > 0)

EVENT_LOG_RING_SIZE = conf_int(
    "spark.rapids.sql.eventLog.ringBufferSize",
    "Events retained per query in the in-memory ring buffer (the "
    "test/introspection sink); older events beyond it drop and the drop "
    "count is reported in the query summary.",
    2048)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
STABLE_SORT = conf_bool(
    "spark.rapids.sql.stableSort.enabled",
    "Force stable full sorts (disables some out-of-core optimizations).",
    False)

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
ENABLE_FLOAT_CAST_STRING = conf_bool(
    "spark.rapids.sql.castFloatToString.enabled",
    "Enable float->string casts (formatting can differ from CPU in last ulp).",
    True)

ENABLE_REGEX = conf_bool(
    "spark.rapids.sql.regexp.enabled",
    "Enable regular expression acceleration via the transpiler "
    "(reference 'spark.rapids.sql.regexp.enabled').",
    True)

MULTITHREADED_READ_NUM_THREADS = conf_int(
    "spark.rapids.sql.multiThreadedRead.numThreads",
    "Thread pool size for MULTITHREADED file readers.",
    8)

READER_TYPE = conf_str(
    "spark.rapids.sql.format.parquet.reader.type",
    "Parquet reader strategy: AUTO | PERFILE | COALESCING | MULTITHREADED "
    "(reference RapidsConf.scala:314 RapidsReaderType).",
    "AUTO")

CSV_READER_TYPE = conf_str(
    "spark.rapids.sql.format.csv.reader.type",
    "CSV reader strategy (same values as the parquet key).",
    "AUTO")

JSON_READER_TYPE = conf_str(
    "spark.rapids.sql.format.json.reader.type",
    "JSON reader strategy (same values as the parquet key).",
    "AUTO")

ORC_READER_TYPE = conf_str(
    "spark.rapids.sql.format.orc.reader.type",
    "ORC reader strategy (same values as the parquet key).",
    "AUTO")

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
AVRO_READER_TYPE = conf_str(
    "spark.rapids.sql.format.avro.reader.type",
    "Avro reader strategy (same values as the parquet key).",
    "AUTO")

# lint: ok=conf-registry -- reference-compat key, reserved (not yet wired)
DEVICE_STRING_MAX_LEN = conf_int(
    "spark.rapids.tpu.string.maxDeviceLen",
    "Strings longer than this stay on the host tier (device strings are "
    "padded [rows, max_len] uint8; padding cost grows with max length).",
    256)

DEBUG_LOCK_ORDER = conf_bool(
    "spark.rapids.debug.lockOrder",
    "Arm the runtime lock-order validator (aux/lockorder.py): the "
    "catalog/arbiter/semaphore/spool locks record every (held -> "
    "acquiring) edge per thread and check it against the canonical "
    "acquisition order the static lint rule enforces "
    "(spool < catalog < semaphore < arbiter); a backward edge counts in "
    "lock_order_violations_total and emits a lockOrderViolation event.  "
    "Debug/test knob: adds one flag read per lock acquire when off.",
    False, ConfLevel.INTERNAL)

DEBUG_PLAN_CHECK = conf_bool(
    "spark.rapids.debug.planCheck",
    "Arm the runtime plan-invariant verifier (plan/verify.py): every "
    "post-optimization physical plan is walked against the structural "
    "contracts the planner passes establish — encoding materialize "
    "boundaries, prefetch-node placement, spillable registration of "
    "queued batches, exchange-reuse key consistency.  A violation "
    "counts in plan_invariant_violations_total and emits a "
    "planInvariantViolation event (mirroring spark.rapids.debug."
    "lockOrder).  Debug/test knob: adds one plan walk per action when "
    "on.",
    False, ConfLevel.INTERNAL)

AUDIT_LEDGER = conf_bool(
    "spark.rapids.audit.ledger",
    "Record a per-program audit ledger row (stageProgram event) every "
    "time the stage compiler builds an executable: the closed jaxpr's "
    "structural signatures, primitive set, const shapes/fingerprints "
    "(never buffers), arg signature, cost-analysis flops/bytes and "
    "cache-key provenance — the input of the offline compiled-program "
    "auditor (python -m spark_rapids_tpu.tools audit, docs/audit.md).  "
    "Rows are recorded only while a sink that will store them is live "
    "(an eventLog.path file sink or a global sink): the analysis costs "
    "a few ms per BUILD, and a row that would die in the per-query "
    "ring buffer is not worth it.  Steady-state dispatch is untouched.",
    True)

RMM_DEBUG = conf_bool(
    "spark.rapids.memory.gpu.debug",
    "Log every pool allocation/free (reference RapidsConf.scala:375).",
    False, ConfLevel.INTERNAL)

COMPILE_CACHE_DIR = conf_str(
    "spark.rapids.sql.compile.cacheDir",
    "Directory for the persistent (on-disk) XLA compilation cache: "
    "compiled stage executables survive across queries AND sessions, so "
    "a restarted process re-traces (cheap) but never re-compiles a "
    "known program (expensive: seconds to tens of seconds per program).  "
    "Ignored where JAX_COMPILATION_CACHE_DIR is set: the cache was then "
    "placed from outside and JAX reads the variable itself.  Empty (the "
    "default) never enables the disk "
    "tier; the in-process executable cache is always on.  The setting is "
    "enable-only per process: an already-enabled tier stays on even if a "
    "later session leaves this empty (interleaved default-conf sessions "
    "must not drop it) — disable explicitly via "
    "exec.stage_compiler.set_persistent_cache_dir('').",
    "")

COMPILE_ASYNC = conf_bool(
    "spark.rapids.sql.compile.async",
    "Background stage compilation: a cache-missing stage program lowers "
    "and compiles on a daemon pool thread while the consumer overlaps "
    "the previous batch's compute (the fused stage exec runs a "
    "one-batch look-ahead), so first-batch compile latency stops "
    "stalling the pipeline.",
    False)

COMPILE_MAX_PROGRAMS = conf_int(
    "spark.rapids.sql.compile.maxPrograms",
    "Bound on the process-wide executable cache (exec/stage_compiler): "
    "least-recently-used programs beyond it are dropped (and recompile "
    "on next use — or reload from compile.cacheDir when set).  "
    "Validated >= 1 at set_conf.",
    4096,
    checker=lambda v: int(v) >= 1)

COMPILE_LITERAL_PROMOTION = conf_bool(
    "spark.rapids.sql.compile.literalPromotion",
    "Promote scalar literals in fused-stage filters/projections to "
    "runtime arguments of the compiled program, so plans differing only "
    "in literal values (dates, thresholds, year filters) share ONE "
    "executable instead of compiling per value — bounds compile-cache "
    "key cardinality for templated/parameterized query workloads.",
    True)

STAGE_FUSION_ENABLED = conf_bool(
    "spark.rapids.sql.compile.stageFusion.enabled",
    "Whole-stage fusion planner pass (plan/stages.py): collapse maximal "
    "device operator pipelines (filter/project chains, hash-agg update "
    "and merge/final passes) into single compiled XLA programs.  "
    "Disabling falls back to per-operator dispatch (differential-test "
    "hook; large end-to-end slowdown).",
    True)

CBO_ENABLED = conf_bool(
    "spark.rapids.sql.optimizer.enabled",
    "Enable the transition cost-based optimizer (reference CostBasedOptimizer.scala).",
    False)

ENCODING_ENABLED = conf_bool(
    "spark.rapids.sql.encoding.enabled",
    "Encoded columnar execution (columnar/encoding.py): parquet scans "
    "keep dictionary pages encoded, batches ship int codes + a "
    "once-per-fingerprint dictionary to the device, fused filters "
    "evaluate code-space lookup tables, hash-agg group keys and join "
    "keys hash the codes when dictionaries match, and sorts ride the "
    "codes of value-sorted dictionaries.  Every unsupported shape "
    "falls back per column to eager decode; disabling reproduces the "
    "plain (decode-at-scan) path exactly.",
    True)

ENCODING_LATE_MAT = conf_bool(
    "spark.rapids.sql.encoding.lateMaterialization",
    "Defer dictionary decode past filters: encoded columns survive the "
    "fused filter/project chain as compacted code planes and only "
    "SURVIVING rows gather through the dictionary where an operator "
    "needs values.  Disabling inserts an explicit materialize node "
    "above encoded scans (plan/encoding.py), keeping the H2D savings "
    "but decoding before any operator runs.",
    True)

ENCODING_MAX_DICT_SIZE = conf_int(
    "spark.rapids.sql.encoding.maxDictionarySize",
    "Dictionaries larger than this many values fall back to eager "
    "decode at upload (high-cardinality columns gain little from "
    "code-space execution and their lookup tables stop fitting the "
    "compile-friendly pow2 buckets).  Validated >= 1 at set_conf.",
    1 << 16,
    checker=lambda v: int(v) >= 1)

ENCODING_RLE_ENABLED = conf_bool(
    "spark.rapids.sql.encoding.rle.enabled",
    "Opportunistic run-length encoding at upload: fixed-width host "
    "columns whose run count is at most rows/8 ship run values + run "
    "ends instead of row planes and expand in-trace inside fused "
    "stages.  Off by default (run detection costs a host pass per "
    "uploaded column).",
    False)

SPILL_CODEC = conf_str(
    "spark.rapids.memory.spill.codec",
    "Codec for host->disk spill files: none | lz4 | zlib (the shuffle "
    "serializer's frame format; reference nvcomp-compressed spill).  "
    "Compressed spill multiplies effective spill capacity under the "
    "same disk budget; spill events and pool stats report the actual "
    "on-disk (compressed) bytes plus the logical bytes.",
    "lz4",
    checker=lambda v: str(v).strip().lower() in ("none", "", "lz4",
                                                 "zlib"))

COLUMN_PRUNING_ENABLED = conf_bool(
    "spark.rapids.sql.columnPruning.enabled",
    "Prune unused columns at scans before plan rewrite (Spark performs this "
    "in its logical optimizer; this engine plans physical trees directly). "
    "On TPU every pruned column is a host->device transfer avoided.",
    True)

# ---------------------------------------------------------------------------
# concurrent query serving (spark_rapids_tpu/serving)
# ---------------------------------------------------------------------------

SERVING_MAX_CONCURRENT = conf_int(
    "spark.rapids.serving.maxConcurrentQueries",
    "Queries the QueryServer executes concurrently; submissions past "
    "this wait in the admission queue.  The per-query device working "
    "sets still arbitrate through the shared pool + TpuSemaphore "
    "budgets — this bounds QUERY-level concurrency, the semaphore "
    "bounds TASK-level device concurrency.  Validated >= 1 at set_conf.",
    4,
    checker=lambda v: int(v) >= 1)

SERVING_MEMORY_RESERVATION = conf_bytes(
    "spark.rapids.serving.queryMemoryReservation",
    "Device-pool bytes the admission controller reserves per admitted "
    "query (Sparkle-style static memory partitioning of the shared "
    "pool): a query is only admitted while the sum of reservations "
    "fits the pool limit.  0 = pool limit / maxConcurrentQueries.  "
    "Reservations are admission-time accounting, not allocations — the "
    "arbiter still resolves real contention inside the pool.",
    "0")

SERVING_QUEUE_TIMEOUT_MS = conf_int(
    "spark.rapids.serving.queueTimeoutMs",
    "How long a submission may wait in the admission queue before "
    "failing with AdmissionTimeout (a bounded queue sheds load instead "
    "of stacking it).  Validated >= 1 at set_conf.",
    60_000,
    checker=lambda v: int(v) >= 1)

SERVING_QUEUE_BACKOFF_MS = conf_int(
    "spark.rapids.serving.queueBackoffMs",
    "Initial re-check backoff for a queued submission; doubles up to "
    "32x between admission re-checks (release notifications short-cut "
    "the wait).  Validated >= 1 at set_conf.",
    20,
    checker=lambda v: int(v) >= 1)

SERVING_PLAN_CACHE_MAX = conf_int(
    "spark.rapids.serving.planCache.maxPlans",
    "Physical plans the cross-query plan cache keeps (LRU).  Keyed by "
    "the normalized plan structure — literal-promoted queries share an "
    "entry and its compiled-executable set — plus the literal values; "
    "an exact repeat skips planning AND compilation.  0 disables.",
    64,
    checker=lambda v: int(v) >= 0)

SERVING_PLAN_CACHE_MAX_BYTES = conf_bytes(
    "spark.rapids.serving.planCache.maxBytes",
    "Byte budget for the physical plans the cross-query plan cache "
    "retains (estimated per-variant from the plan tree; compiled "
    "executables are process-wide jit caches and are not counted).  "
    "Acts alongside the planCache.maxPlans count bound — whichever "
    "trips first evicts LRU non-leased variants, counted in the "
    "cache's evictions stat and visible on the console /server "
    "endpoint.  0 = unbounded (count bound only).",
    "0")

SERVING_RESULT_CACHE_MAX_BYTES = conf_bytes(
    "spark.rapids.serving.resultCache.maxBytes",
    "In-memory budget for the deterministic query/CTE result cache "
    "(keyed by exact plan signature + input-file fingerprints; any "
    "file change invalidates).  Under pressure entries spill to an "
    "on-disk arrow tier (resultCache.spill) bounded at 4x this.  "
    "0 disables.",
    "256m")

SERVING_RESULT_CACHE_SPILL = conf_bool(
    "spark.rapids.serving.resultCache.spill",
    "Spill result-cache entries to an on-disk arrow tier instead of "
    "dropping them when the in-memory budget is exceeded.",
    True)

SERVING_AUTOTUNE_ENABLED = conf_bool(
    "spark.rapids.serving.autotune.enabled",
    "Close the AutoTuner into an online loop: after each query the "
    "server evaluates the rule set (tools/autotune.py) over the live "
    "event stream + resourceSample feed and applies accepted conf "
    "deltas (pipeline depth, concurrentGpuTasks, batch size) to the "
    "NEXT admitted query, emitting an autotuneApplied event per delta.",
    False)


# ---------------------------------------------------------------------------
# cross-run metrics warehouse + calibrated cost model (tools/history)
# ---------------------------------------------------------------------------

HISTORY_PATH = conf_str(
    "spark.rapids.history.path",
    "Path of the persistent cross-run history warehouse (SQLite): the "
    "database the `tools history` sub-commands open when no --db is "
    "given. Empty: the CLI requires --db. "
    "Reference: the spark-rapids-tools Qualification/Profiling store "
    "over Spark event logs.",
    "")

HISTORY_MACHINE_PROFILE_PATH = conf_str(
    "spark.rapids.history.machineProfilePath",
    "Path of a machine-profile JSON artifact written by `tools history "
    "calibrate`. When set (and costModel.enabled), df.explain() renders "
    "a report-only `== Cost ==` section with per-operator predicted "
    "cost from the calibrated profile, and each query's end-of-run "
    "summary cross-checks prediction vs measured per-stage time "
    "(queryEnd `cost` block + a costModel event for `tools audit`). "
    "Never changes plans or results. Empty disables.",
    "")

HISTORY_COST_MODEL_ENABLED = conf_bool(
    "spark.rapids.history.costModel.enabled",
    "Master switch for the report-only predicted-cost annotation layer "
    "(the `== Cost ==` explain section and the post-run predicted-vs-"
    "measured cross-check). Only meaningful when machineProfilePath is "
    "set; leaves query execution and results bit-identical either way.",
    True)

HISTORY_REGRESS_MIN_RUNS = conf_int(
    "spark.rapids.history.regress.minRuns",
    "Baseline runs `tools history regress` requires per query/metric "
    "before trusting a verdict; with fewer samples the metric is "
    "skipped (reported, never failed). Guards cold warehouses from "
    "judging against noise.",
    3,
    checker=lambda v: v >= 1)

HISTORY_REGRESS_MAD_BANDS = conf_float(
    "spark.rapids.history.regress.madBands",
    "Noise-band multiplier for `tools history regress`: the band "
    "around the baseline median is max(5% of |median|, madBands x "
    "1.4826 x MAD), so genuinely noisy metrics widen their own band "
    "instead of flagging every run (1.4826 scales the median absolute "
    "deviation to a Gaussian sigma).",
    3.0)


# ---------------------------------------------------------------------------
# live engine console (spark_rapids_tpu/aux/console.py)
# ---------------------------------------------------------------------------

CONSOLE_ENABLED = conf_bool(
    "spark.rapids.console.enabled",
    "Serve the embedded live-engine console over HTTP (stdlib "
    "ThreadingHTTPServer, no dependencies): /metrics (Prometheus "
    "exposition), /queries (live span trees with progress/ETA), "
    "/memory (pool gauges + per-query byte attribution), /server "
    "(QueryServer admission/cache/latency stats), /debug/dump "
    "(on-demand watchdog ladder) and /events (ring tail).  All "
    "handlers read lock-protected snapshots only.  Off by default "
    "with zero overhead when disabled.  Reference: the Spark UI / "
    "PrometheusServlet sink.",
    False)

CONSOLE_PORT = conf_int(
    "spark.rapids.console.port",
    "TCP port the console binds.  0 picks an ephemeral port (the "
    "bound port is logged in the consoleLifecycle event and exposed "
    "via active_console().port for tests/bench).  Validated >= 0 at "
    "set_conf.",
    0,
    checker=lambda v: 0 <= int(v) <= 65535)

CONSOLE_BIND_ADDRESS = conf_str(
    "spark.rapids.console.bindAddress",
    "Interface the console listens on.  Defaults to loopback; set "
    "0.0.0.0 deliberately to scrape from another host — the console "
    "is unauthenticated diagnostics, not a public API.",
    "127.0.0.1")


class TpuConf:
    """Immutable snapshot of config values (reference: ``new RapidsConf(conf)``
    re-read per query, GpuOverrides.scala:4564)."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        # (environment overrides are applied by default_conf(), which scans
        # SPARK_RAPIDS_CONF_* env vars; a bare TpuConf() reads only `settings`)
        self._values: Dict[str, Any] = {}
        settings = dict(settings or {})
        for k, entry in _REGISTRY.items():
            if k in settings:
                raw = settings.pop(k)
                val = entry.converter(raw)  # converters accept non-strings too
                if entry.checker is not None and not entry.checker(val):
                    raise ValueError(f"invalid value for {k}: {raw!r}")
                self._values[k] = val
            else:
                self._values[k] = entry.default
        self._extra = settings  # unregistered keys kept verbatim

    def get(self, key: str, default: Any = None) -> Any:
        if key in self._values:
            return self._values[key]
        if key in self._extra:
            # the key's rule may have registered AFTER this conf snapshot
            # was built (operator modules import lazily): convert through
            # the now-known entry instead of returning the raw string —
            # a literal "false" is truthy and would silently defeat
            # boolean gates (ADVICE-class bug, r5 review)
            raw = self._extra[key]
            entry = _REGISTRY.get(key)
            if entry is not None and isinstance(raw, str):
                val = entry.converter(raw)
                self._values[key] = val
                return val
            return raw
        entry = _REGISTRY.get(key)
        if entry is not None:
            return entry.default
        return default

    def with_overrides(self, **kv) -> "TpuConf":
        merged = {**self._values, **self._extra}
        merged.update({k.replace("__", "."): v for k, v in kv.items()})
        return TpuConf(merged)

    def set(self, key: str, value: Any) -> "TpuConf":
        merged = {**self._values, **self._extra, key: value}
        return TpuConf(merged)

    # convenience accessors used on hot paths
    @property
    def is_sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED.key)

    @property
    def is_explain_only(self) -> bool:
        return self.get(SQL_MODE.key).lower() == "explainonly"

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES.key)

    @property
    def is_test_enabled(self) -> bool:
        return self.get(TEST_ENABLED.key)

    def __repr__(self):
        non_default = {k: v for k, v in self._values.items()
                       if v != _REGISTRY[k].default}
        return f"TpuConf({non_default})"


def generate_docs() -> str:
    """Generates the configuration reference (reference: docs/configs.md is
    generated from RapidsConf; RapidsConf.scala 'object RapidsConf' doc gen)."""
    lines = ["# spark-rapids-tpu Configuration", "",
             "| Key | Default | Level | Description |",
             "|---|---|---|---|"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        doc = " ".join(str(e.doc).split())
        lines.append(f"| {e.key} | {e.default!r} | {e.level.value} | {doc} |")
    return "\n".join(lines) + "\n"


def default_conf() -> TpuConf:
    overrides = {}
    prefix = "SPARK_RAPIDS_CONF_"
    for k, v in os.environ.items():
        if k.startswith(prefix):
            overrides[k[len(prefix):].replace("_", ".")] = v
    return TpuConf(overrides)
