"""Versioned event-log ingestion for the offline diagnostic toolkit.

Reference: the ``spark-rapids-tools`` Qualification/Profiling CLI parses
Spark event logs (JSON lines) offline; this is the same move over the
engine's own JSONL sink (``spark.rapids.sql.eventLog.path``).

The reader is deliberately defensive — event logs from crashed or killed
processes are the EXPECTED input, not a corner case:

- **rotated sets**: given ``path``, the sibling files ``path.1 …
  path.N`` produced by size-based rotation are read first, oldest
  (smallest N) to newest, then ``path`` itself;
- **compression**: files are sniffed for the gzip magic (multi-member
  streams, one member per write batch) — no extension requirement;
- **truncation**: a torn final line (process killed mid-write) is
  counted, never fatal; unknown event kinds and unknown payload fields
  are carried through untouched;
- **versions**: v1 logs (PR 1, no structural span fields) load with a
  flat span list under a synthetic root; v2 logs rebuild the exec span
  tree from ``parent_id``/``depth`` and per-partition timelines from the
  ``partitions`` payload; v3 adds the compiled-program audit rows
  (``stageProgram``, ``planInvariantViolation``) which ride through as
  ordinary events (tools/audit consumes them); v4 adds the
  host-transition ledger rows (``hostTransition``, ``deviceSync``) from
  aux/transitions.py, consumed by tools/profile and tools/trace.  A
  version newer than ``SUPPORTED_VERSIONS`` raises — guessing at future
  schemas would corrupt attribution.

This module imports only the standard library plus ``aux.events`` (also
stdlib-only), so the CLI runs without jax or a device runtime.
"""

from __future__ import annotations

import dataclasses
import gzip
import io
import json
import os
import re
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.aux.events import NO_QUERY, Event

#: schema versions this reader understands (events carry "v" per line)
SUPPORTED_VERSIONS = (1, 2, 3, 4)


@dataclasses.dataclass
class ReadDiagnostics:
    """What ingestion saw — surfaced in every report so truncation is
    never mistaken for 'nothing happened'."""
    files: List[str] = dataclasses.field(default_factory=list)
    lines: int = 0
    parsed: int = 0
    truncated_lines: int = 0
    header_versions: List[int] = dataclasses.field(default_factory=list)
    #: sum of queryEnd.events_dropped — ring-buffer truncation upstream
    dropped_events: int = 0
    unknown_kinds: List[str] = dataclasses.field(default_factory=list)


class SpanNode:
    """One span reconstructed from a ``spanMetrics`` row: an exec span
    (one plan node), or a ``phase`` span of the query path
    (``kind == "phase"``: ``plan.rewrite``, ``exec.run``, ``xfer.d2h``...,
    its attributes under ``metrics``)."""

    __slots__ = ("span_id", "parent_id", "depth", "name", "desc", "kind",
                 "metrics", "children", "partitions", "start_s", "end_s")

    def __init__(self, row: Dict):
        self.span_id = row.get("span_id", -1)
        self.parent_id = row.get("parent_id")
        self.depth = row.get("depth", 1)
        self.name = row.get("node", "?")
        self.desc = row.get("desc", self.name)
        self.kind = row.get("kind", "exec")
        self.start_s = row.get("start_s")
        self.end_s = row.get("end_s")
        self.partitions = row.get("partitions", [])
        self.children: List["SpanNode"] = []
        meta = {"span_id", "parent_id", "depth", "node", "desc", "kind",
                "device", "start_s", "end_s", "partitions"}
        self.metrics = {k: v for k, v in row.items() if k not in meta}

    @property
    def duration_s(self) -> float:
        if self.start_s is None or self.end_s is None:
            return 0.0
        return max(0.0, self.end_s - self.start_s)

    def op_time(self) -> float:
        return float(self.metrics.get("opTime", 0.0) or 0.0)

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


class QueryProfile:
    """One query reconstructed from the log: span tree + raw events +
    the resource samples that fell inside its time window."""

    def __init__(self, query_id: int, run: int = 0):
        self.query_id = query_id
        #: process-run generation (restarts re-use query ids and restart
        #: the monotonic clock; see load_profiles)
        self.run = run
        self.description = ""
        self.conf: Dict = {}
        self.start_ts: Optional[float] = None
        self.end_ts: Optional[float] = None
        self.summary: Optional[Dict] = None
        self.events: List[Event] = []
        self.spans: Dict[int, SpanNode] = {}
        self.roots: List[SpanNode] = []
        #: the query path's phase spans, in the order they opened
        self.phases: List[SpanNode] = []
        self.samples: List[Event] = []
        self.complete = False

    @property
    def wall_s(self) -> float:
        """Query wall clock: the queryEnd duration when present, else the
        observed event span (truncated logs)."""
        if self.summary and "duration_s" in self.summary:
            return float(self.summary["duration_s"])
        if self.start_ts is not None and self.end_ts is not None:
            return max(0.0, self.end_ts - self.start_ts)
        return 0.0

    def events_of(self, *kinds: str) -> List[Event]:
        want = set(kinds)
        return [e for e in self.events if e.kind in want]

    def exec_spans(self) -> List[SpanNode]:
        out: List[SpanNode] = []
        for r in self.roots:
            out.extend(r.walk())
        return out

    def _link_spans(self) -> None:
        """Builds the tree from parent_id (v2).  v1 rows (no parent_id)
        all become roots — a flat list is still rankable."""
        by_id = self.spans
        self.roots = []
        for sp in by_id.values():
            parent = by_id.get(sp.parent_id) if sp.parent_id is not None \
                else None
            if parent is not None and parent is not sp:
                parent.children.append(sp)
            else:
                self.roots.append(sp)
        for sp in by_id.values():
            sp.children.sort(key=lambda s: s.span_id)
        self.roots.sort(key=lambda s: s.span_id)


# ---------------------------------------------------------------------------
# file-level ingestion
# ---------------------------------------------------------------------------

_GZIP_MAGIC = b"\x1f\x8b"


def log_file_set(path: str) -> List[str]:
    """``path``'s rotated siblings (oldest first) then ``path`` itself.
    Public: exactly the set to clear before a log path is reused, so
    stale rotations never leak into a fresh log's profile."""
    base = os.path.basename(path)
    d = os.path.dirname(os.path.abspath(path))
    rx = re.compile(re.escape(base) + r"\.(\d+)$")
    rotated = []
    if os.path.isdir(d):
        for name in os.listdir(d):
            m = rx.match(name)
            if m:
                rotated.append((int(m.group(1)), os.path.join(d, name)))
    out = [p for _, p in sorted(rotated)]
    if os.path.exists(path):
        out.append(path)
    return out


def _open_maybe_gzip(path: str):
    f = open(path, "rb")
    magic = f.read(2)
    f.seek(0)
    if magic == _GZIP_MAGIC:
        return io.TextIOWrapper(gzip.GzipFile(fileobj=f), encoding="utf-8",
                                errors="replace")
    return io.TextIOWrapper(f, encoding="utf-8", errors="replace")


def _iter_lines_tolerant(fh, diag: ReadDiagnostics):
    """Yields lines, absorbing a decompression failure at the tail: a
    process killed mid-write leaves a partial gzip member, and GzipFile
    raises EOFError/BadGzipFile DURING iteration — that is truncation,
    not a reason to crash the profiler."""
    while True:
        try:
            line = fh.readline()
        except (EOFError, OSError):    # BadGzipFile is an OSError
            diag.truncated_lines += 1
            return
        if not line:
            return
        yield line


def read_events(path: str) -> Tuple[List[Event], ReadDiagnostics]:
    """All events across the rotated file set, in write order, with a
    diagnostics record of everything ingestion had to tolerate."""
    diag = ReadDiagnostics()
    files = log_file_set(path)
    if not files:
        raise FileNotFoundError(f"no event log at {path!r}")
    events: List[Event] = []
    seen_kinds = set()
    for fp in files:
        diag.files.append(fp)
        try:
            fh = _open_maybe_gzip(fp)
        except OSError as e:
            raise FileNotFoundError(f"cannot open event log {fp!r}: {e}")
        with fh:
            for raw in _iter_lines_tolerant(fh, diag):
                line = raw.strip()
                if not line:
                    continue
                diag.lines += 1
                try:
                    d = json.loads(line)
                    kind = d["event"]
                    v = d.get("v", 1)
                except (ValueError, KeyError, TypeError):
                    # a torn line (killed mid-write) — count, keep going
                    diag.truncated_lines += 1
                    continue
                if v not in SUPPORTED_VERSIONS:
                    raise ValueError(
                        f"event log {fp!r} carries schema v{v}; this "
                        f"reader supports {SUPPORTED_VERSIONS} — upgrade "
                        "the tools package")
                ev = Event(kind, d.pop("query_id", NO_QUERY),
                           d.pop("span_id", -1), d.pop("ts", 0.0),
                           {k: val for k, val in d.items()
                            if k not in ("event", "v")})
                if kind == "eventLogHeader":
                    diag.header_versions.append(v)
                    continue
                seen_kinds.add(kind)
                events.append(ev)
    from spark_rapids_tpu.aux.events import EVENT_KINDS
    diag.unknown_kinds = sorted(seen_kinds - EVENT_KINDS)
    return events, diag


def load_profiles(path: str) -> Tuple[List[QueryProfile], ReadDiagnostics]:
    """Reconstructs per-query profiles (span trees, timelines, events)
    plus the out-of-query sample stream, aligned by timestamp."""
    events, diag = read_events(path)
    return profiles_from_events(events, diag)


def profiles_from_events(events: List[Event], diag: ReadDiagnostics
                         ) -> Tuple[List[QueryProfile], ReadDiagnostics]:
    """Profile reconstruction over an already-ingested event list, so a
    caller that needs BOTH the raw events and the profiles (tools/audit)
    pays one file parse, not two."""
    #: latest open profile per query id; query ids restart per PROCESS
    #: (itertools.count in tracing.py), so an append-mode log spanning
    #: restarts re-uses ids — a second queryStart for an id that already
    #: has events marks a new run and opens a fresh profile instead of
    #: silently merging two unrelated queries (and their two unrelated
    #: monotonic clocks) into one corrupt timeline
    latest: Dict[int, QueryProfile] = {}
    out: List[QueryProfile] = []
    #: run -> its resourceSample events; a restarted process restarts the
    #: monotonic clock, so samples may only match queries of their OWN
    #: run or the timestamp windows lie
    samples_by_run: Dict[int, List[Event]] = {}
    run = 0
    for ev in events:
        if ev.query_id == NO_QUERY:
            if ev.kind == "resourceSample":
                samples_by_run.setdefault(run, []).append(ev)
            continue
        qp = latest.get(ev.query_id)
        if qp is not None and ev.kind == "queryStart" and qp.events:
            # id re-use = a new process run; only bump the run counter on
            # the FIRST collision of that restart (later stale ids join
            # the current run instead of cascading it)
            if qp.run == run:
                run += 1
            qp = None
        if qp is None:
            qp = latest[ev.query_id] = QueryProfile(ev.query_id, run)
            out.append(qp)
        qp.events.append(ev)
        if qp.start_ts is None or ev.ts < qp.start_ts:
            qp.start_ts = ev.ts
        if qp.end_ts is None or ev.ts > qp.end_ts:
            qp.end_ts = ev.ts
        if ev.kind == "queryStart":
            qp.description = ev.payload.get("description", "")
            qp.conf = ev.payload.get("conf", {}) or {}
        elif ev.kind == "queryEnd":
            qp.summary = dict(ev.payload)
            qp.complete = True
            diag.dropped_events += int(
                ev.payload.get("events_dropped", 0) or 0)
        elif ev.kind == "spanMetrics":
            # the row's own span_id merges into the JSON envelope key
            # (same value: record_event stamps the row's span); restore
            # it from the envelope after parsing
            row = dict(ev.payload)
            row.setdefault("span_id", ev.span_id)
            sp = SpanNode(row)
            if sp.kind == "phase":
                qp.phases.append(sp)
            elif sp.span_id >= 0:
                qp.spans[sp.span_id] = sp
    for qp in out:
        qp._link_spans()
        qp.phases.sort(key=lambda sp: (sp.start_s or 0.0, sp.span_id))
        if qp.start_ts is not None and qp.end_ts is not None:
            qp.samples = [s for s in samples_by_run.get(qp.run, [])
                          if qp.start_ts <= s.ts <= qp.end_ts]
    diag.parsed = len(events)
    return out, diag
