"""The SQLite warehouse: normalized cross-run telemetry.

One ``ingest`` call per source makes one ``runs`` row; everything else
hangs off ``run_id``.  Sources are sniffed, not flagged: a JSONL event
log (any supported schema version, rotated/gzip sets included) lands as
queries/spans/programs/transitions/spills/ici/compiles/confs/serving
rows; a benchmark payload (a run's one-line JSON, or a driver-wrapper
doc) lands as metric rows keyed by the dotted paths of
``PAYLOAD_METRICS``.  Failed bench runs (placeholder
zeros, see tools/regression.run_failure) are recorded as runs with
``status='failed'`` and NO metric rows — their placeholders must never
enter a baseline.

Spans are stored with their bottleneck bucket and EXCLUSIVE seconds
(tools/profile attribution), which is what calibration joins the audit
ledger's flops/bytes against.  ``stage_programs`` keeps the emitting
span id: a program built under an instrumented operator joins to that
operator's measured time.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import sqlite3
import time
from typing import Dict, List, Optional, Tuple

HISTORY_SCHEMA_VERSION = 1

_TABLES = """
CREATE TABLE IF NOT EXISTS meta(
    key TEXT PRIMARY KEY, value TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS runs(
    run_id INTEGER PRIMARY KEY AUTOINCREMENT,
    kind TEXT NOT NULL,                -- 'event_log' | 'bench'
    source TEXT NOT NULL,
    label TEXT NOT NULL DEFAULT '',
    status TEXT NOT NULL DEFAULT 'ok', -- 'ok' | 'failed'
    ingested_at REAL NOT NULL,
    schema_versions TEXT NOT NULL DEFAULT '',
    queries INTEGER NOT NULL DEFAULT 0,
    truncated_lines INTEGER NOT NULL DEFAULT 0,
    dropped_events INTEGER NOT NULL DEFAULT 0,
    content_digest TEXT NOT NULL DEFAULT '');
CREATE TABLE IF NOT EXISTS queries(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    run_gen INTEGER NOT NULL DEFAULT 0, ordinal INTEGER NOT NULL,
    description TEXT NOT NULL DEFAULT '',
    status TEXT NOT NULL DEFAULT '', wall_s REAL NOT NULL DEFAULT 0,
    tasks INTEGER NOT NULL DEFAULT 0,
    spill_bytes INTEGER NOT NULL DEFAULT 0,
    events INTEGER NOT NULL DEFAULT 0,
    complete INTEGER NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS spans(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    span_id INTEGER NOT NULL, node TEXT NOT NULL,
    bucket TEXT NOT NULL DEFAULT '',
    exclusive_s REAL NOT NULL DEFAULT 0,
    inclusive_s REAL NOT NULL DEFAULT 0,
    rows INTEGER NOT NULL DEFAULT 0, batches INTEGER NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS stage_programs(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    span_id INTEGER NOT NULL DEFAULT -1,
    stage_kind TEXT NOT NULL, key TEXT NOT NULL,
    flops REAL, bytes_accessed REAL,
    eqns INTEGER NOT NULL DEFAULT 0, n_args INTEGER NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS transitions(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    direction TEXT NOT NULL,           -- 'h2d' | 'd2h' | 'sync'
    bytes INTEGER NOT NULL DEFAULT 0, seconds REAL NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS spills(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    op TEXT NOT NULL,                  -- 'spill' | 'unspill'
    bytes INTEGER NOT NULL DEFAULT 0,
    logical_bytes INTEGER NOT NULL DEFAULT 0,
    seconds REAL NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS ici(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    devices INTEGER NOT NULL DEFAULT 0,
    rows INTEGER NOT NULL DEFAULT 0, seconds REAL NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS compiles(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    stage_kind TEXT NOT NULL, seconds REAL NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS confs(
    run_id INTEGER NOT NULL, query_id INTEGER NOT NULL,
    key TEXT NOT NULL, value TEXT NOT NULL);
CREATE TABLE IF NOT EXISTS serving(
    run_id INTEGER NOT NULL, serve_id INTEGER NOT NULL,
    resolved TEXT NOT NULL DEFAULT '',
    error INTEGER NOT NULL DEFAULT 0,
    latency_s REAL NOT NULL DEFAULT 0,
    stage TEXT NOT NULL, seconds REAL NOT NULL DEFAULT 0);
CREATE TABLE IF NOT EXISTS bench_metrics(
    run_id INTEGER NOT NULL, metric TEXT NOT NULL,
    path TEXT NOT NULL, value REAL NOT NULL,
    higher_better INTEGER);            -- NULL = direction-less
"""

_ROTATED = re.compile(r"^(?P<base>.+)\.(\d+)$")

#: (label, dotted path into the payload, higher-is-better or None)
PAYLOAD_METRICS: List[Tuple[str, str, Optional[bool]]] = [
    ("rows/s", "value", True),
    ("vs CPU baseline", "vs_baseline", True),
    ("TPU wall s", "tpu_s", False),
    ("CPU wall s", "cpu_s", False),
    ("HBM fraction", "hbm_frac", True),
    ("bytes/s", "bytes_per_sec", True),
    ("pipeline overlap", "pipeline.overlap_ratio", True),
    ("producer stall s", "pipeline.producer_stall_s", False),
    ("consumer stall s", "pipeline.consumer_stall_s", False),
    ("peak spool depth", "pipeline.peak_depth", None),
    ("TPC-DS geomean", "tpcds.geomean_speedup", True),
    ("TPC-DS queries", "tpcds.queries_counted", True),
    ("faults injected", "chaos.faults_injected", None),
    ("task retries", "chaos.task_retries", False),
    ("fetch retries", "chaos.fetch_retries", False),
    ("query tasks", "query_metrics.tasks", None),
    ("query spill bytes", "query_metrics.spill_bytes", False),
    ("programs built", "event_log.audit.programs", None),
    ("audit errors", "event_log.audit.errors", False),
]


def _dig(payload: Dict, dotted: str):
    cur = payload
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def _load_payload(path: str) -> Dict:
    """One payload file, whichever capture shape it arrived in:

    - a driver wrapper (a pretty-printed doc whose ``parsed`` field
      holds the payload, with the raw stream tail under ``tail``),
    - a run's own stdout (one JSON line, possibly preceded by stderr
      snapshots in merged-stream captures — the LAST parseable line
      wins, matching the 'final stdout line is the payload' contract).
    """
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict):
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            return parsed
        if "tail" in doc and isinstance(doc["tail"], str):
            # no parsed payload: fall through to line-scanning the tail
            text = doc["tail"]
        else:
            return doc
    last = None
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            d = json.loads(line)
        except ValueError:
            continue
        if isinstance(d, dict):
            last = d
    if last is None:
        raise ValueError(f"{path!r} contains no JSON payload line")
    return last


class HistoryWarehouse:
    """One open warehouse.  Context-manage it: ``with
    HistoryWarehouse(path) as wh: wh.ingest(...)``."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        if d and not os.path.isdir(d):
            os.makedirs(d, exist_ok=True)
        self._db = sqlite3.connect(path)
        self._db.executescript(_TABLES)
        try:
            # pre-digest warehouses migrate in place; their existing
            # runs keep '' (never matched, so never deduped against)
            self._db.execute("ALTER TABLE runs ADD COLUMN content_digest"
                             " TEXT NOT NULL DEFAULT ''")
        except sqlite3.OperationalError:
            pass        # column already exists (fresh DDL or migrated)
        self._db.execute(
            "INSERT OR IGNORE INTO meta(key, value) VALUES (?, ?)",
            ("history_schema_version", str(HISTORY_SCHEMA_VERSION)))
        self._db.commit()

    def close(self) -> None:
        self._db.close()

    def __enter__(self) -> "HistoryWarehouse":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- ingestion -----------------------------------------------------------
    def ingest(self, path: str, label: str = "",
               force: bool = False) -> List[Dict]:
        """Path-level entry: a file ingests as one run (sniffed event
        log vs bench payload); a directory ingests every non-rotated
        file inside it, each as its own run (rotated ``.N`` siblings
        ride with their base log, like the reader).

        Idempotent by content: re-ingesting the same path with the same
        content digest UPDATES the existing run row (child rows purged
        and re-inserted under the same run_id) instead of inserting a
        duplicate that would skew the regress baseline.  ``force=True``
        always inserts a new run."""
        if os.path.isdir(path):
            out = []
            names = sorted(os.listdir(path))
            present = set(names)
            for name in names:
                m = _ROTATED.match(name)
                if m and m.group("base") in present:
                    continue        # a rotated sibling of another entry
                fp = os.path.join(path, name)
                if not os.path.isfile(fp):
                    continue
                out.append(self.ingest_file(fp, label=label, force=force))
            return out
        return [self.ingest_file(path, label=label, force=force)]

    def ingest_file(self, path: str, label: str = "",
                    force: bool = False) -> Dict:
        if _sniff_event_log(path):
            return self.ingest_log(path, label=label, force=force)
        return self.ingest_payload(path, label=label, force=force)

    #: run-scoped child tables, purged on an idempotent re-ingest
    _CHILD_TABLES = ("queries", "spans", "stage_programs", "transitions",
                     "spills", "ici", "compiles", "confs", "serving",
                     "bench_metrics")

    def _existing_run(self, src: str, digest: str) -> Optional[int]:
        if not digest:
            return None
        row = self._db.execute(
            "SELECT run_id FROM runs WHERE source = ? AND"
            " content_digest = ? ORDER BY run_id LIMIT 1",
            (src, digest)).fetchone()
        return row[0] if row else None

    def _purge_children(self, cur, run_id: int) -> None:
        for table in self._CHILD_TABLES:
            cur.execute(f"DELETE FROM {table} WHERE run_id = ?",
                        (run_id,))

    def ingest_log(self, path: str, label: str = "",
                   force: bool = False) -> Dict:
        """One event log (rotated/gzip set) -> one run."""
        from spark_rapids_tpu.tools.profile import attribute
        from spark_rapids_tpu.tools.reader import (profiles_from_events,
                                                   read_events)
        events, diag = read_events(path)
        profiles, diag = profiles_from_events(events, diag)
        src = os.path.abspath(path)
        digest = _content_digest(path, rotated=True)
        versions = ",".join(str(v)
                            for v in sorted(set(diag.header_versions)))
        cur = self._db.cursor()
        run_id = None if force else self._existing_run(src, digest)
        updated = run_id is not None
        if updated:
            self._purge_children(cur, run_id)
            cur.execute(
                "UPDATE runs SET label = ?, status = 'ok',"
                " ingested_at = ?, schema_versions = ?, queries = ?,"
                " truncated_lines = ?, dropped_events = ?"
                " WHERE run_id = ?",
                (label, time.time(), versions, len(profiles),
                 diag.truncated_lines, diag.dropped_events, run_id))
        else:
            cur.execute(
                "INSERT INTO runs(kind, source, label, status,"
                " ingested_at, schema_versions, queries,"
                " truncated_lines, dropped_events, content_digest)"
                " VALUES ('event_log', ?, ?, 'ok', ?, ?, ?, ?, ?, ?)",
                (src, label, time.time(), versions, len(profiles),
                 diag.truncated_lines, diag.dropped_events, digest))
            run_id = cur.lastrowid
        counts = {"queries": 0, "spans": 0, "programs": 0,
                  "transitions": 0, "spills": 0, "ici": 0,
                  "compiles": 0, "confs": 0, "serving": 0}
        for ordinal, qp in enumerate(profiles):
            self._ingest_profile(cur, run_id, ordinal, qp,
                                 attribute, counts)
        # serving decompositions are emitted OUTSIDE any query scope
        for ev in events:
            if ev.kind != "servingAdmission" \
                    or ev.payload.get("op") != "complete":
                continue
            p = ev.payload
            for stage, secs in p.items():
                if not stage.endswith("_s") or stage == "latency_s":
                    continue
                cur.execute(
                    "INSERT INTO serving(run_id, serve_id, resolved,"
                    " error, latency_s, stage, seconds)"
                    " VALUES (?, ?, ?, ?, ?, ?, ?)",
                    (run_id, int(p.get("serve_id", -1)),
                     str(p.get("resolved", "")),
                     1 if p.get("error") else 0,
                     float(p.get("latency_s", 0.0) or 0.0),
                     stage, float(secs or 0.0)))
                counts["serving"] += 1
        self._db.commit()
        return {"run_id": run_id, "kind": "event_log",
                "source": src, "updated": updated,
                "schema_versions": sorted(set(diag.header_versions)),
                **counts}

    def _ingest_profile(self, cur, run_id: int, ordinal: int, qp,
                        attribute, counts: Dict) -> None:
        summary = qp.summary or {}
        cur.execute(
            "INSERT INTO queries(run_id, query_id, run_gen, ordinal,"
            " description, status, wall_s, tasks, spill_bytes, events,"
            " complete) VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?, ?, ?)",
            (run_id, qp.query_id, qp.run, ordinal, qp.description,
             str(summary.get("status", "")), qp.wall_s,
             int(summary.get("tasks", 0) or 0),
             int(summary.get("spill_bytes", 0) or 0),
             len(qp.events), 1 if qp.complete else 0))
        counts["queries"] += 1
        att = attribute(qp)
        for op in att.operators:
            cur.execute(
                "INSERT INTO spans(run_id, query_id, span_id, node,"
                " bucket, exclusive_s, inclusive_s, rows, batches)"
                " VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?)",
                (run_id, qp.query_id, op.span_id, op.name, op.bucket,
                 op.exclusive_s, op.inclusive_s, op.rows, op.batches))
            counts["spans"] += 1
        for ev in qp.events_of("stageProgram"):
            p = ev.payload
            cur.execute(
                "INSERT INTO stage_programs(run_id, query_id, span_id,"
                " stage_kind, key, flops, bytes_accessed, eqns, n_args)"
                " VALUES (?, ?, ?, ?, ?, ?, ?, ?, ?)",
                (run_id, qp.query_id, ev.span_id,
                 str(p.get("stage_kind", "?")), str(p.get("key", "?")),
                 p.get("flops"), p.get("bytes_accessed"),
                 int(p.get("eqns", 0) or 0), int(p.get("n_args", 0) or 0)))
            counts["programs"] += 1
        for ev in qp.events_of("hostTransition"):
            p = ev.payload
            cur.execute(
                "INSERT INTO transitions(run_id, query_id, direction,"
                " bytes, seconds) VALUES (?, ?, ?, ?, ?)",
                (run_id, qp.query_id, str(p.get("direction", "?")),
                 int(p.get("bytes", 0) or 0),
                 float(p.get("duration_s", 0.0) or 0.0)))
            counts["transitions"] += 1
        for ev in qp.events_of("deviceSync"):
            cur.execute(
                "INSERT INTO transitions(run_id, query_id, direction,"
                " bytes, seconds) VALUES (?, ?, 'sync', 0, ?)",
                (run_id, qp.query_id,
                 float(ev.payload.get("duration_s", 0.0) or 0.0)))
            counts["transitions"] += 1
        for ev in qp.events_of("spill", "unspill"):
            p = ev.payload
            cur.execute(
                "INSERT INTO spills(run_id, query_id, op, bytes,"
                " logical_bytes, seconds) VALUES (?, ?, ?, ?, ?, ?)",
                (run_id, qp.query_id, ev.kind,
                 int(p.get("bytes", 0) or 0),
                 int(p.get("logical_bytes", 0) or 0),
                 float(p.get("duration_s", 0.0) or 0.0)))
            counts["spills"] += 1
        for ev in qp.events_of("iciExchange"):
            p = ev.payload
            cur.execute(
                "INSERT INTO ici(run_id, query_id, devices, rows,"
                " seconds) VALUES (?, ?, ?, ?, ?)",
                (run_id, qp.query_id, int(p.get("devices", 0) or 0),
                 int(p.get("rows", 0) or 0),
                 float(p.get("duration_s", 0.0) or 0.0)))
            counts["ici"] += 1
        for ev in qp.events_of("stageCompile"):
            p = ev.payload
            cur.execute(
                "INSERT INTO compiles(run_id, query_id, stage_kind,"
                " seconds) VALUES (?, ?, ?, ?)",
                (run_id, qp.query_id, str(p.get("stage_kind", "?")),
                 float(p.get("duration_s", 0.0) or 0.0)))
            counts["compiles"] += 1
        for key, value in (qp.conf or {}).items():
            cur.execute(
                "INSERT INTO confs(run_id, query_id, key, value)"
                " VALUES (?, ?, ?, ?)",
                (run_id, qp.query_id, str(key), str(value)))
            counts["confs"] += 1

    def ingest_payload(self, source, label: str = "",
                       force: bool = False) -> Dict:
        """One benchmark payload (path or already-loaded dict)
        -> one run of metric rows.  A failed run (placeholder zeros) is
        recorded with ``status='failed'`` and no metric rows.  Path
        sources dedupe by content digest like event logs; an
        already-loaded dict always inserts — there is no stable source
        identity to match."""
        from spark_rapids_tpu.tools.regression import run_failure
        if isinstance(source, str):
            payload = _load_payload(source)
            src = os.path.abspath(source)
            digest = _content_digest(source)
        else:
            payload = dict(source or {})
            src = "<payload>"
            digest = ""
        why = run_failure(payload)
        status = "failed" if why is not None else "ok"
        cur = self._db.cursor()
        run_id = None if force else self._existing_run(src, digest)
        updated = run_id is not None
        if updated:
            self._purge_children(cur, run_id)
            cur.execute(
                "UPDATE runs SET label = ?, status = ?, ingested_at = ?"
                " WHERE run_id = ?",
                (label, status, time.time(), run_id))
        else:
            cur.execute(
                "INSERT INTO runs(kind, source, label, status,"
                " ingested_at, content_digest)"
                " VALUES ('bench', ?, ?, ?, ?, ?)",
                (src, label, status, time.time(), digest))
            run_id = cur.lastrowid
        metrics = 0
        if why is None:
            for mlabel, dotted, higher in PAYLOAD_METRICS:
                v = _dig(payload, dotted)
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool):
                    continue
                cur.execute(
                    "INSERT INTO bench_metrics(run_id, metric, path,"
                    " value, higher_better) VALUES (?, ?, ?, ?, ?)",
                    (run_id, mlabel, dotted, float(v),
                     None if higher is None else int(higher)))
                metrics += 1
            # per-query TPC-DS trajectory: the speedups bench measured
            per_query = ((payload.get("tpcds") or {})
                         .get("queries") or {})
            for qname, row in sorted(per_query.items()):
                for field, higher in (("speedup", True), ("tpu_s", False)):
                    v = (row or {}).get(field)
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool):
                        cur.execute(
                            "INSERT INTO bench_metrics(run_id, metric,"
                            " path, value, higher_better)"
                            " VALUES (?, ?, ?, ?, ?)",
                            (run_id, f"{qname}.{field}",
                             f"tpcds.queries.{qname}.{field}",
                             float(v), int(higher)))
                        metrics += 1
        self._db.commit()
        return {"run_id": run_id, "kind": "bench", "source": src,
                "status": status, "updated": updated,
                "failure": why, "metrics": metrics}

    # -- queries over the warehouse -----------------------------------------
    def query(self, sql: str, params: Tuple = ()) -> List[Tuple]:
        return self._db.execute(sql, params).fetchall()

    def runs(self) -> List[Dict]:
        cols = ("run_id", "kind", "source", "label", "status",
                "ingested_at", "schema_versions", "queries",
                "truncated_lines", "dropped_events")
        return [dict(zip(cols, row)) for row in self.query(
            "SELECT " + ", ".join(cols) + " FROM runs ORDER BY run_id")]

    def report(self) -> Dict:
        counts = {}
        for table in ("runs", "queries", "spans", "stage_programs",
                      "transitions", "spills", "ici", "compiles",
                      "confs", "serving", "bench_metrics"):
            counts[table] = self.query(
                f"SELECT COUNT(*) FROM {table}")[0][0]
        return {"path": self.path,
                "history_schema_version": HISTORY_SCHEMA_VERSION,
                "tables": counts, "runs": self.runs()}


def render_report(report: Dict) -> str:
    t = report["tables"]
    lines = [f"== history warehouse {report['path']} "
             f"(schema v{report['history_schema_version']}) =="]
    lines.append("  " + "  ".join(f"{k}={v}" for k, v in t.items()))
    lines.append(f"{'run':>4} {'kind':<10}{'status':<8}{'label':<14}"
                 f"{'queries':>8}  source")
    for r in report["runs"]:
        lines.append(f"{r['run_id']:>4} {r['kind']:<10}{r['status']:<8}"
                     f"{(r['label'] or '-'):<14}{r['queries']:>8}  "
                     f"{os.path.basename(r['source'])}")
    return "\n".join(lines) + "\n"


def _content_digest(path: str, rotated: bool = False) -> str:
    """sha256 of the file's bytes — the idempotency key alongside the
    absolute path.  For event logs, rotated ``.N`` siblings fold in
    (numeric order): the ingested run covers the whole set, so its
    identity must too.  Unreadable files digest as '' (never matched)."""
    h = hashlib.sha256()
    paths = [path]
    if rotated:
        d = os.path.dirname(os.path.abspath(path)) or "."
        base = os.path.basename(path)
        sibs = []
        try:
            for name in os.listdir(d):
                m = _ROTATED.match(name)
                if m and m.group("base") == base:
                    sibs.append((int(name.rsplit(".", 1)[1]),
                                 os.path.join(d, name)))
        except OSError:
            pass
        paths.extend(p for _, p in sorted(sibs))
    read_any = False
    for p in paths:
        try:
            with open(p, "rb") as f:
                for chunk in iter(lambda: f.read(1 << 20), b""):
                    h.update(chunk)
            read_any = True
        except OSError:
            continue
    return h.hexdigest() if read_any else ""


def _sniff_event_log(path: str) -> bool:
    """True when the file reads as a JSONL event log (the first
    parseable line carries an ``event`` field) — gzip members sniffed
    by magic like the reader."""
    import gzip
    try:
        with open(path, "rb") as f:
            head = f.read(2)
            f.seek(0)
            data = gzip.GzipFile(fileobj=f).read(65536) \
                if head == b"\x1f\x8b" else f.read(65536)
    except OSError:
        return False
    for raw in data.decode("utf-8", errors="replace").splitlines():
        raw = raw.strip()
        if not raw:
            continue
        try:
            d = json.loads(raw)
        except ValueError:
            return False
        return isinstance(d, dict) and "event" in d
    return False
