"""Chrome-trace / Perfetto export of an event log: ``tools trace``.

Renders a JSONL event log as the Trace Event Format that
``chrome://tracing`` and https://ui.perfetto.dev load directly — the
same move the reference ecosystem makes with Nsight/NVTX ranges, but
from the engine's own schema-v4 events, offline, with no profiler
attached to the run:

- one **process per query** (process_name metadata = query id +
  description), timestamps relative to the query run's earliest event;
- the exec-span tree renders as nested complete ("X") slices on a
  ``plan`` thread (span nesting reconstructs operator containment);
- the query path's phase spans (``plan.rewrite``, ``exec.run``,
  ``xfer.d2h``...; ``aux.tracing.span``) render on a ``phases`` thread;
- per-partition task timelines render on one thread per partition
  index — the gantt ``tools profile`` draws in ASCII, zoomable;
- duration-carrying events land on per-resource threads:
  ``transitions`` (hostTransition H2D/D2H + deviceSync, slices drawn
  backward from their emit timestamp over the measured duration),
  ``compile`` (stageCompile), ``spill`` (spill/unspill), ``ici``
  (iciExchange);
- resource samples inside the query window render as counter ("C")
  tracks (device pool bytes, active tasks).

The module is stdlib-only (reader + json), like the rest of the tools
package.  ``unattributed`` counts hostTransition/deviceSync events that
fired OUTSIDE any traced query (query_id == -1): every transfer the
gateway sees during a traced run should belong to a query, and
``tests/test_transitions.py::test_trace_cli_roundtrip_and_check`` holds a
traced query's round trip to zero of them.
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.aux.events import NO_QUERY
from spark_rapids_tpu.tools.reader import (QueryProfile, ReadDiagnostics,
                                           SpanNode, profiles_from_events,
                                           read_events)

#: fixed thread ids per process (query); partition tracks start above
_TID_PLAN = 1
_TID_TRANSITIONS = 2
_TID_COMPILE = 3
_TID_SPILL = 4
_TID_ICI = 5
_TID_PHASES = 6
_TID_PARTITION_BASE = 100

#: event kind -> (thread id, slice-name prefix) for duration events
_DURATION_TRACKS = {
    "hostTransition": (_TID_TRANSITIONS, None),
    "deviceSync": (_TID_TRANSITIONS, "sync"),
    "stageCompile": (_TID_COMPILE, "compile"),
    "spill": (_TID_SPILL, "spill"),
    "unspill": (_TID_SPILL, "unspill"),
    "iciExchange": (_TID_ICI, "ici"),
}


def _us(seconds: float) -> float:
    """Trace Event Format timestamps are microseconds."""
    return round(seconds * 1e6, 3)


def _meta(pid: int, name: str, tid: Optional[int] = None,
          thread_name: Optional[str] = None) -> Dict:
    if tid is None:
        return {"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": name}}
    return {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
            "args": {"name": thread_name or name}}


def _span_slices(sp: SpanNode, pid: int, base: float,
                 out: List[Dict]) -> None:
    if sp.start_s is not None and sp.end_s is not None:
        out.append({"ph": "X", "pid": pid, "tid": _TID_PLAN,
                    "ts": _us(sp.start_s - base),
                    "dur": _us(max(0.0, sp.end_s - sp.start_s)),
                    "name": sp.name, "cat": "plan",
                    "args": dict(sp.metrics)})
    for part in sp.partitions:
        ps, pe = part.get("start_s"), part.get("end_s")
        pidx = part.get("pidx")
        if ps is None or pe is None or pidx is None:
            continue
        out.append({"ph": "X", "pid": pid,
                    "tid": _TID_PARTITION_BASE + int(pidx),
                    "ts": _us(ps - base),
                    "dur": _us(max(0.0, pe - ps)),
                    "name": f"{sp.name} p{pidx}", "cat": "task",
                    "args": {"rows": part.get("rows", 0),
                             "batches": part.get("batches", 0)}})
    for c in sp.children:
        _span_slices(c, pid, base, out)


def _query_events(qp: QueryProfile, pid: int, base: float,
                  out: List[Dict]) -> None:
    """Duration events + counters for one query's process."""
    for ev in qp.events:
        track = _DURATION_TRACKS.get(ev.kind)
        if track is None:
            continue
        tid, prefix = track
        dur = float(ev.payload.get("duration_s", 0.0) or 0.0)
        if ev.kind == "hostTransition":
            name = str(ev.payload.get("direction", "transition"))
        elif ev.kind == "deviceSync":
            name = f"sync:{ev.payload.get('site', '?')}"
        elif prefix:
            name = prefix
        else:
            name = ev.kind
        # emit happens AFTER the measured operation: the slice ends at
        # the event timestamp and starts duration earlier
        out.append({"ph": "X", "pid": pid, "tid": tid,
                    "ts": _us(max(0.0, ev.ts - dur - base)),
                    "dur": _us(dur), "name": name, "cat": ev.kind,
                    "args": {k: v for k, v in ev.payload.items()
                             if isinstance(v, (int, float, str, bool))}})
    for s in qp.samples:
        out.append({"ph": "C", "pid": pid, "tid": 0,
                    "ts": _us(s.ts - base), "name": "pool_used_bytes",
                    "args": {"bytes":
                             int(s.payload.get("pool_used_bytes", 0)
                                 or 0)}})
        out.append({"ph": "C", "pid": pid, "tid": 0,
                    "ts": _us(s.ts - base), "name": "active_tasks",
                    "args": {"tasks":
                             int(s.payload.get("active_tasks", 0) or 0)}})


def build_trace(profiles: List[QueryProfile],
                query_id: Optional[int] = None) -> Dict:
    """The Trace Event Format document for the selected queries."""
    selected = [p for p in profiles
                if query_id is None or p.query_id == query_id]
    events: List[Dict] = []
    #: per process-run timebase: a restart restarts the monotonic clock,
    #: so queries only share a zero with queries of their OWN run
    run_base: Dict[int, float] = {}
    for qp in selected:
        if qp.start_ts is None:
            continue
        cur = run_base.get(qp.run)
        run_base[qp.run] = qp.start_ts if cur is None \
            else min(cur, qp.start_ts)
    for i, qp in enumerate(selected):
        if qp.start_ts is None:
            continue
        pid = i + 1
        base = run_base[qp.run]
        label = (f"query {qp.query_id}"
                 + (f" run {qp.run}" if qp.run else "")
                 + (f" {qp.description!r}" if qp.description else ""))
        events.append(_meta(pid, label))
        events.append(_meta(pid, "", _TID_PLAN, "plan"))
        events.append(_meta(pid, "", _TID_TRANSITIONS, "transitions"))
        events.append(_meta(pid, "", _TID_COMPILE, "compile"))
        events.append(_meta(pid, "", _TID_SPILL, "spill"))
        events.append(_meta(pid, "", _TID_ICI, "ici"))
        events.append(_meta(pid, "", _TID_PHASES, "phases"))
        pidxs = sorted({int(part["pidx"])
                        for sp in qp.exec_spans()
                        for part in sp.partitions
                        if part.get("pidx") is not None})
        for pidx in pidxs:
            events.append(_meta(pid, "", _TID_PARTITION_BASE + pidx,
                                f"partition {pidx}"))
        for root in qp.roots:
            _span_slices(root, pid, base, events)
        for sp in qp.phases:
            if sp.start_s is not None and sp.end_s is not None:
                events.append({"ph": "X", "pid": pid, "tid": _TID_PHASES,
                               # a text is planned before its query
                               # begins: the zero is the query's start
                               "ts": _us(max(0.0, sp.start_s - base)),
                               "dur": _us(max(0.0, sp.end_s - sp.start_s)),
                               "name": sp.name, "cat": "phase",
                               "args": dict(sp.metrics)})
        _query_events(qp, pid, base, events)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def unattributed_transitions(events) -> int:
    """hostTransition/deviceSync events that fired outside any traced
    query — the ledger saw a boundary crossing no query owns."""
    return sum(1 for ev in events
               if ev.kind in ("hostTransition", "deviceSync")
               and ev.query_id == NO_QUERY)


def trace_from_log(path: str, query_id: Optional[int] = None
                   ) -> Tuple[Dict, int, ReadDiagnostics]:
    """(trace document, unattributed transition count, diagnostics)."""
    events, diag = read_events(path)
    profiles, diag = profiles_from_events(events, diag)
    return (build_trace(profiles, query_id=query_id),
            unattributed_transitions(events), diag)


def render_trace(trace: Dict) -> str:
    return json.dumps(trace, separators=(",", ":"), default=str)
