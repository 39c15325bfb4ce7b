"""Offline diagnostic toolkit over the engine's JSONL event log.

The reference pairs its in-process instrumentation with EXTERNAL
qualification/profiling tools and an AutoTuner that consume event logs
offline (``spark-rapids-tools``); this package is that consumer for the
logs PR 1's sink writes:

- ``reader``   — versioned, truncation-tolerant event-log ingestion that
                 reconstructs per-query span trees and timelines;
- ``profile``  — per-query wall-clock decomposition into resource
                 buckets (decode / H2D / compute / D2H / shuffle /
                 stalls / spill / recovery) plus operator ranking;
- ``autotune`` — rule-based conf recommendations, each citing the
                 evidence events that triggered it;
- ``lint``     — static AST analysis of the engine's own source against
                 its declared invariants (docs/lint.md);
- ``history``  — persistent SQLite warehouse across runs: ingest event
                 logs/BENCH payloads, regress the latest run against
                 the accumulated baseline, and calibrate the machine
                 profile ``plan/cost.py`` predicts from (docs/history.md).

CLI: ``python -m spark_rapids_tpu.tools
<profile|autotune|trace|audit|lint|history>``
(stdlib-only; runs without jax or a device).
"""

from spark_rapids_tpu.tools.autotune import (Recommendation, autotune,
                                             render_recommendations,
                                             to_conf_dict)
from spark_rapids_tpu.tools.history import (HistoryWarehouse, calibrate,
                                            regress)
from spark_rapids_tpu.tools.profile import (Attribution, attribute,
                                            profiles_to_json,
                                            render_report)
from spark_rapids_tpu.tools.reader import (QueryProfile, ReadDiagnostics,
                                           load_profiles, read_events)

__all__ = [
    "Attribution", "HistoryWarehouse", "QueryProfile", "ReadDiagnostics",
    "Recommendation", "attribute", "autotune", "calibrate",
    "load_profiles", "profiles_to_json", "read_events", "regress",
    "render_recommendations", "render_report",
    "to_conf_dict",
]
