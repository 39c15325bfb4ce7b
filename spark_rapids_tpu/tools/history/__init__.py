"""Cross-run metrics warehouse (``python -m spark_rapids_tpu.tools
history ...``).

Every run's telemetry used to die with its log file; this package is
the durable substrate under the offline toolkit — a SQLite warehouse
(``spark.rapids.history.path``) that ingests event logs (schemas v1–v4)
and benchmark payloads into normalized tables, and three
consumers over the accumulated history:

- ``report``: what the warehouse holds (runs, queries, spans, ledger
  rows) — the inventory view;
- ``regress``: the trajectory sentinel — the latest run vs the history
  baseline per query/metric with noise-aware thresholds (min-runs,
  median-absolute-deviation bands, from tools/regression.py), nonzero
  exit on regression;
- ``calibrate``: joins the audit ledger's flops/bytes to measured
  per-stage-kind exclusive time and fits a machine profile (achieved
  byte/s and FLOP/s per stage kind, per-dispatch fixed overhead,
  H2D/D2H bandwidth from the transition ledger, spill and compile
  costs), emitted as a versioned JSON artifact with residual
  statistics.  ``plan/cost.py`` loads that artifact to annotate plans
  with predicted cost (``== Cost ==`` in ``df.explain()``) and the
  tracer cross-checks prediction vs measurement post-run.

Stdlib-only (sqlite3 + the reader/profile modules), like the rest of
``spark_rapids_tpu.tools`` — no jax, no device, no running engine.
Reference: the spark-rapids-tools Qualification/Profiling pair keeps
per-application metric stores for exactly this cross-run analysis.
"""

from spark_rapids_tpu.tools.history.calibrate import (calibrate,
                                                      render_profile)
from spark_rapids_tpu.tools.history.regress import regress, render_regress
from spark_rapids_tpu.tools.history.warehouse import (HISTORY_SCHEMA_VERSION,
                                                      HistoryWarehouse)

__all__ = ["HistoryWarehouse", "HISTORY_SCHEMA_VERSION", "calibrate",
           "render_profile", "regress", "render_regress"]
