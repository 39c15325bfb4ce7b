"""device layer: mean time per query in which the device's queue was empty
from another query's fetch (the previous query's answer, in one session) to
this query's first device work: the harness's own time between queries and
the text's parse, analysis and rewrite.  Read from the gateway's gap ledger
(the summary's ``device_gaps.query_s``, ``aux/transitions.py``), with or
without the profiler.  A program without the ledger (the parent of the PR
that added it) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: 1e3 * s["device_gaps"]["query_s"])
