"""device layer: mean time per query in which the device's queue was empty
between a fetch of the query and the query's own next device work: the
host's turn-around inside a query (sizing and dispatching after a
``join-size`` or ``stage-size`` fetch).  Read from the gateway's gap ledger
(the summary's ``device_gaps.fetch_s``, ``aux/transitions.py``), which needs
no profiler, so the traced and the untraced run read the same thing.  A
program without the ledger (the parent of the PR that added it) leaves the
metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: 1e3 * s["device_gaps"]["fetch_s"])
