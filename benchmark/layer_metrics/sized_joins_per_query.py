"""operators layer: probe batches of hash joins whose pair table and
output batch were sized by the probe's fetched candidate total (one
scalar sync, site ``join-size``) and not by the probe's bucket, a query
of the window: the mean of the summaries' ``sized_joins``.  Every probe
batch over the join's floor (32,768 rows) counts one; a query whose joins
all speculate counts 0, and a replay counts every join again.  A program
that does not count it (the parent of the PR that added the counter)
leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["sized_joins"])
