"""operators layer: probe batches of hash joins that probed a
direct-address table (one gather a probe row, no verify, no pair
compaction: the build side's one integral key was unique and narrowly
spread), a query of the window: the mean of the summaries'
``direct_joins``.  Every fact-to-dimension join on a surrogate key counts
one a probed batch; a join the rule sends to the hash path counts 0.  A
program that does not count it (the parent of the PR that added the
counter) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["direct_joins"])
