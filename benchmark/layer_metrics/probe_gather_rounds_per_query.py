"""operators layer: gathers a probe row makes to find its candidate range
in a hash join's build side, summed over the ``join.probe`` programs a
query of the window ran: the mean of the summaries'
``probe_gather_rounds``.  Two a probed batch where the build side carries
a bucket-start table; a binary search makes two a round, 32 to 44 a join
at SF1, and that is what a rise here means.  A program that does not
count it (the parent of the PR that added the counter) leaves the metric
out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["probe_gather_rounds"])
