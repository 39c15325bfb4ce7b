"""operators layer: batches of fused stages whose compact terminal was
sized by the filter's fetched live count (one scalar sync, site
``stage-size``) and not by the input's bucket, a query of the window: the
mean of the summaries' ``sized_stages``.  Every batch over the stage's
floor that a stage with a filter takes counts one; a query whose stages
all stay at or under the floor counts 0.  A program that does not count it
(the parent of the PR that added the counter) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["sized_stages"])
