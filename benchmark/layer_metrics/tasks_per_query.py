"""operators layer: partition tasks a query of the window ran: the mean of
the summaries' ``tasks`` (its finished tasks' ``TaskMetrics``, counted where
each task ends, so sibling tasks on several threads add up).  A
one-partition query counts the tasks of its one chain; a query over ten
partitions counts ten a stage that keeps its partitions, plus the reduce
side's.  A program whose summary has no ``tasks`` leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["tasks"])
