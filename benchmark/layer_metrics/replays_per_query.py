"""operators layer: whole-query replays per query: a speculative bucket
overflowed and ``collect_with_speculation`` ran the action again in exact
mode.  A query that ran twice otherwise reads as a query that was slow."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(run, lambda s: s["speculation_replays"])
