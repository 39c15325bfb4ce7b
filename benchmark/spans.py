"""What the per-layer readers take from the program's own spans and
counters: the per-query summaries that ``QueryExecution.finish`` publishes
(``spark_rapids_tpu/aux/tracing.py``), one for every query the window ran.

``run.py`` snapshots only the counters it names, so what the program counts
per query rides its summary: ``phases`` (self seconds by span name),
``dispatches``, ``speculation_replays``, ``pair_rows_padded`` and the
``nodes`` with their partitions' ``rows`` and ``padded_rows``.  A program
that publishes no such thing (the parent of the PR that added them) gives
``None``, and the reader leaves its metric out."""

from __future__ import annotations


def window_summaries(run):
    """The summaries of the window's queries, oldest first: the newest
    ``len(run.records)`` the program holds.  ``None`` where it holds fewer
    (a served query answered from the result cache runs no query)."""
    try:
        from spark_rapids_tpu.aux.tracing import recent_summaries
    except ImportError:
        return None
    n = len(run.records)
    held = recent_summaries()
    if n == 0 or len(held) < n:
        return None
    return held[-n:]


def mean_per_query(run, value):
    """Mean of ``value(summary)`` over the window's queries; ``None`` where
    a summary lacks what ``value`` reads (``KeyError``)."""
    summaries = window_summaries(run)
    if summaries is None:
        return None
    try:
        return float(sum(value(s) for s in summaries)) / len(summaries)
    except KeyError:
        return None
