"""exchange layer: rows the exchange stored, padding included, over the
rows that were live: ``exchange_rows_padded`` (the buckets of the pieces a
map task stored or staged) over ``exchange_rows`` (the live rows it wrote),
sums over the window's queries.  A store that keeps, for every map batch,
one compacted copy a reduce partition at the input's bucket reads n times
the input's own padding.  A program that counts neither (the parent of the
PR that added the counters) leaves the metric out, as does a window that
exchanged no row."""

from benchmark.spans import window_summaries


def read(run):
    summaries = window_summaries(run)
    if summaries is None:
        return None
    try:
        padded = sum(s["exchange_rows_padded"] for s in summaries)
        live = sum(s["exchange_rows"] for s in summaries)
    except KeyError:
        return None
    return padded / live if live else None
