"""operators layer: rows the device was handed, padding included, over the
rows that were live.  Over the device operators of the window's queries:
the buckets of every batch they put out plus the hash joins' padded pair
tables, over the output rows the program knows without a sync (a deferred
count that nothing forced is in neither sum's favour: its batch counts
above and not below, so the factor is an upper reading and falls when the
padding does)."""

from benchmark.spans import window_summaries


def read(run):
    summaries = window_summaries(run)
    if summaries is None:
        return None
    padded = live = 0
    try:
        for s in summaries:
            padded += s["pair_rows_padded"]
            for node in s["nodes"]:
                if not node["device"]:
                    continue
                padded += sum(p["padded_rows"]
                              for p in node.get("partitions", ()))
                live += node.get("numOutputRows", 0)
    except KeyError:
        return None
    return padded / live if live else None
