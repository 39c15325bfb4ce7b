"""entry layer: mean time per query in which the host was not blocked on
the device: the query's duration (the root span, parse to rows) less the
time inside the ``xfer.d2h``, ``xfer.sync`` and ``xfer.h2d`` spans.  It is
the host's own work, a floor under ``1 / qps`` that no kernel gets under."""

from benchmark.spans import mean_per_query

BLOCKED = ("xfer.d2h", "xfer.sync", "xfer.h2d")


def read(run):
    return mean_per_query(
        run, lambda s: 1e3 * (s["duration_s"] - sum(
            s["phases"].get(name, 0.0) for name in BLOCKED)))
