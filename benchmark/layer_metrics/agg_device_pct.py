"""operators layer: share of the device's program time in the grouping-set
fan-out and the aggregation's programs (kinds that start ``expand.``,
``fused.agg`` or ``agg.``: the trace names a program ``jit_run_<kind>``,
which the reduction shortens to the kind), as ``join_device_pct`` reads the
joins' share."""

KINDS = ("expand.", "fused.agg", "agg.")


def read(run):
    t = run.trace
    programs = (t or {}).get("programs")
    if not programs:
        return None
    total = sum(seconds for _, seconds in programs)
    if not total:
        return None
    return 100.0 * sum(seconds for name, seconds in programs
                       if name.startswith(KINDS)) / total
