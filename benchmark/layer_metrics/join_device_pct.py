"""operators layer: share of the device's program time in the join's
programs (kinds that start ``join.``: the trace names a program
``jit_run_<kind>``, which the reduction shortens to the kind)."""


def read(run):
    t = run.trace
    programs = (t or {}).get("programs")
    if not programs:
        return None
    total = sum(seconds for _, seconds in programs)
    if not total:
        return None
    joins = sum(seconds for name, seconds in programs
                if name.startswith("join."))
    return 100.0 * joins / total
