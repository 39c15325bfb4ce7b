"""exchange layer: share of the device's program time in the exchange's own
programs (kinds that start ``exchange.``: the trace names a program
``jit_run_<kind>``, which the reduction shortens to the kind).  A program
that dispatches the exchange's work under no kind of its own (the parent of
the PR that named them) has no such program in its trace and leaves the
metric out."""


def read(run):
    t = run.trace
    programs = (t or {}).get("programs")
    if not programs:
        return None
    total = sum(seconds for _, seconds in programs)
    mine = [seconds for name, seconds in programs
            if name.startswith("exchange.")]
    if not total or not mine:
        return None
    return 100.0 * sum(mine) / total
