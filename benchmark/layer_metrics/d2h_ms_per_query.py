"""transfer layer: host-clock time in device-to-host copies and syncs, per
completed query."""


def read(run):
    if not run.completed:
        return None
    before, after = (run.counters["before"]["transitions"],
                     run.counters["after"]["transitions"])
    s = sum(after[k] - before[k] for k in ("d2h_seconds", "sync_seconds"))
    return 1e3 * s / len(run.completed)
