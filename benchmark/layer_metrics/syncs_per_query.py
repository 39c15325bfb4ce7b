"""transfer layer: host-device synchronisations per completed query."""


def read(run):
    if not run.completed:
        return None
    before, after = run.counters["before"], run.counters["after"]
    syncs = after["transitions"]["sync_count"] - before["transitions"]["sync_count"]
    return syncs / len(run.completed)
