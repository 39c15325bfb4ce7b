"""parse/plan layer: share of the window's plan-cache lookups that found
the query's plan structure cached: an exact repeat (``hits``: no planning,
no compile) or the same structure with other literals (``norm_hits``: the
query plans again and shares the entry's compiled programs), over all
lookups (hits, misses, which hold the norm hits, and busy bypasses), from
the server's counters on both sides of the window (``driver.counters()``).
A driver without a server reports none and the metric is left out."""


def read(run):
    before = run.counters["before"]["server"].get("plan_cache")
    after = run.counters["after"]["server"].get("plan_cache")
    if not before or not after:
        return None
    try:
        d = {k: after[k] - before[k]
             for k in ("hits", "norm_hits", "misses", "busy_bypass")}
    except KeyError:
        return None
    lookups = d["hits"] + d["misses"] + d["busy_bypass"]
    if not lookups:
        return None
    return 100.0 * (d["hits"] + d["norm_hits"]) / lookups
