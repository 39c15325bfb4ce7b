"""operators layer: the least time the chip could take to read what the
traced queries have to read (``work.query_bytes`` over the peak HBM
bandwidth), as a share of the time the device was busy for them.  It is
bound by bandwidth: the queries do a few operations per byte."""


from benchmark.work import query_bytes


def read(run):
    t = run.trace
    if t is None or not t["busy_s"]:
        return None
    traced = [q for q in run.completed if q.get("traced")]
    if not traced:
        return None
    least = {name: query_bytes(query, run.gen, run.datagen)
             for name, query in run.cell.queries.items()}
    least_s = sum(least[q["q"]] for q in traced) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
