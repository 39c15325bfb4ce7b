"""exchange layer: mean time of a query of the window inside the shuffle
exchange's own spans: ``phases["exchange.write"]`` (a map task's partition
ids, split and store) plus ``phases["exchange.read"]`` (a reduce
partition's pieces handed on, with the upload of host-staged ones): self
time, so the child operator's work under a map task is not in it.  A
program that counts no ``exchanges`` (the parent of the PR that added the
spans and the counter) leaves the metric out; a query that ran no exchange
counts 0."""

from benchmark.spans import mean_per_query


def read(run):
    def exchange_ms(s):
        s["exchanges"]                      # a program without the counter
        phases = s["phases"]
        return 1e3 * (phases.get("exchange.write", 0.0)
                      + phases.get("exchange.read", 0.0))
    return mean_per_query(run, exchange_ms)
