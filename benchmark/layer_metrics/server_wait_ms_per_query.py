"""entry layer: mean time a served query of the window waited before its
work began: for a worker to pick it up (the span ``serve.queue``: submit
to pickup) and for admission (``serve.admit``:
``AdmissionController.admit``), from the summaries' ``phases``.  A
program whose served queries carry no such spans (the parent of the PR
that added them) leaves the metric out."""

from benchmark.spans import mean_per_query


def read(run):
    return mean_per_query(
        run, lambda s: 1e3 * (s["phases"]["serve.queue"]
                              + s["phases"]["serve.admit"]))
