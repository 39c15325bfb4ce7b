"""parse/plan layer: mean host time per query from text to plan.  In a
session the harness clocks ``session.sql(text)``; a served query reports
``lookup_s + plan_s`` in ``Submission.info["stages"]``."""


def read(run):
    times = [q["plan_s"] for q in run.completed if q.get("plan_s") is not None]
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
