"""Queries completed in the window over the window's seconds, all streams
together.  A stream submits nothing after ``--seconds``; the query it has in
flight then is waited for and counted, and the window runs from its first
submission to its last answer, so the rate is over all the work and all the
time whatever a query takes."""


def read(run):
    if not run.completed:
        return None
    return len(run.completed) / run.seconds
