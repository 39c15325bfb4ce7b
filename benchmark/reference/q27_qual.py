"""The cell ``store_star_join``'s q27 (``queries/q27_qual.sql``: query27.tpl
with its string parameters at the specification's qualification values):
the same text as ``q27.sql`` over a narrower domain, so the plain reference
is ``reference/q27.py``'s, unchanged."""
from benchmark.reference.q27 import run  # noqa: F401
