"""The cell ``store_star_join``'s q7 (``queries/q7_qual.sql``: query7.tpl
with its string parameters at the specification's qualification values):
the same text as ``q7.sql`` over a narrower domain, so the plain reference
is ``reference/q7.py``'s, unchanged."""
from benchmark.reference.q7 import run  # noqa: F401
