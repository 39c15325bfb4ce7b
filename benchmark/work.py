"""The least work a query needs, counted from its text and the data and from
nothing the engine does: the bytes of the columns the text names, once each,
at the width the configuration's types give them.  A scan cannot read less
whatever implements it, so bytes over the peak bandwidth is the floor of the
device time and the roofline share's numerator.  ``datagen`` is the
configuration's generator module (``datagen/__init__.py``)."""

from __future__ import annotations


def query_columns(query, datagen) -> dict:
    """table -> the columns of the schema that the text names."""
    return datagen.columns_named(query.words())


def query_bytes(query, gen, datagen) -> float:
    """Least bytes one execution of ``query`` reads from the tables."""
    return float(sum(
        gen.n(table) * datagen.column_width(c, gen.col(table, c))
        for table, cols in query_columns(query, datagen).items()
        for c in cols))
