"""Generators of a configuration's data, one module each; the configuration's
file names its module under ``datagen`` and that module's arguments under
``datagen_args``.  The harness asks of a module:

``make(rows, seed, **datagen_args)``
    the generator of one seed at one scale, with ``col(table, name)`` (the
    column, as the reference reads it) and ``n(table)`` (its rows);
``columns_named(words)``
    table -> the schema's columns among the words of a query text;
``arrow_tables(gen, wanted, integer_type)``
    table -> the arrow table the session registers;
``column_width(name, column)``
    bytes per row as the configuration types the column.
"""
