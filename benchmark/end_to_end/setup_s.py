"""Process start to the first timed query: imports, the native library, data
made from the seed, tables registered, every text of the cell warmed (upload
and compile, or the load from the compile cache)."""


def read(run):
    return run.setup_s
