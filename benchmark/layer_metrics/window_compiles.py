"""compile layer: programs compiled or traced inside the window (should be 0)."""


def read(run):
    before, after = run.counters["before"], run.counters["after"]
    return float(sum(after["stage_compiler"][k] - before["stage_compiler"][k]
                     for k in ("compiles", "traces")))
