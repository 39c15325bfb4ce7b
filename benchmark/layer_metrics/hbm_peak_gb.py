"""device layer: peak bytes in use on the chip after the window."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
