"""Peak bytes in use on the fullest chip at the close of the window
(``device.memory_stats()``), in GB."""


def read(record, args, ctx):
    peak = record.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
