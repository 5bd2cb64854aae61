"""Peak device memory after the window, in GB: arrays
(``memory_stats()["peak_bytes_in_use"]``) plus what the programs reserved for
their temporaries (``peak_bytes_reserved``), as ``device.memory_peak_bytes``."""


def read(ctx):
    peak = ctx["counters"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
