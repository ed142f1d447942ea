"""Reader of the device's peak memory after the window
(``memory_stats()["peak_bytes_in_use"]`` on the fullest chip), in GB."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
