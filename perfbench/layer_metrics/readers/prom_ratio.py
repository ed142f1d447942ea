"""Reader of the ratio of two of the program's own counters over the window:
Δ ``numerator`` / Δ ``denominator`` (``Context.delta``: a family's value summed
over its label sets, closing snapshot less opening). None where the
denominator did not move: a program without the counters, or a window in
which nothing they count happened."""


def read(ctx, *, numerator: str, denominator: str, scale: float = 1.0):
    steps = ctx.delta(denominator)
    return scale * ctx.delta(numerator) / steps if steps > 0 else None
