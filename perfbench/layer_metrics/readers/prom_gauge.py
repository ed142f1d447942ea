"""Reader of one gauge of the program's own ``METRICS`` by family name, as
the window's closing snapshot holds it (``Context.prom_after``: a family's
value summed over its label sets), times ``scale``. None where the program
has no such gauge, or where it reads 0 (a model without what it measures)."""


def read(ctx, *, family: str, scale: float = 1.0):
    value = ctx.prom_after.get(family)
    return value * scale if value else None
