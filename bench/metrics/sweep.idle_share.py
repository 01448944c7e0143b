"""Share of the traced window (%) in which no operation ran on the device,
averaged over the cell's chips: 1 - busy / window (bench/trace.py)."""


def read(ctx):
    t = ctx["trace"]
    return None if t is None else 100.0 * t.idle_share
