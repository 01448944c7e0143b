"""Device busy time per serial scan iteration (us): the union of the
operation intervals over the traced dispatches, averaged over the chips,
divided by the iterations each chip ran in series (dispatches x iterations)."""


def read(ctx):
    t, layer = ctx["trace"], ctx["layer"]
    if t is None or not layer.get("serial_iters"):
        return None
    return 1e6 * t.busy_s / layer["serial_iters"]
