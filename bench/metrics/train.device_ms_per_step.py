"""Device busy time per train step (ms): the union of the operation
intervals over the traced steps, averaged over the chips, per step."""


def read(ctx):
    t, layer = ctx["trace"], ctx["layer"]
    if t is None or not layer.get("steps"):
        return None
    return 1e3 * t.busy_s / layer["steps"]
