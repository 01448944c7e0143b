"""Share (%) of the device busy time spent in collective operations
(all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all,
send/recv) over the traced dispatches, averaged over the chips.  0 when the
traced program ran device operations but no collective."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.busy_s <= 0:
        return None
    return 100.0 * t.collective_s / t.busy_s
