"""Share (%) of the chips' bfloat16 peak spent on the work the algorithm
needs over the traced dispatches (bench/flops.py: forward and backward of
the arrived rows, the evals), over window x chips x peak.  The simulation
runs float32 products and v5e publishes no float32 peak, so this is a lower
bound; it cannot pass 100%."""

from bench import flops


def read(ctx):
    layer = ctx["layer"]
    if not layer.get("k_records"):
        return None
    work = sum(flops.sweep_dispatch(k, layer["eval_every"], layer["m"], layer["d"],
                                    layer["n_workers"])
               for k in layer["k_records"])
    return 100.0 * work / (layer["window_s"] * layer["chips"] * ctx["peaks"]["bf16_flops"])
