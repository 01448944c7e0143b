"""Model FLOP/s utilization (%) of the traced steps: forward and backward of
the rows of the workers that arrived (k from each step's own output, times
rows per worker, times sequence length, times bench/flops.py's FLOP per
token), over window x chips x the bfloat16 peak.  The post-update eval
forward the step also runs, and the rows of workers that did not arrive,
are not counted."""

from bench import flops


def read(ctx):
    layer = ctx["layer"]
    if not layer.get("k"):
        return None
    cfg = layer["config"]
    rows = layer["batch"] // layer["n_workers"]
    tokens = sum(layer["k"]) * rows * layer["seq"]
    work = tokens * flops.lm_train_per_token(cfg, layer["seq"])
    return 100.0 * work / (layer["window_s"] * layer["chips"] * ctx["peaks"]["bf16_flops"])
