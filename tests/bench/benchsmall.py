"""Small copies of the benchmark's cells, for the CPU tests.

``small_root(tmp)`` copies ``bench/`` and ``BENCHMARK.json`` into ``tmp`` and
shrinks every configuration and traffic mix to a size the CPU runs in
seconds, with limits for that size.  The copies keep every name, so the
harness finds them exactly as it finds the real ones.
"""

from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Limits at these sizes, per traffic mix, from CPU readings over seeds 1-5:
# the program reads about 2e-7 in the sync mean cells and the model (float32
# on both sides), up to 5e-5 in the stale and robust cells of fig2-modes
# (the reference adds in another order); the controls read 2e-4 and more
# (tests/bench/test_bench_control.py).  Times and k are exact.
SWEEP = {"loss_gap": 1e-5, "time_gap": 0.0, "k_mismatch": 0.0}
SMALL_LIMITS = {
    "sweep": SWEEP,
    "fig2-modes": {"loss_gap": 2e-4},
    "train": {"ce_gap": 1e-5, "grad_gap": 1e-4, "grad_err": 1e-4, "change_gap": 1e-4,
              "time_gap": 0.0, "sign_count_gap": 0},
}


def _edit(path, fn):
    with open(path) as f:
        d = json.load(f)
    fn(d)
    with open(path, "w") as f:
        json.dump(d, f, indent=1)


def small_root(tmp) -> str:
    root = str(tmp)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    b = os.path.join(root, "bench")

    def peaks(d):
        d["kinds"]["cpu"] = {"bf16_flops": 1e12, "int8_ops": 2e12,
                             "hbm_bytes": 1e9, "hbm_bytes_per_s": 1e10}

    _edit(os.path.join(b, "peaks.json"), peaks)

    def linreg(d):
        d["problem"].update(m=200, d=10)
        d["fleet"]["n_workers"] = 10
        d["controllers"]["pflug"].update(k0=2, step=2, k_max=8, burnin=20)
        d.update(iterations=1000, eval_every=250)

    def qwen(d):
        d.update(hidden_size=256, intermediate_size=512,
                 num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                 head_dim=64, vocab_size=512, vocab_pad_multiple=64)
        d["precision"]["dtype"] = "float32"

    for name in os.listdir(os.path.join(b, "configs")):
        p = os.path.join(b, "configs", name)
        _edit(p, linreg if name.startswith("linreg") else qwen)

    def traffic(name, d):
        small = SMALL_LIMITS.get(name, SMALL_LIMITS[d["entry"]])
        lim = d["check"]["limits"]
        for k in lim:
            lim[k] = small[k]
        if d["entry"] == "sweep":
            d.update(replicas=4, trace_dispatches=1)
            for c in d["cases"]:
                if "k" in c:
                    c["k"] = max(1, c["k"] // 5)
        else:
            d.update(seq=32, trace_steps=2)

    for name in os.listdir(os.path.join(b, "traffic")):
        _edit(os.path.join(b, "traffic", name),
              lambda d, name=name: traffic(name[:-len(".json")], d))
    return root
