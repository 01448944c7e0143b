"""The work the algorithm needs, counted from shapes.

Only work the algorithm needs counts: rows of workers that did not arrive,
recomputed activations and padding are left out, so a change that stops
doing wasted work raises utilization, and none can make it count work that
is not done.
"""

from __future__ import annotations


def sweep_dispatch(k_record, eval_every: int, m: int, d: int, n_workers: int) -> float:
    """FLOP of one sweep dispatch of the linear regression, from its
    (cells, replicas, evals) k record: each iteration's forward (x.w - y) and
    backward (g += 2 r x) over the k arrived workers' rows, 4 d FLOP per row,
    with each eval point's k held over its block; and each eval's forward
    over all m rows, 2 d FLOP per row."""
    import numpy as np

    k = np.asarray(k_record, np.float64)
    rows = m // n_workers
    per_iter = k.sum() * rows * 4.0 * d * eval_every
    evals = k.size * m * 2.0 * d
    return float(per_iter + evals)


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that multiply activations, per token: the attention
    projections and the MLP of every layer, and the output projection over
    the real vocabulary (the embedding lookup is no product)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f = cfg["intermediate_size"]
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return L * per_layer + d * cfg["vocab_size"]


def lm_train_per_token(cfg: dict, seq: int) -> float:
    """Forward and backward FLOP per token (PaLM, Chowdhery et al. 2022,
    appendix B): 6 N for the products with N matmul parameters, plus
    12 L H Q T for attention's scores and weighted values."""
    L, h, hd = cfg["num_hidden_layers"], cfg["num_attention_heads"], cfg["head_dim"]
    return 6.0 * lm_matmul_params(cfg) + 12.0 * L * h * hd * seq
