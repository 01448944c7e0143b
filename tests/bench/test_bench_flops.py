"""FLOP counts against hand counts, and the train step's against the
compiler's own count at a small size."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, lm_inputs, registry


def test_sweep_dispatch_hand_count():
    # 2 cells x 3 replicas x 2 eval blocks of 10 iterations; n=5 workers of
    # 4 rows, d=3: per arrived row 4d = 12 FLOP, each eval 20 rows x 2d = 6.
    k = np.array([[[1, 2]] * 3, [[5, 5]] * 3])
    want = (1 + 2) * 3 * 4 * 12 * 10 + (5 + 5) * 3 * 4 * 12 * 10 + 12 * 20 * 6
    assert flops.sweep_dispatch(k, 10, 20, 3, 5) == want


def test_lm_matmul_params_hand_count_qwen():
    cfg = registry.config("qwen1.5-0.5b")
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    assert flops.lm_matmul_params(cfg) == 24 * per_layer + 1024 * 151936
    # 6 N + 12 L H Q T at T = 512
    assert flops.lm_train_per_token(cfg, 512) == (
        6 * flops.lm_matmul_params(cfg) + 12 * 24 * 16 * 64 * 512)


def _small_cfg():
    cfg = dict(registry.config("qwen1.5-0.5b"))
    cfg.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, head_dim=64,
               vocab_size=512, vocab_pad_multiple=64,
               precision={**cfg["precision"], "dtype": "float32"})
    return cfg


def test_train_count_against_the_compiled_step_at_smoke_size(monkeypatch):
    """The compiler counts every row forward and backward, the post-update
    eval forward and the optimizer; the benchmark's count of forward and
    backward over all rows must lie under it, and the eval forward is a
    third of it.  The layers are unrolled here, because the compiler counts
    a loop's body once, and not recomputed in the backward pass (remat),
    which is work the count leaves out."""
    import repro.configs as configs
    from bench.drivers import train

    full = configs.get_config
    monkeypatch.setattr(configs, "get_config",
                        lambda arch: full(arch).replace(scan_layers=False, remat=False))
    cfg = _small_cfg()
    tr = {"mode": "sync"}
    model, jitted, make_state = train.build(cfg, tr)
    state = make_state(lm_inputs.make_weights(cfg, jax.random.PRNGKey(0)))
    B, T = 8, 32
    tok = jnp.zeros((B, T), jnp.int32)
    compiled = jitted.lower(state, {"tokens": tok, "targets": tok},
                            jax.random.PRNGKey(1)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    counted = B * T * flops.lm_train_per_token(cfg, T)
    # the eval forward of the step is a third of forward + backward
    assert counted < cost["flops"] < 1.6 * counted, (counted, cost["flops"])


@pytest.mark.parametrize("k", [1, 4])
def test_train_mfu_counts_only_arrived_rows(k):
    reader = registry.metric("train.mfu")
    cfg = registry.config("qwen1.5-0.5b")
    layer = {"config": cfg, "k": [k] * 10, "batch": 8, "n_workers": 4, "seq": 512,
             "window_s": 2.0, "chips": 1}
    got = reader.read({"layer": layer, "peaks": {"bf16_flops": 197e12}})
    want = 100 * 10 * k * 2 * 512 * flops.lm_train_per_token(cfg, 512) / (2.0 * 197e12)
    assert got == pytest.approx(want)
