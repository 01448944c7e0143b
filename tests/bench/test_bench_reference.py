"""The plain references agree with the program at small sizes on the CPU,
and their pieces do what they say."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.reference import linreg, precision, qwen
from benchsmall import small_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return small_root(tmp_path_factory.mktemp("bench_ref"))


@pytest.mark.parametrize("cell", ["fig2-sync", "fig2-modes", "qwen05b-train-sync"])
def test_program_matches_the_reference_at_a_small_size(root, cell):
    line = run.run_cell(cell, 2 ** 31 + 5, 0.0, False, jax.devices(), root=root)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


def test_fastest_breaks_ties_to_the_lower_index():
    arrive, tau = linreg.fastest(jnp.array([3.0, 1.0, 1.0, 0.5]), 2)
    np.testing.assert_array_equal(arrive, [0, 1, 0, 1])
    assert float(tau) == 1.0


def test_response_times_are_exponential():
    keys = jax.random.split(jax.random.PRNGKey(0), 2000)
    t = np.asarray(jax.vmap(lambda k: linreg.response_times(k, 50))(keys))
    assert (t > 0).all()
    assert abs(t.mean() - 1.0) < 0.01
    assert abs(np.median(t) - math.log(2)) < 0.01


@pytest.mark.parametrize("prec,tol", [("highest", 1e-6), ("high", 1e-4), ("fp8", 2e-1)])
def test_lowered_products_err_by_their_precision(prec, tol):
    a = jax.random.normal(jax.random.PRNGKey(1), (64, 128))
    b = jax.random.normal(jax.random.PRNGKey(2), (128, 32))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    got = np.asarray(precision.einsum("ij,jk->ik", a, b, prec), np.float64)
    err = np.abs(got - exact).max() / np.abs(exact).max()
    assert err < tol
    if prec != "highest":
        assert err > 1e-7


def test_lowered_backward_runs_at_the_same_precision():
    a = jax.random.normal(jax.random.PRNGKey(3), (16, 8)) * 1e-4
    b = jax.random.normal(jax.random.PRNGKey(4), (8, 4))

    def f(a, p):
        return jnp.sum(precision.einsum("ij,jk->ik", a, b, p) ** 2)

    g_hi = jax.grad(f)(a, "highest")
    g_lo = jax.grad(f)(a, "fp8")
    rel = float(jnp.linalg.norm(g_lo - g_hi) / jnp.linalg.norm(g_hi))
    assert 1e-3 < rel < 0.3  # scaled fp8, not flushed to zero


@pytest.mark.parametrize("tied", [False, True])
def test_qwen_reference_loss_is_log_vocab_with_a_zero_head(tied):
    """A zero output projection (with tied embeddings, a zero embedding)
    gives every real token the same logit; padded rows of the vocabulary
    are not in the loss."""
    cfg = {"rms_norm_eps": 1e-6, "rope_theta": 1e6, "head_dim": 8, "vocab_size": 40,
           "tie_word_embeddings": tied}
    L, d, h, f, V = 2, 16, 2, 24, 48
    k = jax.random.split(jax.random.PRNGKey(5), 8)
    n = lambda i, s: 0.1 * jax.random.normal(k[i], s)  # noqa: E731
    head = {} if tied else {"lm_head": jnp.zeros((d, V))}
    params = {
        "embed": jnp.zeros((V, d)) if tied else n(0, (V, d)), **head,
        "final_norm": {"scale": jnp.ones(d)},
        "layers": {"attn": {"wq": n(1, (L, d, h, 8)), "wk": n(2, (L, d, h, 8)),
                            "wv": n(3, (L, d, h, 8)), "wo": n(4, (L, h, 8, d))},
                   "mlp": {"w_gate": n(5, (L, d, f)), "w_in": n(6, (L, d, f)),
                           "w_out": n(7, (L, f, d))},
                   "ln1": {"scale": jnp.ones((L, d))}, "ln2": {"scale": jnp.ones((L, d))}},
    }
    toks = jnp.arange(12).reshape(2, 6) % 40
    ce = qwen.ce_per_row(params, toks, (toks + 1) % 40, cfg, "highest")
    np.testing.assert_allclose(ce, math.log(40), rtol=1e-6)


def test_sim_times_add_the_kth_fastest_time_of_each_step():
    keys = list(jax.random.split(jax.random.PRNGKey(6), 5))
    ks = [1, 1, 2, 3, 4]
    got = qwen.sim_times(keys, ks, 4)
    clock, want = np.float32(0), []
    for key, k in zip(keys, ks):
        t = np.sort(np.asarray(linreg.response_times(key, 4)))
        clock = np.float32(clock + t[k - 1])
        want.append(float(clock))
    assert got == want
    assert all(b > a for a, b in zip(got, got[1:]))
