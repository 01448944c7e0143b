"""Plain reference of the fastest-k language-model train step, written from
the published descriptions and independent of the program.

Model (Qwen1.5, hf:Qwen/Qwen1.5-0.5B): token embedding; per layer
``x += attn(rmsnorm(x))`` with biased q/k/v projections, rotary position
embedding (rotate-half, base ``rope_theta``), causal softmax attention and
an output projection, then ``x += W_out(silu(W_gate h) * W_in h)`` on
``h = rmsnorm(x)``; a final RMSNorm and the output projection; the loss of a
row is its mean next-token cross-entropy over the real (unpadded) vocabulary.

Step (arXiv:2002.11005 eq. (2), Algorithm 1): the n workers' response
times come from the step's key (see ``bench.reference.linreg``); the k
fastest arrive and the simulated clock advances by the k-th time; the gradient is ``(1/k) sum_{arrived i} (1/s) sum_{rows of
i} grad ce_row``; AdamW (decoupled decay) updates the weights, which are then
stored in their storage type; Pflug's test updates k; the step reports the
mean cross-entropy of the new weights over the whole batch.

Everything is computed in float32 with the products at ``precision``
(``bench.reference.precision``): ``highest`` for the reference, a lower one
for the control.  Only the arrived rows are differentiated (the others
have weight 0), and the reported loss is computed two rows at a time, so
the reference fits beside nothing else on one chip.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.reference import linreg
from bench.reference.precision import einsum


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    t, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def ce_per_row(params, tokens, targets, cfg: dict, precision: str):
    """Mean next-token cross-entropy of each row, (rows,)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    hd = cfg["head_dim"]
    x = f32(params["embed"])[tokens]
    t = tokens.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        a, m = p["attn"], p["mlp"]
        h = _rmsnorm(x, p["ln1"]["scale"], eps)
        q = einsum("btd,dhk->bthk", h, a["wq"], precision)
        k = einsum("btd,dhk->bthk", h, a["wk"], precision)
        v = einsum("btd,dhk->bthk", h, a["wv"], precision)
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k = _rope(q, theta), _rope(k, theta)
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = einsum("bthk,bshk->bhts", q, k, precision) / jnp.sqrt(jnp.float32(hd))
        s = jnp.where(causal, s, -jnp.inf)
        o = einsum("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v, precision)
        x = x + einsum("bthk,hkd->btd", o, a["wo"], precision)
        h = _rmsnorm(x, p["ln2"]["scale"], eps)
        g = einsum("btd,df->btf", h, m["w_gate"], precision)
        u = einsum("btd,df->btf", h, m["w_in"], precision)
        return x + einsum("btf,fd->btd", jax.nn.silu(g) * u, m["w_out"], precision), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = _rmsnorm(x, params["final_norm"]["scale"], eps)
    head = params["embed"].T if cfg["tie_word_embeddings"] else params["lm_head"]
    logits = einsum("btd,dv->btv", x, head, precision)[..., :cfg["vocab_size"]]
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(nll, axis=-1)


def _grad_and_ce(cfg, precision, rows_per_block):
    """Jitted (gradient of the eq.-(2) loss over the arrived rows,
    per-row ce of the whole batch) pieces."""

    @jax.jit
    def grad(params, tokens, targets, weights):
        def loss(p32):
            return jnp.sum(weights * ce_per_row(p32, tokens, targets, cfg, precision))

        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return jax.grad(loss)(p32)

    @jax.jit
    def ce(params, tokens, targets):
        b = tokens.shape[0]
        tb = tokens.reshape(b // rows_per_block, rows_per_block, -1)
        gb = targets.reshape(b // rows_per_block, rows_per_block, -1)
        per = jax.lax.map(lambda tg: ce_per_row(params, tg[0], tg[1], cfg, precision),
                          (tb, gb))
        return jnp.mean(per)

    return grad, ce


def _adamw(cfg):
    o = cfg["optimizer"]
    lr, b1, b2, eps, wd = o["lr"], o["b1"], o["b2"], o["eps"], o["weight_decay"]

    def update(params, g, mu, nu, step):
        mu = jax.tree.map(lambda m, gi: b1 * m + (1 - b1) * gi, mu, g)
        nu = jax.tree.map(lambda v, gi: b2 * v + (1 - b2) * gi * gi, nu, g)
        bc1 = 1 - b1 ** step
        bc2 = 1 - b2 ** step

        def new(p, m, v):
            p32 = p.astype(jnp.float32)
            u = -lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps) - lr * wd * p32
            return (p32 + u).astype(p.dtype)

        return jax.tree.map(new, params, mu, nu), mu, nu

    return jax.jit(update, donate_argnums=(0, 2, 3))


@jax.jit
def _tree_dot(a, b):
    return sum(jnp.vdot(x, y) for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


class Steps(NamedTuple):
    """What ``train`` returns."""

    ce: list  # mean ce of the updated weights, per step
    grad: dict  # the first step's gradient
    params: dict  # the weights after the last step
    count: int  # Pflug's sign counter after the last step
    k: list  # the k each step used


def train(cfg: dict, params, batches, step_keys, precision: str = "highest",
          rows_per_block: int = 2) -> Steps:
    """Run the first ``len(batches)`` steps from ``params`` (donated).

    ``batches`` are (tokens, targets) pairs and ``step_keys`` the keys the
    steps were given."""
    n = cfg["fleet"]["n_workers"]
    ctl = cfg["controller"]
    grad, ce = _grad_and_ce(cfg, precision, rows_per_block)
    update = _adamw(cfg)
    zeros = lambda p: jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)  # noqa: E731
    mu, nu = zeros(params), zeros(params)
    k, neg, cnt, prev = ctl["k0"], 0, 1, None
    ces, ks, g_first = [], [], None
    for j, ((tokens, targets), key) in enumerate(zip(batches, step_keys)):
        s = tokens.shape[0] // n
        arrive, _ = linreg.fastest(linreg.response_times(key, n), k)
        idx = jnp.nonzero(jnp.repeat(arrive, s) > 0, size=k * s)[0]
        weights = jnp.full((k * s,), 1.0 / (k * s), jnp.float32)
        g = grad(params, tokens[idx], targets[idx], weights)
        if g_first is None:
            g_first = g
        ks.append(k)
        # Pflug's test on the applied gradient (Algorithm 1)
        if prev is not None:
            neg += 1 if float(_tree_dot(g, prev)) < 0 else -1
        params, mu, nu = update(params, g, mu, nu, jnp.float32(j + 1))
        prev = g
        ces.append(float(ce(params, tokens, targets)))
        if neg > ctl["thresh"] and cnt > ctl["burnin"] and k + ctl["step"] <= n:
            k, neg, cnt = k + ctl["step"], 0, 0
        cnt += 1
    return Steps(ces, g_first, params, neg, ks)


def sim_times(step_keys, ks, n: int) -> list:
    """The simulated clock after each step: the running float32 sum of each
    step's k-th fastest response time, drawn from its key, at the k that
    step used."""
    import numpy as np

    t = np.asarray(jax.vmap(lambda key: linreg.response_times(key, n))(
        jnp.stack(step_keys)))
    tau = np.sort(t, axis=1)[np.arange(len(ks)), np.asarray(ks) - 1]
    out, clock = [], np.float32(0.0)
    for v in tau:
        clock = np.float32(clock + v)
        out.append(float(clock))
    return out
