"""Plain reference of fastest-k SGD on the paper's linear regression
(arXiv:2002.11005, Algorithm 1 and §V-B), with the K-async and K-batch-async
modes of Dutta et al. (arXiv:1803.01113) and the geometric-median
aggregator, written from those descriptions and independent of the program.

One lane is one replica of one grid cell.  Worker i owns rows
[i s, (i + 1) s).  A response-time draw from a key splits it, and the
first half draws n uniforms u; the times are ``-log1p(-u)`` (Exp(1) by
inverse CDF).  Each master update (an *event*) first splits the lane's key
into (next key, event key).

* ``sync``: every worker draws a fresh time from the event key; the k
  fastest (ties to the lower index) arrive; the event lasts the k-th
  smallest time; the update direction is the gradient of
  ``(1/(k s)) sum_{arrived rows} (x w - y)^2``.
* ``kasync``: workers that are not in flight draw a fresh time from the
  event key, the others keep their remaining time; the K smallest
  remaining times arrive and the event lasts the K-th; each arrival's
  gradient is taken at the weights it was dispatched with (its
  snapshot); arrivals are redispatched with the new weights; every other
  clock runs down by the event's length.
* ``kbatch``: the event key is split into (inner key, draw key); workers not
  in flight draw from the draw key; then, K times, the worker with the
  smallest remaining time completes (ties to the lower index), its shard's
  mean gradient at its snapshot is added, every clock runs down by that
  time, and it is redispatched at once with the weights before this update
  and a fresh time (the completer's entry of a full draw from the next split
  of the inner key).  The update direction is the sum over K.
* ``geomedian`` (sync): the arrived workers' shard-mean gradients, each
  multiplied by its fault weight, are aggregated by 8 Weiszfeld iterations
  from their mean, with distances clamped below at 1e-12.
* A fault weight of -1 flips the sign of that worker's contribution.

The step is ``w - eta * direction``.  Pflug's test (Algorithm 1) runs on
the direction: the counter moves +1 when it points against the previous one
and -1 otherwise; past the burn-in, once it exceeds the threshold, k grows
by ``step`` (up to ``k_max``) and both counters restart.  Every
``eval_every`` events the lane records the simulated time, the mean loss
over all m rows and the k of the last event.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from bench.reference.precision import einsum, matmul

FIXED, PFLUG = 0, 1
MODES = ("sync", "kasync", "kbatch")
WEISZFELD_ITERS = 8


class Lane(NamedTuple):
    """Per-lane settings (arrays with a leading lane axis)."""

    key: jax.Array
    ctrl: jax.Array  # int32: FIXED or PFLUG
    k0: jax.Array
    step: jax.Array
    thresh: jax.Array
    burnin: jax.Array
    k_max: jax.Array
    eta: jax.Array  # float32
    fault: jax.Array  # (n,) float32 per-worker weight: 1 healthy, -1 sign flip


def draw(key, n):
    ku, _ = jax.random.split(key)
    u = jax.random.uniform(ku, (n,), dtype=jnp.float32)
    return -jnp.log1p(-u)


def response_times(key, n):
    """The fresh draw of a sync event whose lane key is ``key``."""
    return draw(jax.random.split(key)[1], n)


def fastest(times, k):
    """(0/1 arrival mask of the k fastest, k-th smallest time)."""
    order = jnp.argsort(times, stable=True)
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(times.shape[0]))
    return (ranks < k).astype(jnp.float32), jnp.sort(times)[k - 1]


def simulate(X, y, lanes: Lane, n: int, iters: int, eval_every: int,
             precision: str = "highest", mode: str = "sync", agg: str = "mean"):
    """(time, loss, k), each (lanes, iters // eval_every)."""
    m, d = X.shape
    s = m // n
    Xw, yw = X.reshape(n, s, d), y.reshape(n, s)
    if iters % eval_every:
        raise ValueError("iters must be a multiple of eval_every")
    if mode not in MODES or agg not in ("mean", "geomedian"):
        raise ValueError(f"unknown mode {mode!r} or aggregator {agg!r}")

    def residuals(W):
        """(n, s): each worker's residuals at its own weights W[i]."""
        return einsum("isd,id->is", Xw, W, precision) - yw

    def shard_grads(W):
        """(n, d): worker i's shard-mean gradient at W[i]."""
        return einsum("isd,is->id", Xw, (2.0 / s) * residuals(W), precision)

    def shard_grad(i, wv):
        """Worker i's shard-mean gradient at wv."""
        r = matmul(Xw[i], wv, precision) - yw[i]
        return matmul(Xw[i].T, (2.0 / s) * r, precision)

    def geomedian(rows, mask, k):
        yv = matmul(mask, rows, precision) / k
        for _ in range(WEISZFELD_ITERS):
            dist = jnp.sqrt(jnp.sum((rows - yv) ** 2, axis=1))
            wt = mask / jnp.maximum(dist, 1e-12)
            yv = matmul(wt, rows, precision) / jnp.sum(wt)
        return yv

    def lane(ln: Lane):
        def event(c, _):
            w, W, rem, pending, k, neg, cnt, prev, have, t, key = c
            key_next, sub = jax.random.split(key)
            kf = k.astype(jnp.float32)
            if mode == "kbatch":
                inner_key, sub0 = jax.random.split(sub)
                rem = jnp.where(pending, rem, draw(sub0, n))

                def complete(ic, e):
                    rem, W, gsum, tau, ikey = ic
                    on = e < k
                    i = jnp.argmin(rem)
                    te = rem[i]
                    g_i = shard_grad(i, W[i]) * ln.fault[i]
                    gsum = gsum + jnp.where(on, g_i, 0.0)
                    ikey, isub = jax.random.split(ikey)
                    fresh = draw(isub, n)[i]
                    rem_on = (rem - te).at[i].set(fresh)
                    rem = jnp.where(on, rem_on, rem)
                    W = jnp.where(on, W.at[i].set(w), W)
                    return (rem, W, gsum, tau + jnp.where(on, te, 0.0), ikey), None

                (rem, W, gsum, tau, _), _ = jax.lax.scan(
                    complete, (rem, W, jnp.zeros((d,), jnp.float32), jnp.float32(0.0),
                               inner_key), jnp.arange(n))
                direction = gsum / kf
                pending = jnp.ones((n,), bool)
            else:
                fresh = draw(sub, n)
                if mode == "kasync":
                    rem = jnp.where(pending, rem, fresh)
                else:
                    rem = fresh
                arrive, tau = fastest(rem, k)
                if mode == "sync" and agg == "mean":
                    v = jnp.repeat(arrive * ln.fault, s) / (kf * s)
                    r = matmul(X, w, precision) - y
                    direction = 2.0 * matmul(X.T, v * r, precision)
                elif agg == "mean":  # kasync: each arrival at its snapshot
                    c = (arrive * ln.fault / (kf * s))[:, None]
                    rows = einsum("isd,is->id", Xw, 2.0 * (c * residuals(W)), precision)
                    direction = jnp.sum(rows, axis=0)
                else:
                    at = W if mode == "kasync" else jnp.broadcast_to(w, (n, d))
                    rows = shard_grads(at) * ln.fault[:, None]
                    direction = geomedian(rows, arrive, kf)
                if mode == "kasync":
                    rem = jnp.maximum(rem - tau, 0.0)
                    pending = arrive == 0
            w_new = w - ln.eta * direction
            if mode == "kasync":
                W = jnp.where((arrive > 0)[:, None], w_new, W)
            t = t + tau
            dot = matmul(direction, prev, precision)
            neg = neg + jnp.where(have, jnp.where(dot < 0, 1, -1), 0)
            switch = ((ln.ctrl == PFLUG) & (neg > ln.thresh) & (cnt > ln.burnin)
                      & (k + ln.step <= ln.k_max))
            k_used = k
            k = jnp.where(switch, k + ln.step, k)
            neg = jnp.where(switch, 0, neg)
            cnt = jnp.where(switch, 0, cnt) + 1
            return (w_new, W, rem, pending, k, neg, cnt, direction, True, t,
                    key_next), k_used

        def block(c, _):
            c, ks = jax.lax.scan(event, c, None, length=eval_every)
            r = matmul(X, c[0], precision) - y
            return c, (c[9], jnp.mean(r * r), ks[-1])

        w0 = jnp.zeros((d,), jnp.float32)
        c0 = (w0, jnp.zeros((n, d), jnp.float32), jnp.zeros((n,), jnp.float32),
              jnp.zeros((n,), bool), ln.k0, jnp.int32(0), jnp.int32(1), w0, False,
              jnp.float32(0.0), ln.key)
        _, rec = jax.lax.scan(block, c0, None, length=iters // eval_every)
        return rec

    return jax.jit(jax.vmap(lane))(lanes)
