"""Inputs of the language-model cells, made from the seed: the weights,
which the program and the reference are both given (the reference never
reads what the program made), and the reference's copy of the token stream.

* Weights: every matrix N(0, initializer_range^2), every RMSNorm scale 1,
  every bias 0, each leaf from its own ``fold_in`` of the key, made on the
  device in one jitted call in the configuration's storage type (matrices in
  ``precision.dtype``; scales and biases float32).  The tree has the
  program's leaf names and layout: ``embed`` (padded vocab, d), ``lm_head``
  (d, padded vocab), ``final_norm.scale``, and per layer, stacked on a
  leading layer axis, ``attn.{wq,wk,wv,wo,bq,bk,bv}``, ``mlp.{w_gate,w_in,
  w_out}``, ``ln1.scale``, ``ln2.scale``.
* Tokens: a seeded Markov stream (with probability ``correlation`` the next
  token is the previous one plus 1, else a fresh uniform token); batch
  ``step`` is a pure function of (seed, step), so every step gets fresh rows.
  It is written from the description of ``repro.data.TokenStream`` and must
  give its batches bit for bit: the program's window draws the program's
  stream and the reference this one, and the train driver checks the two
  agree in set-up.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def padded_vocab(cfg: dict) -> int:
    m = cfg["vocab_pad_multiple"]
    return (cfg["vocab_size"] + m - 1) // m * m


def weight_shapes(cfg: dict) -> dict:
    """The weight tree as ``{path: (shape, dtype)}``."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    f, V = cfg["intermediate_size"], padded_vocab(cfg)
    mat = cfg["precision"]["dtype"]
    f32 = "float32"
    shapes = {
        "embed": ((V, d), mat),
        "final_norm/scale": ((d,), f32),
        "layers/attn/wq": ((L, d, h, hd), mat),
        "layers/attn/wk": ((L, d, kv, hd), mat),
        "layers/attn/wv": ((L, d, kv, hd), mat),
        "layers/attn/wo": ((L, h, hd, d), mat),
        "layers/ln1/scale": ((L, d), f32),
        "layers/ln2/scale": ((L, d), f32),
        "layers/mlp/w_gate": ((L, d, f), mat),
        "layers/mlp/w_in": ((L, d, f), mat),
        "layers/mlp/w_out": ((L, f, d), mat),
    }
    if cfg["qkv_bias"]:
        shapes.update({"layers/attn/bq": ((L, h, hd), f32),
                       "layers/attn/bk": ((L, kv, hd), f32),
                       "layers/attn/bv": ((L, kv, hd), f32)})
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = ((d, V), mat)
    return dict(sorted(shapes.items()))


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """``{path: leaf}`` of a nested dict, paths joined by ``/``."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, p + "/"))
        else:
            out[p] = v
    return out


def make_weights(cfg: dict, key):
    """The weight tree, made on the device in one jitted call."""
    shapes = weight_shapes(cfg)
    std = cfg["initializer_range"]

    @jax.jit
    def make(key):
        flat = {}
        for i, (path, (shape, dtype)) in enumerate(shapes.items()):
            name = path.rsplit("/", 1)[-1]
            if name == "scale":
                flat[path] = jnp.ones(shape, dtype)
            elif name in ("bq", "bk", "bv"):
                flat[path] = jnp.zeros(shape, dtype)
            else:
                z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
                flat[path] = (z * std).astype(dtype)
        return _nest(flat)

    return make(key)


@functools.partial(jax.jit, static_argnames=("batch", "seq", "vocab", "correlation"))
def token_batch(seed, step, *, batch: int, seq: int, vocab: int, correlation: float):
    """(tokens, targets), each (batch, seq) int32: batch ``step`` of the
    stream of ``seed`` (a whole number under 2**31)."""
    k1, k2 = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), step))
    base = jax.random.randint(k1, (batch, seq + 1), 0, vocab)
    follow = jax.random.bernoulli(k2, correlation, (batch, seq + 1))

    def walk(prev, inp):
        rnd, fol = inp
        tok = jnp.where(fol, (prev + 1) % vocab, rnd)
        return tok, tok

    _, toks = jax.lax.scan(walk, base[:, 0], (base.T, follow.T))
    toks = toks.T
    return toks[:, :-1], toks[:, 1:]
