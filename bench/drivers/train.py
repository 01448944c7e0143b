"""Entry kind ``train``: the fastest-k LM train step that
``repro.launch.steps.make_train_step`` builds, jitted with donation and
compiled ahead of the loop exactly as ``repro.launch.train.main`` does, and
driven by ``main``'s loop body: draw batch ``step`` of the program's
``repro.data.TokenStream`` (its ``batch_at``, jitted), split the step key,
run the step, wait for the state and the outputs, read ``ce``, ``k``,
``sim_time`` and ``iter_time`` on the host.  A step is timed from its batch
draw to its outputs being read.

The model is the program's architecture (``arch``) at the sizes the
configuration file states.  Set-up builds the one compiled step and its
state (weights from the seed, ``bench/lm_inputs.py``), checks that the
stream's first batch is the one the reference will be given, and drives the
step through its first three steps with the window's own call and feed; it
keeps host copies of the ce of each, of the first gradient as AdamW got it
(its first moment after one step over ``1 - b1``), of the weights and of
Pflug's sign counter after the three.  The window then runs the same object
on.  After the window, with the program's state freed, the plain reference
(``bench/reference/qwen.py``) runs the same three steps from the same
weights, batches and keys, and the two are compared; the simulated clock
of every step, the window's included, is compared with the straggler draws
of each step's key at the k that step used.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import common, lm_inputs
from bench.common import Check, CellRun, Outcome, span

CHECK_STEPS = 3


# Keys of the configuration file that the program's ModelConfig takes,
# beside the name it gives them.
MODEL_KEYS = (("hidden_size", "d_model"), ("intermediate_size", "d_ff"),
              ("num_hidden_layers", "n_layers"), ("num_attention_heads", "n_heads"),
              ("num_key_value_heads", "n_kv_heads"), ("head_dim", "head_dim"),
              ("vocab_size", "vocab_size"), ("qkv_bias", "qkv_bias"),
              ("rope_theta", "rope_theta"), ("tie_word_embeddings", "tie_embeddings"),
              ("vocab_pad_multiple", "vocab_pad_multiple"))


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of ``cfg["arch"]`` at the file's sizes
    and storage type."""
    from repro.configs import get_config

    dtype = cfg["precision"]["dtype"]
    return get_config(cfg["arch"]).replace(
        **{a: cfg[k] for k, a in MODEL_KEYS}, param_dtype=dtype, compute_dtype=dtype)


def build(cfg: dict, tr: dict):
    """(model, jitted step, state maker) for the configuration, through the
    program's own builders."""
    import jax
    import jax.numpy as jnp

    from repro.core.aggregation import CommModel
    from repro.core.controller import get_controller
    from repro.core.straggler import get_straggler_model
    from repro.launch import steps as steps_lib
    from repro.models import build_model
    from repro.optim import get_optimizer

    model = build_model(model_config(cfg))
    o = cfg["optimizer"]
    opt = get_optimizer(o["name"], o["lr"])
    ctl = cfg["controller"]
    n = cfg["fleet"]["n_workers"]
    controller = get_controller(ctl["name"], n, k0=ctl["k0"], step=ctl["step"],
                                thresh=ctl["thresh"], burnin=ctl["burnin"])
    straggler = get_straggler_model(cfg["fleet"]["straggler"])
    step = steps_lib.make_train_step(model, opt, controller, straggler, n,
                                     CommModel(alpha=0.0, beta=0.0), mode=tr["mode"])

    def make_state(params):
        return steps_lib.TrainState(
            params=params, opt_state=opt.init(params),
            ctrl_state=controller.init(params),
            sim_time=jnp.zeros((), jnp.float32), step=jnp.zeros((), jnp.int32))

    return (model, jax.jit(step, donate_argnums=(0,)),
            jax.jit(make_state, donate_argnums=(0,)))


def gap_of_norms(prog: dict, ref: dict, keep) -> float:
    """Widest gap ``|‖prog‖ - ‖ref‖|`` over the kept leaves, each against the
    larger of that leaf's reference norm and the median leaf's."""
    med = float(np.median([ref[p] for p in keep]))
    return max(abs(prog[p] - ref[p]) / max(ref[p], med) for p in keep)


def kept_leaves(ref_grad: dict, share: float) -> list:
    """Leaves whose reference gradient norm is at least ``share`` of the
    median leaf's; the rest move under Adam by round-off alone."""
    med = float(np.median(list(ref_grad.values())))
    return sorted(p for p, v in ref_grad.items() if v >= share * med)


def compare(prog: dict, ref: dict, share: float) -> dict:
    """The numbers compared: the widest relative gap of the three steps'
    ce; by the worst kept leaf, the gap of the first gradient's norms, the
    first gradient's error (``‖g_prog - g_ref‖``) and the gap of the norms of
    the weights' change over the three steps, each against the larger of
    that leaf's reference norm and the median leaf's; the widest relative
    gap of the simulated clock over every step; and, where the controller
    is Pflug's, how far its sign counter after the three steps lies from
    the reference's."""
    keep = kept_leaves(ref["grad"], share)
    med = float(np.median([ref["grad"][p] for p in keep]))
    out = {
        "ce_gap": common.rel_gap(prog["ce"], ref["ce"]),
        "grad_gap": gap_of_norms(prog["grad"], ref["grad"], keep),
        "grad_err": max(prog["grad_err"][p] / max(ref["grad"][p], med) for p in keep),
        "change_gap": gap_of_norms(prog["change"], ref["change"], keep),
        "time_gap": common.rel_gap(prog["times"], ref["times"]),
    }
    if ref["count"] is not None:
        out["sign_count_gap"] = float(abs(prog["count"] - ref["count"]))
    return out


def _change_norms(p, p0) -> dict:
    """``‖p - p0‖`` of every leaf, in float32."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(a, b):
        return jax.tree.map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b)

    return {k: float(v) for k, v in lm_inputs.flatten(norms(p, p0)).items()}


def _batches(cfg, tr, stream_seed):
    return [lm_inputs.token_batch(stream_seed, j, batch=tr["batch"], seq=tr["seq"],
                                  vocab=cfg["vocab_size"], correlation=tr["correlation"])
            for j in range(CHECK_STEPS)]


def _readings(cfg, tr, weight_key, stream_seed, step_keys, precision, prog: dict):
    """``prog`` (ce per check step, first gradient times ``grad_scale``,
    weights and Pflug's counter after the check steps, and the simulated
    clock and k of every step, as host values) against the reference
    computed at ``precision`` from the same weights, batches and keys;
    returns the compared numbers."""
    import jax
    import jax.numpy as jnp

    from bench.reference import qwen

    r = qwen.train(cfg, lm_inputs.make_weights(cfg, weight_key),
                   _batches(cfg, tr, stream_seed), step_keys[:CHECK_STEPS], precision)
    ces, g_ref, count = r.ce, r.grad, r.count
    p0 = lm_inputs.make_weights(cfg, weight_key)
    change_ref = _change_norms(r.params, p0)
    del r
    change_prog = _change_norms(jax.device_put(prog["params"]), p0)
    del p0
    flat_ref = lm_inputs.flatten(g_ref)
    flat_prog = lm_inputs.flatten(prog["grad"])
    scale = prog["grad_scale"]
    grad_ref, grad_prog, grad_err = {}, {}, {}
    for path, g in flat_ref.items():
        gp = jnp.asarray(flat_prog[path], jnp.float32) * scale
        grad_ref[path] = float(jnp.linalg.norm(g))
        grad_prog[path] = float(jnp.linalg.norm(gp))
        grad_err[path] = float(jnp.linalg.norm(gp - g))
        del gp
    times = qwen.sim_times(step_keys, prog["ks"], cfg["fleet"]["n_workers"])
    pflug = cfg["controller"]["name"] == "pflug"
    ref = {"ce": ces, "grad": grad_ref, "change": change_ref, "times": times,
           "count": count if pflug else None}
    mine = {"ce": prog["ce"], "grad": grad_prog, "change": change_prog,
            "grad_err": grad_err, "times": prog["times"], "count": prog["count"]}
    return compare(mine, ref, tr["check"]["grad_floor"])


def _keys(c: CellRun):
    """(weight key, stream seed, step key) of a run: the weights from one
    key, the program's token stream from a 31-bit seed (``TokenStream``
    takes an int), and the chain of step keys from a third."""
    import jax

    from bench.registry import seed32

    root = common.seed_key(c.seed)
    stream_seed = seed32(c.seed, "tokens") & 0x7FFFFFFF
    return jax.random.fold_in(root, 0), stream_seed, jax.random.fold_in(root, 2)


def run(c: CellRun) -> Outcome:
    import jax

    from repro.data import TokenStream
    from repro.launch import mesh as mesh_lib
    from repro.launch import sharding as shard_lib
    from repro.shardctx import activation_sharding

    cfg, tr = c.config, c.traffic
    B, T = tr["batch"], tr["seq"]
    b1 = cfg["optimizer"]["b1"]
    weight_key, stream_seed, key = _keys(c)

    model, jitted, make_state = build(cfg, tr)
    params = lm_inputs.make_weights(cfg, weight_key)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
        raise ValueError("bench/lm_inputs weight tree differs from the program's")
    state = make_state(params)
    del params
    data = TokenStream(vocab_size=cfg["vocab_size"], seq_len=T, global_batch=B,
                       seed=stream_seed, correlation=tr["correlation"])
    # Called as it stands, batch_at traces and compiles its scan anew on
    # every call; under jit it compiles once, here in set-up.
    batch_at = jax.jit(data.batch_at)
    mine = jax.device_get(batch_at(0))
    theirs = jax.device_get(lm_inputs.token_batch(
        stream_seed, 0, batch=B, seq=T, vocab=cfg["vocab_size"],
        correlation=tr["correlation"]))
    if not all(np.array_equal(a, b) for a, b in zip(mine, theirs)):
        raise ValueError("repro.data.TokenStream's batch differs from bench/lm_inputs'")
    common.phase(c, "weights, state and stream made")

    mesh = mesh_lib.make_host_mesh()
    steps_ms, ks, ces, times, step_keys = [], [], [], [], []
    with mesh, activation_sharding(shard_lib.activation_resolver(mesh)):
        tokens, targets = batch_at(0)
        batch = {"tokens": tokens, "targets": targets}
        t0 = time.perf_counter()
        step_fn = jitted.lower(state, batch, key).compile()
        compile_s = time.perf_counter() - t0
        common.phase(c, "step compiled")

        def one(step):
            nonlocal state, key
            t = time.perf_counter()
            with span("bench.draw"):
                tokens, targets = batch_at(step)
                batch = {"tokens": tokens, "targets": targets}
                key, sub = jax.random.split(key)
            with span("bench.dispatch"):
                state, metrics = step_fn(state, batch, sub)
            with span("bench.block"):
                jax.block_until_ready((state, metrics))
            with span("bench.read"):
                ces.append(float(metrics["ce"]))
                ks.append(int(metrics["k"]))
                times.append(float(metrics["sim_time"]))
                float(metrics["iter_time"])
            step_keys.append(sub)
            return (time.perf_counter() - t) * 1e3

        # Set-up: the first steps through the window's own call and feed.
        for j in range(CHECK_STEPS):
            ms = one(j)
            if j == 0:
                first_call_s = compile_s + ms * 1e-3
                mu_first = jax.device_get(state.opt_state.mu)
        params_after = jax.device_get(state.params)
        count = int(getattr(state.ctrl_state, "count_negative", 0))
        check_ce = list(ces)
        common.phase(c, f"first {CHECK_STEPS} steps done")

        n0 = len(ces)
        with common.settled():
            setup_s = time.time() - c.start_wall
            t_win = time.perf_counter()
            with common.traced(c):
                while True:
                    steps_ms.append(one(len(ces)))
                    window_s = time.perf_counter() - t_win
                    if (c.trace and len(steps_ms) >= tr["trace_steps"]) or (
                            not c.trace and window_s >= c.seconds):
                        break
    trace = None
    if c.trace:
        from bench import trace as trace_lib

        common.phase(c, "traced window done")
        trace = trace_lib.reduce_dir(c.trace_dir)
        common.phase(c, "trace reduced")
    mem = common.peak_bytes(c.devices)
    window_ks = ks[n0:]
    common.log(f"window: {len(steps_ms)} steps, k from {min(window_ks)} to {max(window_ks)}, "
               f"slowest step {max(steps_ms):.1f} ms")
    failed = sum(not np.isfinite(v) for v in ces)
    del state, step_fn
    gc.collect()

    # AdamW's first moment after one step is (1 - b1) times the gradient.
    prog = {"ce": check_ce, "grad": mu_first, "grad_scale": 1.0 / (1.0 - b1),
            "params": params_after, "count": count, "times": times, "ks": ks}
    readings = _readings(cfg, tr, weight_key, stream_seed, step_keys, "highest", prog)
    common.phase(c, "reference compared")
    limits = tr["check"]["limits"]
    checks = {k: Check(v, limits[k]) for k, v in readings.items() if k in limits}
    n_steps = len(steps_ms)
    return Outcome(
        metrics={"tokens_per_s": n_steps * B * T / window_s,
                 "step_ms_p90": float(np.percentile(steps_ms, 90)),
                 "peak_hbm_gb": mem / 1e9, "setup_s": setup_s},
        attempted=n_steps, failed=failed, checks=checks, memory_peak_bytes=mem,
        layer={"first_call_s": first_call_s, "window_s": window_s, "steps": n_steps,
               "k": window_ks, "batch": B, "seq": T, "n_workers": cfg["fleet"]["n_workers"],
               "config": cfg, "chips": len(c.devices)},
        trace=trace)


def control(c: CellRun, precision: str) -> dict:
    """The control's readings: the reference computed at ``precision`` (one
    step below the configuration's) put in the program's place, over the
    check steps of a run of this seed, from the same weights, batches and
    step keys."""
    import jax

    from bench.reference import qwen

    cfg, tr = c.config, c.traffic
    weight_key, stream_seed, key = _keys(c)
    step_keys = []
    for _ in range(CHECK_STEPS):
        key, sub = jax.random.split(key)
        step_keys.append(sub)
    r = qwen.train(cfg, lm_inputs.make_weights(cfg, weight_key),
                   _batches(cfg, tr, stream_seed), step_keys, precision)
    low = {"ce": r.ce, "grad": jax.device_get(r.grad), "grad_scale": 1.0,
           "params": jax.device_get(r.params), "count": r.count, "ks": r.k,
           "times": qwen.sim_times(step_keys, r.k, cfg["fleet"]["n_workers"])}
    del r
    return _readings(cfg, tr, weight_key, stream_seed, step_keys, "highest", low)
