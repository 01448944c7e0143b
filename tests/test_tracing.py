"""What a profile of the program can name: the ``repro.*`` named scopes in
the compiled programs' ``op_name`` metadata (the lean sync grid, the moded
grid, the looped engine, the LM train step), the ``repro.sweep.*`` host
spans of a sweep dispatch, and the compile counter ``cache.compile_stats``.
"""

import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import montecarlo as mc
from repro.core import sweep as sw
from repro.core.controller import FixedKController, PflugController
from repro.core.faults import byzantine_plan
from repro.core.straggler import Exponential
from repro.core.sweep import SweepCase
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as shard_lib
from repro.launch import steps as steps_lib
from repro.models import build_model
from repro.optim import get_optimizer
from repro.shardctx import activation_sharding

N, M, D = 6, 48, 4
STEP = {"repro.sampler", "repro.ranks", "repro.grad", "repro.update",
        "repro.controller", "repro.eval"}
SPANS = ["repro.sweep.cells", "repro.sweep.layout", "repro.sweep.place",
         "repro.sweep.program", "repro.sweep.build", "repro.sweep.call",
         "repro.sweep.unpad"]
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scopes(hlo_text: str) -> set:
    """Every repro.* component of the compiled program's op_names."""
    return {s for on in re.findall(r'op_name="([^"]*)"', hlo_text)
            for s in re.findall(r"repro\.[a-z_]+", on)}


def _loss(w, X, y):
    return (X @ w - y) ** 2


def _data():
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    X = jax.random.normal(kx, (M, D))
    return X, X @ jnp.arange(1.0, D + 1) + 0.1 * jax.random.normal(ky, (M,))


def _pflug():
    return PflugController(n_workers=N, k0=2, step=1, thresh=2, burnin=2, k_max=5)


def _compiled_texts(monkeypatch, module, factory):
    """Capture the compiled HLO of every program ``module.<factory>`` builds
    from here on (a fresh program cache, so nothing is served from before)."""
    texts = []
    real = getattr(module, factory)

    def build(*args, **kwargs):
        program = real(*args, **kwargs)

        def run(*call_args):
            texts.append(program.lower(*call_args).compile().as_text())
            return program(*call_args)

        return run

    monkeypatch.setattr(module, "_PROGRAM_CACHE", type(module._PROGRAM_CACHE)(maxsize=4))
    monkeypatch.setattr(module, factory, build)
    return texts


def _sweep(cases, **kw):
    X, y = _data()
    return sw.run_sweep(_loss, jnp.zeros((D,)), X, y, n_workers=N, cases=cases,
                        num_iters=8, key=jax.random.PRNGKey(1), n_replicas=2,
                        eval_every=4, **kw)


def test_lean_sync_grid_names_every_part(monkeypatch):
    texts = _compiled_texts(monkeypatch, sw, "_build_grid_program")
    strag = Exponential(rate=1.0)
    _sweep([SweepCase(_pflug(), strag, eta=0.01, label="pflug"),
            SweepCase(FixedKController(n_workers=N, k=3), strag, eta=0.01, label="k3")])
    assert len(texts) == 1
    found = _scopes(texts[0])
    assert found >= STEP
    assert not found & {"repro.async_state", "repro.aggregate"}  # no such tail here


def test_moded_grid_names_async_state_and_aggregation(monkeypatch):
    texts = _compiled_texts(monkeypatch, sw, "_build_grid_program")
    strag = Exponential(rate=1.0)
    _sweep([SweepCase(_pflug(), strag, eta=0.01, label="sync"),
            SweepCase(_pflug(), strag, eta=0.01, label="kasync", mode="kasync"),
            SweepCase(_pflug(), strag, eta=0.01, label="kbatch", mode="kbatch"),
            SweepCase(FixedKController(n_workers=N, k=4), strag, eta=0.01, label="gm",
                      fault=byzantine_plan(N, 0.34, "sign_flip"), agg="geomedian")])
    assert len(texts) == 1
    assert _scopes(texts[0]) >= STEP | {"repro.async_state", "repro.aggregate"}


def test_looped_engine_names_every_part(monkeypatch):
    texts = _compiled_texts(monkeypatch, mc, "_build_program")
    X, y = _data()
    mc.run_monte_carlo(_loss, jnp.zeros((D,)), X, y, n_workers=N, controller=_pflug(),
                       straggler=Exponential(rate=1.0), eta=0.01, num_iters=8,
                       key=jax.random.PRNGKey(1), n_replicas=2, eval_every=4)
    assert len(texts) == 1
    assert _scopes(texts[0]) >= STEP


def test_train_step_names_every_part():
    cfg = get_smoke_config("qwen1.5-0.5b")
    model = build_model(cfg)
    opt = get_optimizer("adamw", 1e-3)
    controller = PflugController(n_workers=4, k0=1, step=1, thresh=2, burnin=2)
    step = steps_lib.make_train_step(model, opt, controller, Exponential(rate=1.0), 4)
    state = steps_lib.init_train_state(model, opt, controller, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": jnp.roll(toks, -1, 1)}
    mesh = mesh_lib.make_host_mesh()
    with mesh, activation_sharding(shard_lib.activation_resolver(mesh)):
        text = jax.jit(step).lower(state, batch, jax.random.PRNGKey(2)).compile().as_text()
    assert _scopes(text) >= STEP


def test_sweep_dispatch_writes_its_host_spans(tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(sw, "_PROGRAM_CACHE", type(sw._PROGRAM_CACHE)(maxsize=4))
    cases = [SweepCase(_pflug(), Exponential(rate=1.0), eta=0.01, label="pflug")]
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(_sweep(cases).loss)  # builds, compiles, runs
    paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path) for f in fs
             if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    spans = [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
             for plane in ProfileData.from_file(paths[0]).planes for line in plane.lines
             for e in line.events if e.name.startswith("repro.sweep.")]
    runs = [s for s in spans if s[2] == "repro.sweep.run"]
    assert len(runs) == 1
    lo, hi, _ = runs[0]
    children = sorted((s for s in spans if s[2] != "repro.sweep.run"), key=lambda s: s[0])
    assert [n for _, _, n in children] == SPANS
    assert all(lo <= s and e <= hi for s, e, _ in children)


_COUNTER_SCRIPT = """
import json
from repro.core import cache
cache.setup_compilation_cache()
import jax, jax.numpy as jnp
s0 = cache.compile_stats()
f = jax.jit(lambda x: x * 2.0 + 1.0)
jax.block_until_ready(f(jnp.ones(7)))
s1 = cache.compile_stats()
jax.block_until_ready(f(jnp.ones(7)))
s2 = cache.compile_stats()
print(json.dumps([s0, s1, s2]))
"""


def _run_script(script, tmp_path, *args):
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)  # keep the checkout's cache out of it
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT] + [p for p in [env.get("PYTHONPATH")] if p])
    proc = subprocess.run([sys.executable, "-c", script, *args], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_stats_counts_a_new_jit_and_not_a_repeat(tmp_path):
    s0, s1, s2 = _run_script(_COUNTER_SCRIPT, tmp_path)
    assert set(s0) == {"traces", "compiles", "cache_hits"}
    assert s1["traces"] > s0["traces"] and s1["compiles"] > s0["compiles"]
    assert s2 == s1


_MAIN_SCRIPT = """
import json
from repro.launch import train
out = train.main(["--arch", "qwen1.5-0.5b", "--smoke", "--steps", "3", "--batch", "4",
                  "--seq", "16", "--log-every", "100"])
print(json.dumps([[s["traces"], s["compiles"]] for s in out["steps"]]))
"""


def test_train_main_logs_each_steps_compiles(tmp_path):
    counts = _run_script(_MAIN_SCRIPT, tmp_path)
    assert len(counts) == 3
    # Step 0 compiles the step and the batch draw.  Every later step still
    # compiles once: main calls TokenStream.batch_at eagerly, and its scan
    # compiles anew on each call.
    assert counts[0][1] >= 2
    assert [c for _, c in counts[1:]] == [1, 1]
