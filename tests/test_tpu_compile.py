"""Compiles for a described TPU v5e: what the chip's compiler would refuse.

No chip is needed: ``jax.experimental.topologies`` describes a v5e slice and
the installed TPU compiler compiles for its first chip.  Nothing runs, so
these tests say nothing about results or times; they catch a Pallas block
that is not aligned to the TPU tiling, an op Mosaic cannot lower, and a
train step that does not fit one chip's memory.

* the flash-attention kernel at qwen1.5-0.5b and llama3.2-3b (GQA) widths,
* the wkv6 kernel at rwkv6-3b widths,
* the full-width, full-depth qwen1.5-0.5b sync train step at batch 8 x
  seq 512 — the step ``chip_smoke.py`` runs on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

# One TPU v5e chip's HBM (Google Cloud documentation, "TPU v5e": 16 GB).
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep these compiles out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "llama3.2-3b"])
def test_flash_attention_compiles_for_v5e(one_chip, arch):
    from repro.kernels.attention.ops import flash_attention

    cfg = get_config(arch)
    b, t, hd = 1, 2048, cfg.resolved_head_dim
    q = _sds(one_chip, (b, t, cfg.n_heads, hd), jnp.bfloat16)
    kv = _sds(one_chip, (b, t, cfg.n_kv_heads, hd), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    ).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_wkv6_compiles_for_v5e(one_chip):
    from repro.kernels.wkv.ops import wkv6

    cfg = get_config("rwkv6-3b")
    b, t, h, k = 1, 2048, cfg.n_heads, cfg.resolved_head_dim
    x = _sds(one_chip, (b, t, h, k), jnp.float32)
    u = _sds(one_chip, (h, k), jnp.float32)
    s0 = _sds(one_chip, (b, h, k, k), jnp.float32)
    compiled = jax.jit(
        lambda r, kk, v, w, u, s0: wkv6(r, kk, v, w, u, s0, chunk=cfg.wkv_chunk)
    ).lower(x, x, x, x, u, s0).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_qwen_train_step_fits_one_v5e(one_chip):
    from repro.core.aggregation import CommModel
    from repro.core.controller import PflugController
    from repro.core.straggler import Exponential
    from repro.launch import steps as steps_lib
    from repro.models import build_model
    from repro.optim import get_optimizer

    cfg = get_config("qwen1.5-0.5b")
    batch, seq, n_workers = 8, 512, 4
    model = build_model(cfg)
    opt = get_optimizer("adamw", 3e-4)
    controller = PflugController(n_workers=n_workers, k0=1, step=1, thresh=10,
                                 burnin=20)
    train_step = steps_lib.make_train_step(
        model, opt, controller, Exponential(rate=1.0), n_workers,
        CommModel(alpha=0.0, beta=0.0), mode="sync",
    )
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(
        lambda k: steps_lib.init_train_state(model, opt, controller, k), key
    )
    place = lambda s: _sds(one_chip, s.shape, s.dtype)  # noqa: E731
    state = jax.tree.map(place, state)
    tokens = _sds(one_chip, (batch, seq), jnp.int32)
    compiled = jax.jit(train_step, donate_argnums=(0,)).lower(
        state, {"tokens": tokens, "targets": tokens}, place(key)
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (
        f"train step needs {used / 1e9:.2f} GB of the chip's "
        f"{V5E_HBM_BYTES / 1e9:.0f} GB"
    )
