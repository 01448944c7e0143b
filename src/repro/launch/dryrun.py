import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For a given (--arch, --shape, --mesh) this lowers + compiles the real step
program (train_step with the full adaptive fastest-k machinery for train
shapes; prefill/decode for serving shapes) against the production mesh using
ShapeDtypeStruct inputs only — no allocation — then records
memory_analysis(), cost_analysis() and the HLO collective schedule for the
roofline report.

NOTE the XLA_FLAGS line above MUST precede every other import (jax locks the
device count on first init).  Do not import this module from test/bench code.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import INPUT_SHAPES, get_config, list_archs  # noqa: E402
from repro.core.aggregation import CommModel  # noqa: E402
from repro.core.controller import PflugController, SketchedPflugController  # noqa: E402
from repro.core.straggler import ShiftedExponential  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402
from repro.launch import sharding as shard_lib  # noqa: E402
from repro.launch import specs as specs_lib  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.shardctx import activation_sharding  # noqa: E402
from repro.roofline import analysis as roofline  # noqa: E402
from repro.roofline import memory as mem_model  # noqa: E402


def build_lowered(arch: str, shape_name: str, multi_pod: bool, *,
                  scan_layers: bool = True, overrides: Dict[str, Any] | None = None):
    """Lower the step program for one (arch, shape, mesh) combination."""
    overrides = dict(overrides or {})
    controller_kind = overrides.pop("controller", "pflug")
    n_micro = int(overrides.pop("n_micro", 1))
    moments_dtype = overrides.pop("moments_dtype", "float32")
    cfg = get_config(arch).replace(scan_layers=scan_layers, **overrides)
    shape = INPUT_SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    n_work = mesh_lib.n_workers(mesh)

    if shape.kind == "train":
        opt = adamw(lr=1e-4, weight_decay=0.01, moments_dtype=moments_dtype)
        ctrl_cls = SketchedPflugController if controller_kind == "sketched" else PflugController
        controller = ctrl_cls(n_workers=n_work, k0=max(n_work // 4, 1),
                              step=max(n_work // 8, 1), thresh=10, burnin=100)
        straggler = ShiftedExponential(shift=1.0, rate=1.0)
        train_step = steps_lib.make_train_step(
            model, opt, controller, straggler, n_work, CommModel(), n_micro=n_micro
        )
        state_sds = jax.eval_shape(
            lambda key: steps_lib.init_train_state(model, opt, controller, key),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        batch_sds = specs_lib.input_specs(cfg, shape)
        state_sh = shard_lib.param_shardings(state_sds, mesh)
        batch_sh = shard_lib.batch_shardings(batch_sds, mesh)
        key_sh = shard_lib.replicated(mesh)
        metrics_sh = jax.tree.map(lambda _: shard_lib.replicated(mesh),
                                  {"loss": 0, "ce": 0, "k": 0, "iter_time": 0,
                                   "sim_time": 0, "active_workers": 0})
        with mesh, activation_sharding(shard_lib.activation_resolver(mesh)):
            jitted = jax.jit(
                train_step,
                in_shardings=(state_sh, batch_sh, key_sh),
                out_shardings=(state_sh, metrics_sh),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(
                state_sds, batch_sds, jax.ShapeDtypeStruct((2,), jnp.uint32)
            )
        ctx = dict(cfg=cfg, shape=shape, mesh=mesh, params_sds=state_sds.params,
                   state_sds=state_sds, state_sh=state_sh,
                   params_sh=state_sh.params, n_micro=n_micro)
        return lowered, ctx

    # serving shapes
    params_sds = jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
    params_sh = shard_lib.param_shardings(params_sds, mesh)
    batch_sds = specs_lib.input_specs(cfg, shape)
    batch_sh = shard_lib.batch_shardings(batch_sds, mesh)

    if shape.kind == "prefill":
        step_fn = steps_lib.make_prefill_step(model, cfg, shape)
        with mesh, activation_sharding(shard_lib.activation_resolver(mesh)):
            jitted = jax.jit(step_fn, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(params_sds, batch_sds)
        ctx = dict(cfg=cfg, shape=shape, mesh=mesh, params_sds=params_sds,
                   state_sds=params_sds, state_sh=params_sh, params_sh=params_sh)
        return lowered, ctx

    # decode.  VLM patches are already in the KV cache at decode time; only
    # the enc-dec frames (the static encoder memory) are a decode input.
    step_fn = steps_lib.make_decode_step(model, cfg, shape)
    has_frames = "frames" in batch_sds

    if has_frames:
        def decode(params, token, cache, pos, frames):
            return step_fn(params, token, cache, pos, frames=frames)
        in_sh = (params_sh, batch_sh["token"], batch_sh["cache"],
                 shard_lib.replicated(mesh), batch_sh["frames"])
        args = (params_sds, batch_sds["token"], batch_sds["cache"],
                batch_sds["pos"], batch_sds["frames"])
    else:
        def decode(params, token, cache, pos):
            return step_fn(params, token, cache, pos)
        in_sh = (params_sh, batch_sh["token"], batch_sh["cache"],
                 shard_lib.replicated(mesh))
        args = (params_sds, batch_sds["token"], batch_sds["cache"], batch_sds["pos"])

    with mesh, activation_sharding(shard_lib.activation_resolver(mesh)):
        jitted = jax.jit(decode, in_shardings=in_sh, donate_argnums=(2,))
        lowered = jitted.lower(*args)
    ctx = dict(cfg=cfg, shape=shape, mesh=mesh, params_sds=params_sds,
               state_sds=params_sds, state_sh=params_sh, params_sh=params_sh,
               cache_sds=batch_sds["cache"], cache_sh=batch_sh["cache"])
    return lowered, ctx


def run_one(arch: str, shape_name: str, multi_pod: bool, *,
            scan_layers: bool = True, overrides=None,
            collect_roofline: bool = True) -> Dict[str, Any]:
    t0 = time.time()
    lowered, ctx = build_lowered(
        arch, shape_name, multi_pod, scan_layers=scan_layers, overrides=overrides
    )
    cfg, shape, mesh, params_sds = ctx["cfg"], ctx["shape"], ctx["mesh"], ctx["params_sds"]
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    result: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "scan_layers": scan_layers,
        "analytic_memory": mem_model.analytic_memory(
            cfg, shape, mesh, ctx["state_sds"], ctx["state_sh"],
            params_sds=ctx["params_sds"], params_shardings=ctx["params_sh"],
            cache_sds=ctx.get("cache_sds"), cache_shardings=ctx.get("cache_sh"),
            n_micro=ctx.get("n_micro", 1),
        ),
    }
    if collect_roofline:
        hlo = compiled.as_text()
        coll = roofline.collective_bytes_from_hlo(hlo)
        terms = roofline.roofline_terms(cost, coll["total"])
        tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else
                                       (shape.seq_len if shape.kind == "prefill" else 1))
        mf = roofline.model_flops(cfg, params_sds,
                                  tokens, "train" if shape.kind == "train" else "fwd")
        terms["model_flops_global"] = mf
        terms["useful_flops_ratio"] = mf / max(terms["hlo_flops"] * mesh.size, 1.0)
        result["collectives"] = coll
        result["roofline"] = terms
    return result


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--shape", required=True, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layers (accurate cost analysis; slower compile)")
    ap.add_argument("--out", default=None, help="write result JSON here")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides key=value (repeatable)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            v = json.loads(v)
        except json.JSONDecodeError:
            pass
        overrides[k] = v

    result = run_one(args.arch, args.shape, args.multi_pod,
                     scan_layers=not args.unroll, overrides=overrides)
    print(json.dumps(result, indent=2, default=str))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2, default=str)


if __name__ == "__main__":
    main()
