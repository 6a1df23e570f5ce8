import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
production meshes and extract the roofline inputs.

The two lines ABOVE this docstring must run before ANY other import — jax
locks the device count at first init (assignment requirement).

Per cell:
  * train_4k / prefill_32k lower ``train_step`` / ``prefill_step``;
    decode_32k / long_500k lower ``serve_step`` with a full-length cache;
  * params/optimizer/batch/cache are ``ShapeDtypeStruct``s with
    NamedShardings from ``repro.distributed.sharding_rules`` — nothing is
    allocated;
  * ``compiled.memory_analysis()`` proves the per-device footprint,
    ``compiled.cost_analysis()`` + the trip-count-aware HLO parse give the
    roofline terms;
  * results land in ``experiments/dryrun/<mesh>/<arch>__<shape>.json``.

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--skip-existing]
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, cell_is_applicable, get_config, get_shape, list_archs
from ..configs.base import ModelConfig, ShapeConfig
from ..distributed.context import DistContext, distribution
from ..distributed.sharding_rules import (
    batch_specs,
    cache_specs,
    opt_specs,
    param_specs,
)
from ..models import build_model
from ..optim import init_adamw
from ..serving.engine import make_serve_step, split_cache
from ..training import make_train_step
from .hlo_analysis import analyze_hlo
from .mesh import make_production_mesh, mesh_batch_axes

#: per-(arch, shape) gradient-accumulation / prefill-chunking overrides —
#: the knob the memory term is iterated with (EXPERIMENTS.md §Perf).
#: train cells: gradient-accumulation microbatches; prefill cells: the
#: batch is processed in this many sequential lax.map chunks.
MICROBATCHES: Dict[Tuple[str, str], int] = {
    # train: gradient-accumulation; prefill: sequential batch chunks.
    # Values from the §Perf memory-term iteration (EXPERIMENTS.md).
    ("gemma3-12b", "train_4k"): 2,
    ("granite-34b", "train_4k"): 4,
    ("whisper-large-v3", "train_4k"): 2,
    ("mamba2-370m", "train_4k"): 4,
    ("granite-moe-3b-a800m", "train_4k"): 2,
    ("llava-next-mistral-7b", "train_4k"): 2,
    ("granite-34b", "prefill_32k"): 2,
    ("gemma3-12b", "prefill_32k"): 2,
    ("llava-next-mistral-7b", "prefill_32k"): 2,
    ("qwen3-moe-30b-a3b", "prefill_32k"): 2,
}

OUT_ROOT = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def _struct(shape, dtype, sharding) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _ns_tree(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _with_shardings(shape_tree, ns_tree):
    return jax.tree.map(
        lambda sd, ns: _struct(sd.shape, sd.dtype, ns), shape_tree, ns_tree
    )


def input_specs(
    arch: str, shape_name: str, mesh, ctx: DistContext
) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input of a cell —
    weak-type-correct, sharded, no device allocation."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    api = build_model(cfg, ep=ctx.model_size)
    spec_dict = api.batch_spec(shape)
    b_specs = batch_specs(spec_dict, ctx)
    return {
        name: _struct(shp, dtype, NamedSharding(mesh, b_specs[name]))
        for name, (shp, dtype) in spec_dict.items()
    }


def _model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS: 6·N·D (train), 2·N·D (prefill), 2·N_active·B (decode)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    return 2.0 * n_active * shape.global_batch  # one token per sequence


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    microbatches: Optional[int] = None,
    save: bool = True,
    hlo_analysis: bool = True,
    impl: str = "ref",
    variant: str = "",
    kv_dtype: str = "bfloat16",
) -> Dict[str, Any]:
    import dataclasses

    cfg = get_config(arch)
    if kv_dtype != "bfloat16":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    shape = get_shape(shape_name)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    if variant:
        mesh_name = f"{mesh_name}__{variant}"
    if not cell_is_applicable(cfg, shape):
        result = {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_name,
            "status": "SKIP",
            "reason": "long_500k requires sub-quadratic attention "
            "(pure full-attention arch; see DESIGN.md §5)",
        }
        if save:
            _save(result, mesh_name, arch, shape_name)
        return result

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    ctx = DistContext(mesh=mesh, batch_axes=mesh_batch_axes(mesh))
    api = build_model(cfg, ep=ctx.model_size, impl=impl)
    mb = microbatches or MICROBATCHES.get((arch, shape_name), 1)
    rng = jax.random.PRNGKey(0)

    with distribution(ctx):
        params_shapes = jax.eval_shape(api.init, rng)
        p_spec = param_specs(params_shapes, cfg, ctx)
        p_ns = _ns_tree(mesh, p_spec)
        params_in = _with_shardings(params_shapes, p_ns)
        batch_in = input_specs(arch, shape_name, mesh, ctx)

        if shape.kind == "train":
            opt_shapes = jax.eval_shape(init_adamw, params_shapes)
            o_spec = opt_specs(opt_shapes, p_spec, cfg, ctx)
            o_ns = _ns_tree(mesh, o_spec)
            opt_in = _with_shardings(opt_shapes, o_ns)
            step_fn = make_train_step(api, microbatches=mb)
            out_shapes = jax.eval_shape(step_fn, params_in, opt_in, batch_in)
            metrics_ns = jax.tree.map(
                lambda _: NamedSharding(mesh, P()), out_shapes[2]
            )
            jitted = jax.jit(
                step_fn,
                out_shardings=(p_ns, o_ns, metrics_ns),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(params_in, opt_in, batch_in)
        elif shape.kind == "prefill":
            def one_chunk(params, chunk):
                if cfg.family == "encdec":
                    logits, _ = api.forward(
                        params, chunk["frames"], chunk["tokens"],
                        remat=False, last_only=True,
                    )
                elif cfg.family == "vlm":
                    logits, _ = api.forward(
                        params, chunk["tokens"],
                        prefix_embeds=chunk["prefix_embeds"],
                        remat=False, last_only=True,
                    )
                else:
                    logits, _ = api.forward(
                        params, chunk["tokens"], remat=False, last_only=True
                    )
                return logits

            # chunking must preserve DP divisibility: a chunk whose batch
            # no longer divides the DP axes would replicate activations
            # across them (measured: 153× FLOPs blowup on multipod MoE)
            bt = ctx.batch_size_total
            b_total = shape.global_batch
            mb_eff = mb
            while mb_eff > 1 and (b_total // mb_eff) % bt != 0:
                mb_eff //= 2

            def prefill_step(params, batch):
                if mb_eff == 1:
                    return one_chunk(params, batch)
                # memory-term lever: process the request batch in ``mb``
                # sequential chunks (live activations shrink by mb)
                chunked = jax.tree.map(
                    lambda x: x.reshape(mb_eff, x.shape[0] // mb_eff, *x.shape[1:]),
                    batch,
                )
                out = jax.lax.map(lambda c: one_chunk(params, c), chunked)
                return out.reshape(-1, *out.shape[2:])

            jitted = jax.jit(prefill_step, donate_argnums=())
            lowered = jitted.lower(params_in, batch_in)
        else:  # decode
            cache_shapes = jax.eval_shape(
                lambda: api.init_cache(shape.global_batch, shape.seq_len)
            )
            c_spec = cache_specs(cache_shapes, cfg, ctx)
            c_ns = _ns_tree(mesh, c_spec)
            kv_in, state_in = split_cache(_with_shardings(cache_shapes, c_ns))
            kv_ns, state_ns = split_cache(c_ns)
            serve_step = make_serve_step(api)
            tok_ns = batch_in["tokens"].sharding
            pos_in = _struct((), jnp.int32, NamedSharding(mesh, P()))
            jitted = jax.jit(
                serve_step,
                out_shardings=(tok_ns, kv_ns, state_ns),
                donate_argnums=(1, 2),
            )
            lowered = jitted.lower(
                params_in, kv_in, state_in, batch_in["tokens"], pos_in
            )

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    analysis = (
        analyze_hlo(hlo, dict(mesh.shape)) if hlo_analysis else {}
    )
    n_dev = mesh.size
    hbm_per_dev = (
        mem.argument_size_in_bytes
        + mem.temp_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
    )
    # host-platform bf16→f32 weight-copy artifact (see hlo_analysis):
    # subtract for the TPU-corrected footprint, report both.
    artifact = analysis.get("cpu_upcast_artifact_bytes", 0)
    hbm_corrected = hbm_per_dev - artifact
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "status": "OK",
        "kind": shape.kind,
        "impl": impl,
        "variant": variant,
        "microbatches": mb,
        "n_devices": n_dev,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device": hbm_per_dev,
            "cpu_upcast_artifact_bytes": artifact,
            "peak_per_device_tpu_corrected": hbm_corrected,
            "fits_16GiB": bool(hbm_corrected <= 16 * (1 << 30)),
        },
        "xla_cost_analysis": {
            "flops_per_device_loopbody_once": cost.get("flops", 0.0),
            "bytes_accessed_loopbody_once": cost.get("bytes accessed", 0.0),
        },
        "hlo_analysis": analysis,
        "model_flops_global": _model_flops(cfg, shape),
        "params_total": cfg.param_count(),
        "params_active": cfg.active_param_count(),
        "hlo_len_lines": hlo.count("\n"),
    }
    if save:
        _save(result, mesh_name, arch, shape_name)
    return result


def _save(result: Dict, mesh_name: str, arch: str, shape_name: str) -> None:
    out_dir = os.path.join(os.path.abspath(OUT_ROOT), mesh_name)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}.json")
    with open(path, "w") as fh:
        json.dump(result, fh, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-hlo", action="store_true", help="skip HLO parse")
    ap.add_argument("--impl", default="ref", help="attention impl: ref|blocked")
    ap.add_argument("--variant", default="", help="artifact subdir suffix")
    ap.add_argument("--kv-dtype", default="bfloat16", help="bfloat16|int8")
    args = ap.parse_args()

    cells = (
        [(a, s) for a in list_archs() for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    failures = 0
    for arch, shape_name in cells:
        mesh_name = "multipod_2x16x16" if args.multi_pod else "pod_16x16"
        if args.variant:
            mesh_name = f"{mesh_name}__{args.variant}"
        path = os.path.join(
            os.path.abspath(OUT_ROOT), mesh_name, f"{arch}__{shape_name}.json"
        )
        if args.skip_existing and os.path.exists(path):
            with open(path) as fh:
                if json.load(fh).get("status") in ("OK", "SKIP"):
                    print(f"[skip] {arch} × {shape_name} ({mesh_name})")
                    continue
        print(f"[cell] {arch} × {shape_name} ({mesh_name}) ...", flush=True)
        try:
            r = run_cell(
                arch,
                shape_name,
                multi_pod=args.multi_pod,
                microbatches=args.microbatches,
                hlo_analysis=not args.no_hlo,
                impl=args.impl,
                variant=args.variant,
                kv_dtype=args.kv_dtype,
            )
            if r["status"] == "OK":
                m = r["memory"]
                print(
                    f"   OK compile={r['compile_s']}s "
                    f"mem/dev={m['peak_per_device']/2**30:.2f}GiB "
                    f"(tpu-corr={m['peak_per_device_tpu_corrected']/2**30:.2f}) "
                    f"fits={m['fits_16GiB']} "
                    f"flops/dev={r['hlo_analysis'].get('flops', 0):.3e} "
                    f"coll/dev={r['hlo_analysis'].get('collective_bytes', 0):.3e}B",
                    flush=True,
                )
            else:
                print(f"   SKIP: {r['reason']}", flush=True)
        except Exception as exc:  # noqa: BLE001
            failures += 1
            print(f"   FAIL: {type(exc).__name__}: {exc}", flush=True)
            traceback.print_exc()
            _save(
                {
                    "arch": arch,
                    "shape": shape_name,
                    "mesh": mesh_name,
                    "status": "FAIL",
                    "error": f"{type(exc).__name__}: {exc}",
                },
                mesh_name,
                arch,
                shape_name,
            )
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
