import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell against
ShapeDtypeStruct inputs (no allocation), record memory/cost analysis and the
optimized HLO for the roofline pass.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh pod1
  python -m repro.launch.dryrun --all [--mesh pod1|pod2|both] [--out DIR]
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config, list_archs
from repro.launch import mesh as mesh_lib
from repro.launch.specs import (cache_specs, decode_token_specs,
                                pick_microbatches, train_batch_specs)
from repro.models.model import build_model
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.optimizer import AdamWConfig
from repro.train.step import abstract_train_state, make_train_step


def lower_cell(arch: str, shape_name: str, mesh, *, moe_impl="sliced",
               extra=None):
    """Returns (lowered, meta) for one cell."""
    from repro.models.shard_ctx import sharding_rules
    cfg = get_config(arch)
    extra = extra or {}
    overrides = {}
    if moe_impl == "ep" and cfg.is_moe:
        tp = mesh.shape.get("model", 1)
        if cfg.n_experts % tp == 0:
            overrides = {"exp": "model", "moe_ff": None}
    extra = dict(extra, overrides=overrides)
    with sharding_rules(cfg.policy, mesh, fsdp_pod=extra.get("fsdp_pod", False),
                        **overrides):
        return _lower_cell_inner(arch, shape_name, mesh, moe_impl=moe_impl,
                                 extra=extra)


def _lower_cell_inner(arch: str, shape_name: str, mesh, *, moe_impl="sliced",
                      extra=None):
    import dataclasses as _dc
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    extra = extra or {}
    if extra.get("capacity_factor"):
        cfg = _dc.replace(cfg, capacity_factor=extra["capacity_factor"])
        import repro.configs.base as _b
        _b._REGISTRY[cfg.name] = cfg
    model = build_model(cfg, moe_impl=moe_impl,
                        remat=extra.get("remat", True),
                        opts=extra.get("opts"))
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "params": cfg.param_count(), "active_params": cfg.active_param_count(),
            "policy": cfg.policy, "moe_impl": moe_impl}

    if shape.kind == "train":
        import jax.numpy as _jnp
        nm = extra.get("n_microbatch") or pick_microbatches(cfg, shape, mesh)
        meta["n_microbatch"] = nm
        mdt = getattr(_jnp, extra.get("moments_dtype", "float32"))
        opt_cfg = AdamWConfig(moments_dtype=extra.get("moments_dtype", "float32"))
        gdt = getattr(_jnp, extra.get("grad_dtype", "float32") or "float32")
        step = make_train_step(model, opt_cfg, n_microbatch=nm, grad_dtype=gdt)
        state_sh = mesh_lib.state_shardings(
            model, mesh, fsdp_pod=extra.get("fsdp_pod", False),
            overrides=extra.get("overrides"))
        state_abs = abstract_train_state(model, moments_dtype=mdt)
        bspecs, bshard = train_batch_specs(cfg, shape, mesh)
        lowered = jax.jit(step, in_shardings=(state_sh, bshard),
                          out_shardings=(state_sh, None),
                          donate_argnums=(0,)).lower(state_abs, bspecs)
        return lowered, meta

    model_bf16 = build_model(cfg, moe_impl=moe_impl, remat=False,
                             opts=extra.get("opts"))
    param_sh = mesh_lib.param_shardings(model_bf16, mesh,
                                        overrides=extra.get("overrides"))
    from repro.models.param import abstract_params
    p_abs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        abstract_params(model_bf16.defs()))
    import jax.numpy as _j
    cache_dtype = getattr(_j, extra.get("cache_dtype", "bfloat16"))
    caches_abs, cache_sh = cache_specs(model_bf16, shape, mesh,
                                       dtype=cache_dtype)

    if shape.kind == "prefill":
        step = make_prefill_step(model_bf16)
        bspecs, bshard = train_batch_specs(cfg, shape, mesh)
        bspecs.pop("labels"), bspecs.pop("mask")
        bshard.pop("labels"), bshard.pop("mask")
        lowered = jax.jit(step, in_shardings=(param_sh, bshard, cache_sh),
                          out_shardings=(None, cache_sh),
                          donate_argnums=(2,)).lower(p_abs, bspecs, caches_abs)
        return lowered, meta

    # decode: one new token against a cache of seq_len
    step = make_decode_step(model_bf16)
    tok_abs, tok_sh = decode_token_specs(cfg, shape, mesh)
    len_abs = jax.ShapeDtypeStruct((), jnp.int32)
    len_sh = NamedSharding(mesh, P())
    lowered = jax.jit(step, in_shardings=(param_sh, cache_sh, tok_sh, len_sh),
                      out_shardings=(tok_sh, cache_sh),
                      donate_argnums=(1,)).lower(p_abs, caches_abs, tok_abs,
                                                 len_abs)
    return lowered, meta


def run_cell(arch, shape_name, mesh, mesh_name, out_dir=None, save_hlo=True,
             moe_impl="sliced", extra=None, tag=""):
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, mesh, moe_impl=moe_impl,
                               extra=extra)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):   # older jax: one dict per device
        ca = ca[0] if ca else {}
    txt = compiled.as_text()
    colls = {k: txt.count(k + "(") + txt.count(k + "-start(")
             for k in ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")}
    meta.update({
        "mesh": mesh_name, "tag": tag,
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_gb": round((ma.argument_size_in_bytes + ma.temp_size_in_bytes +
                          ma.output_size_in_bytes -
                          ma.alias_size_in_bytes) / 2 ** 30, 3),
        "ca_flops_per_dev_while_once": ca.get("flops"),
        "ca_bytes_per_dev_while_once": ca.get("bytes accessed"),
        "collective_op_counts": colls,
    })
    if out_dir and save_hlo:
        import zstandard as zstd
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}_{shape_name}_{mesh_name}{('_' + tag) if tag else ''}"
        with open(os.path.join(out_dir, name + ".hlo.zst"), "wb") as f:
            f.write(zstd.ZstdCompressor(level=3).compress(txt.encode()))
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(meta, f, indent=1)
    return meta


def all_cells():
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in cfg.shapes():
            yield arch, shape.name


def pp_smoke(out_dir=None):
    """Pipeline-parallel dry-run: a llama3-8b-proportioned layer stack
    pipelined over mesh (4,8,16) = ("pipe","data","model") — 512 chips."""
    import jax.numpy as _jnp
    from repro.train.pipeline import pipelined_apply
    mesh = jax.make_mesh((4, 8, 16), ("pipe", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    L, B, S, D, F = 32, 64, 4096, 4096, 14336

    def layer_fn(p, h):
        hn = h * jax.lax.rsqrt(
            _jnp.mean(h * h, -1, keepdims=True) + 1e-6)
        up = _jnp.dot(hn, p["w_in"].astype(_jnp.bfloat16))
        return h + _jnp.dot(jax.nn.silu(up),
                            p["w_out"].astype(_jnp.bfloat16))

    params = {"w_in": jax.ShapeDtypeStruct((L, D, F), _jnp.bfloat16),
              "w_out": jax.ShapeDtypeStruct((L, F, D), _jnp.bfloat16)}
    x = jax.ShapeDtypeStruct((B, S, D), _jnp.bfloat16)

    def step(p, x_):
        return pipelined_apply(layer_fn, p, x_, mesh, n_microbatch=8)

    t0 = time.time()
    lowered = jax.jit(step).lower(params, x)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes +
            ma.output_size_in_bytes - ma.alias_size_in_bytes) / 2 ** 30
    txt = compiled.as_text()
    cp = txt.count("collective-permute(") + txt.count("collective-permute-start(")
    meta = {"arch": "pp-smoke-llama-proportioned", "mesh": "pipe4_data8_model16",
            "compile_s": round(time.time() - t0, 1), "peak_gb": round(peak, 2),
            "collective_permutes": cp}
    print(f"[OK]   pp-smoke (4,8,16) compile={meta['compile_s']}s "
          f"peak={meta['peak_gb']}GB collective-permutes={cp}", flush=True)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "pp_smoke.json"), "w") as f:
            json.dump(meta, f, indent=1)
    return meta


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--moe-impl", default="sliced")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--pp-smoke", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=0.0)
    ap.add_argument("--grad-dtype", default="float32")
    ap.add_argument("--scores-bf16", action="store_true")
    ap.add_argument("--no-attn-chunk-remat", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=0)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--moments-dtype", default="float32")
    ap.add_argument("--cache-dtype", default="bfloat16")
    ap.add_argument("--fsdp-pod", action="store_true")
    args = ap.parse_args()
    if args.pp_smoke:
        pp_smoke(out_dir=args.out)
        raise SystemExit(0)
    extra = {"moments_dtype": args.moments_dtype, "fsdp_pod": args.fsdp_pod,
             "cache_dtype": args.cache_dtype, "grad_dtype": args.grad_dtype,
             "capacity_factor": args.capacity_factor}
    opts = {}
    if args.scores_bf16:
        opts["scores_bf16"] = True
    if args.q_chunk:
        opts["q_chunk"] = args.q_chunk
    if args.no_attn_chunk_remat:
        opts["attn_chunk_remat"] = False
    if opts:
        extra["opts"] = opts
    if args.microbatch:
        extra["n_microbatch"] = args.microbatch

    meshes = []
    if args.mesh in ("pod1", "both"):
        meshes.append(("pod1", mesh_lib.make_production_mesh(multi_pod=False)))
    if args.mesh in ("pod2", "both"):
        meshes.append(("pod2", mesh_lib.make_production_mesh(multi_pod=True)))

    cells = list(all_cells()) if args.all else [(args.arch, args.shape)]
    failures = 0
    for mesh_name, mesh in meshes:
        for arch, shape in cells:
            try:
                meta = run_cell(arch, shape, mesh, mesh_name, out_dir=args.out,
                                save_hlo=not args.no_hlo,
                                moe_impl=args.moe_impl, tag=args.tag,
                                extra=extra)
                print(f"[OK]   {arch:24s} {shape:12s} {mesh_name} "
                      f"compile={meta['compile_s']:7.1f}s "
                      f"peak={meta['peak_gb']:7.2f}GB "
                      f"colls={meta['collective_op_counts']}", flush=True)
            except Exception as e:
                failures += 1
                print(f"[FAIL] {arch:24s} {shape:12s} {mesh_name}: {e!r}",
                      flush=True)
                traceback.print_exc()
    # note skipped long_500k cells for full-attention archs
    for arch in list_archs():
        cfg = get_config(arch)
        for s in cfg.skipped_shapes():
            print(f"[SKIP] {arch:24s} {s.name:12s} (full-attention arch; "
                  "see DESIGN.md §4)", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
