import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this builds the real step function (train_step with optimizer
update / serve prefill / serve decode), abstract state via jax.eval_shape
(no allocation anywhere), production shardings from parallel/sharding.py,
then::

    lowered  = jax.jit(step, in_shardings=…, out_shardings=…).lower(*specs)
    compiled = lowered.compile()
    compiled.memory_analysis()   # proves it fits
    compiled.cost_analysis()     # FLOPs/bytes for §Roofline

and parses the compiled HLO for collective wire bytes.  Results stream to a
JSON file consumed by EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs import shapes as shp
from repro.launch import mesh as meshlib
from repro.launch import roofline
from repro.models import transformer as tf
from repro.optim.adamw import AdamW
from repro.parallel import sharding as shd
from repro.serve.engine import quantize_for_serving
from repro.train.step import TrainState, make_train_step

# Per-arch knobs for the production run.  Default regime is ZeRO-1 (params
# TP-only over "model"; optimizer m/v 2D-sharded over data×model) — no
# per-layer weight all-gathers.  ≥100B models can't hold params TP-only, so
# they go full 2D param FSDP + int8 optimizer state + bf16 grad accum.
BIG = {"deepseek-v3-671b", "jamba-1.5-large-398b", "dbrx-132b"}
MID = {"granite-20b", "deepseek-7b", "qwen2-vl-7b"}


def train_knobs(arch: str, overrides: Optional[dict] = None) -> dict:
    kn = {"state_dtype": "f32", "n_microbatches": 8, "fsdp": False,
          "opt_fsdp": True, "accum_dtype": "f32", "tp": True}
    if arch in MID:
        kn.update(state_dtype="bf16")
    if arch in BIG:
        kn.update(state_dtype="int8", n_microbatches=16, fsdp=True,
                  accum_dtype="bf16")
    if overrides:
        kn.update({k: v for k, v in overrides.items() if v is not None})
    return kn


def _policy_state_specs(policy):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32),
                        policy.as_arrays())


def _replicated(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def build_train_cell(cfg, shape, mesh, ctx, knobs):
    """Returns (step_fn, arg_specs, in_shardings, out_shardings, meta)."""
    optimizer = AdamW(learning_rate=1e-4, weight_decay=0.1,
                      state_dtype=knobs["state_dtype"])
    accum = jnp.bfloat16 if knobs["accum_dtype"] == "bf16" else jnp.float32
    step_fn = make_train_step(cfg, ctx, optimizer,
                              n_microbatches=knobs["n_microbatches"],
                              accum_dtype=accum)

    params_shapes = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                                   jax.random.PRNGKey(0))
    opt_shapes = jax.eval_shape(optimizer.init, params_shapes)
    policy = tf.build_policy(cfg)
    policy_shapes = _policy_state_specs(policy)
    state_shapes = TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32), params=params_shapes,
        opt_state=opt_shapes, policy=policy_shapes, grad_error=None)
    batch_shapes = shp.batch_specs(cfg, shape)

    p_shard = shd.params_shardings(cfg, params_shapes, mesh, ctx,
                                   fsdp=knobs["fsdp"], tp=knobs["tp"])
    # ZeRO-1: optimizer state always 2D-sharded (params may be TP-only).
    p_shard_fsdp = (p_shard if knobs["fsdp"] else
                    shd.params_shardings(cfg, params_shapes, mesh, ctx,
                                         fsdp=knobs["opt_fsdp"],
                                         tp=knobs["tp"]))
    o_shard = shd.opt_state_shardings(p_shard_fsdp, opt_shapes, mesh)
    state_shard = TrainState(
        step=NamedSharding(mesh, P()), params=p_shard, opt_state=o_shard,
        policy=_replicated(mesh, policy_shapes), grad_error=None)
    b_shard = shd.batch_shardings(batch_shapes, mesh, ctx)

    metrics_shapes = jax.eval_shape(step_fn, state_shapes, batch_shapes)[1]
    out_shard = (state_shard, _replicated(mesh, metrics_shapes))
    return (step_fn, (state_shapes, batch_shapes),
            (state_shard, b_shard), out_shard, {"policy": policy})


def build_serve_cell(cfg, shape, mesh, ctx, kind: str,
                     serve_dtype: str = "int4"):
    """Prefill or decode step over serve-layout params.

    serve_dtype: 'int4' (paper's mixed-precision deployment — packed codes
    + scales) or 'bf16' (unquantized baseline for the §Perf comparison)."""
    policy = tf.build_policy(cfg)
    policy_shapes = _policy_state_specs(policy)

    params_shapes = jax.eval_shape(lambda k: tf.init_params(cfg, k),
                                   jax.random.PRNGKey(0))
    if serve_dtype == "int4":
        qparams_shapes = jax.eval_shape(
            lambda p: quantize_for_serving(p, policy.as_arrays(), cfg),
            params_shapes)
    else:   # bf16 baseline: raw weights, 16-"bit" policy (quant ~identity)
        qparams_shapes = params_shapes
        policy = tf.build_policy(cfg, b_hi=16.0, b_lo=16.0)
        policy_shapes = _policy_state_specs(policy)
    batch_shapes = shp.batch_specs(cfg, shape)

    # ≥100B: TP-only would replicate expert banks over 'data' (10s of GiB);
    # 2D-shard them and pay the per-layer gather (removed by the 2-axis EP
    # optimization in §Perf).
    qp_shard = shd.params_shardings(cfg, qparams_shapes, mesh, ctx,
                                    fsdp=(cfg.name in BIG))
    b_shard = shd.batch_shardings(batch_shapes, mesh, ctx)

    def logits_sharding(shape3):
        sp = shd._validate(P(ctx.batch_spec, None, "model"), shape3, mesh,
                           ("logits",))
        return NamedSharding(mesh, sp)

    if kind == "prefill":
        def step_fn(params, pa, batch):
            logits, caches, _ = tf.apply(params, pa, batch, cfg, ctx,
                                         mode="prefill")
            return logits, caches
        arg_specs = (qparams_shapes, policy_shapes, batch_shapes)
        in_shard = (qp_shard, _replicated(mesh, policy_shapes), b_shard)
        out_abs = jax.eval_shape(step_fn, *arg_specs)
        logits_shard = logits_sharding(out_abs[0].shape)
        cache_shard = shd.cache_shardings(cfg, out_abs[1], mesh, ctx)
        return step_fn, arg_specs, in_shard, (logits_shard, cache_shard), \
            {"policy": policy}

    assert kind == "decode"
    cache_shapes = jax.eval_shape(
        lambda: tf.init_caches(cfg, shape.batch, shape.seq))
    cache_shard = shd.cache_shardings(cfg, cache_shapes, mesh, ctx)

    def step_fn(params, pa, caches, batch):
        logits, new_caches, _ = tf.apply(params, pa, batch, cfg, ctx,
                                         mode="decode", caches=caches,
                                         positions=batch["positions"])
        return logits, new_caches
    arg_specs = (qparams_shapes, policy_shapes, cache_shapes, batch_shapes)
    in_shard = (qp_shard, _replicated(mesh, policy_shapes), cache_shard,
                b_shard)
    out_abs = jax.eval_shape(step_fn, *arg_specs)
    logits_shard = logits_sharding(out_abs[0].shape)
    return step_fn, arg_specs, in_shard, (logits_shard, cache_shard), \
        {"policy": policy}


def model_flops(policy, shape) -> float:
    macs = sum(u.macs_per_token for u in policy.units)
    tokens = shape.batch * (1 if shape.kind == "decode" else shape.seq)
    factor = 6.0 if shape.kind == "train" else 2.0
    return factor * macs * tokens


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             knob_overrides: Optional[dict] = None, verbose: bool = True):
    cfg = configs.get_config(arch)
    shape = shp.SHAPES[shape_name]
    reason = shp.skip_reason(cfg, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if reason is not None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": reason}

    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    ctx = meshlib.make_context(mesh)
    knobs = train_knobs(arch, knob_overrides)
    if shape.kind == "train" and not knobs["tp"]:
        # small-model regime: every mesh axis carries batch (see §Perf B)
        from repro.parallel.context import ParallelContext
        ctx = ParallelContext(mesh=mesh, batch_axes=tuple(mesh.axis_names),
                              model_axis="model")

    t0 = time.time()
    serve_dtype = (knob_overrides or {}).get("serve_dtype") or "int4"
    if shape.kind == "train":
        step_fn, args, in_sh, out_sh, meta = build_train_cell(
            cfg, shape, mesh, ctx, knobs)
    else:
        step_fn, args, in_sh, out_sh, meta = build_serve_cell(
            cfg, shape, mesh, ctx, shape.kind, serve_dtype=serve_dtype)

    # donate the big mutable buffers: train state (arg 0) / decode caches
    donate = (0,) if shape.kind == "train" else \
        ((2,) if shape.kind == "decode" else ())
    with jax.set_mesh(mesh):
        lowered = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    chips = mesh.devices.size
    bytes_per_dev = None
    if mem is not None:
        bytes_per_dev = (getattr(mem, "argument_size_in_bytes", 0)
                         + getattr(mem, "output_size_in_bytes", 0)
                         + getattr(mem, "temp_size_in_bytes", 0)
                         + getattr(mem, "generated_code_size_in_bytes", 0))
    rf = roofline.analyze(arch, shape_name, mesh_name, chips, cost, hlo,
                          model_flops(meta["policy"], shape), bytes_per_dev)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "status": "ok", "chips": chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "bytes_per_device": bytes_per_dev,
        "hlo_flops": rf.hlo_flops, "hlo_bytes": rf.hlo_bytes,
        "coll_bytes": rf.coll_bytes, "coll_detail": rf.coll_detail,
        "compute_s": rf.compute_s, "memory_s": rf.memory_s,
        "collective_s": rf.collective_s, "dominant": rf.dominant,
        "model_flops": rf.model_flops, "useful_ratio": rf.useful_ratio,
        "roofline_fraction": rf.roofline_fraction,
        "knobs": knobs if shape.kind == "train" else {"serve": serve_dtype},
    }
    if verbose:
        gb = (bytes_per_dev or 0) / 2**30
        print(f"[{arch} × {shape_name} × {mesh_name}] OK "
              f"mem/dev={gb:.2f} GiB dominant={rf.dominant} "
              f"compute={rf.compute_s*1e3:.1f}ms memory={rf.memory_s*1e3:.1f}ms "
              f"coll={rf.collective_s*1e3:.1f}ms "
              f"useful={rf.useful_ratio:.2f} "
              f"roofline={rf.roofline_fraction:.3f} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--state-dtype", default=None)
    ap.add_argument("--fsdp", type=lambda s: s == "true", default=None)
    ap.add_argument("--tp", type=lambda s: s == "true", default=None)
    ap.add_argument("--serve-dtype", default=None)
    args = ap.parse_args()

    archs = configs.ARCHS if (args.all or not args.arch) else [args.arch]
    names = list(shp.SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    overrides = {"n_microbatches": args.microbatches,
                 "state_dtype": args.state_dtype, "fsdp": args.fsdp,
                 "tp": args.tp, "serve_dtype": args.serve_dtype}

    results = []
    for arch in archs:
        for shape_name in names:
            for mp in meshes:
                try:
                    rec = run_cell(arch, shape_name, multi_pod=mp,
                                   knob_overrides=overrides)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e)}
                    print(f"[{arch} × {shape_name}] FAILED: {e}")
                    traceback.print_exc()
                results.append(rec)
                if args.out:
                    with open(args.out, "w") as f:
                        json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    er = sum(1 for r in results if r["status"] == "error")
    print(f"\n== dry-run: {ok} ok, {sk} skipped, {er} errors "
          f"of {len(results)} cells ==")
    return 1 if er else 0


if __name__ == "__main__":
    raise SystemExit(main())
