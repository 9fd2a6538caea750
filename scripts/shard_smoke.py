import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

"""Dev driver: lower+compile smoke configs on a (2,4) mesh — fast sharding
bug shakeout before the production 512-device dry-run."""
import sys
import traceback

import jax

from repro import configs
from repro.configs.shapes import ShapeSpec
from repro.launch import dryrun
from repro.launch.mesh import make_context, make_test_mesh
from repro.models import transformer as tf

SMOKE_SHAPES = {
    "train": ShapeSpec("t", "train", 256, 8),
    "prefill": ShapeSpec("p", "prefill", 256, 8),
    "decode": ShapeSpec("d", "decode", 256, 8),
}


def run(arch: str):
    base = configs.get_config(arch)
    cfg = base.smoke().replace(name=base.name)
    mesh = make_test_mesh(2, 4)
    ctx = make_context(mesh)
    knobs = {"state_dtype": "int8", "n_microbatches": 2, "fsdp": True}
    for kind, shape in SMOKE_SHAPES.items():
        reason = None
        if not cfg.causal and kind == "decode":
            reason = "encoder"
        if reason:
            print(f"  {arch} {kind}: skip ({reason})")
            continue
        try:
            if kind == "train":
                fn, args, in_sh, out_sh, meta = dryrun.build_train_cell(
                    cfg, shape, mesh, ctx, knobs)
            else:
                fn, args, in_sh, out_sh, meta = dryrun.build_serve_cell(
                    cfg, shape, mesh, ctx, kind)
            with jax.set_mesh(mesh):
                lowered = jax.jit(fn, in_shardings=in_sh,
                                  out_shardings=out_sh).lower(*args)
                compiled = lowered.compile()
            cost = compiled.cost_analysis()
            print(f"  {arch} {kind}: OK flops/chip={cost.get('flops',0):.3g}")
        except Exception as e:
            print(f"  {arch} {kind}: FAIL {type(e).__name__}: {e}")
            traceback.print_exc()
            return False
    return True


if __name__ == "__main__":
    targets = sys.argv[1:] or configs.ARCHS
    bad = [a for a in targets if not run(a)]
    print("FAILED:" if bad else "ALL OK", bad)
