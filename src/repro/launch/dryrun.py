"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the placeholder device count before ANY other import — jax locks
the device count on first init.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.models import model as model_lib
from repro.parallel import context as pctx
from repro.parallel.sharding import (
    batch_specs,
    guard_spec,
    partition_caches,
    partition_opt,
    partition_params,
    to_named,
)
from repro.roofline.analysis import analyze_compiled, memory_summary
from repro.roofline.model_flops import active_param_count, model_flops
from repro.train.train_step import (
    TrainConfig,
    init_train_state,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")
BF16_PARAMS = False  # flipped by --bf16-params (see EXPERIMENTS.md sec Perf)
# the chip the dry run prices its rooflines against: TPU v5e, by the
# device_kind JAX reports for it
TARGET_DEVICE_KIND = "TPU v5 lite"


def pick_microbatch(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """Grad-accumulation size for train shapes (keeps activations in HBM)."""
    if shape.kind != "train":
        return 0
    if cfg.d_model >= 4096:
        return 32
    if cfg.d_model >= 2048:
        return 64
    return 0


def input_specs(cfg: ModelConfig, shape: ShapeConfig):
    """ShapeDtypeStruct stand-ins for every model input (no allocation).
    [vlm]/[audio] archs get precomputed frontend embeddings per assignment."""
    b, s = shape.global_batch, shape.seq_len
    if cfg.input_mode == "tokens":
        inp = jax.ShapeDtypeStruct((b, s), jnp.int32)
    else:
        inp = jax.ShapeDtypeStruct((b, s, cfg.d_model), cfg.dtype)
    labels = jax.ShapeDtypeStruct((b, s), jnp.int32)
    pos_shape = (b, s, 3) if cfg.mrope else (b, s)
    positions = jax.ShapeDtypeStruct(pos_shape, jnp.int32)
    return inp, labels, positions


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig):
    b, t = shape.global_batch, shape.seq_len
    if cfg.input_mode == "tokens":
        inp = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    else:
        inp = jax.ShapeDtypeStruct((b, 1, cfg.d_model), cfg.dtype)
    caches = jax.eval_shape(lambda: model_lib.init_caches(cfg, b, t))
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    return inp, caches, pos


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, microbatch=None):
    """Returns (jitted_fn, example_args) for one cell, shardings applied."""
    dp = dp_axes(mesh)
    dpa = dp if len(dp) > 1 else dp[0]
    param_specs = partition_params(cfg, mesh)
    inp_spec, lab_spec, pos_spec = batch_specs(cfg, mesh, dp)
    # guard against non-divisible global batch (e.g. long_500k has B=1)
    _inp, _lab, _pos = input_specs(cfg, shape)
    inp_spec = guard_spec(inp_spec, _inp.shape, mesh)
    lab_spec = guard_spec(lab_spec, _lab.shape, mesh)
    pos_spec = guard_spec(pos_spec, _pos.shape, mesh)

    if shape.kind == "train":
        mb = pick_microbatch(cfg, shape) if microbatch is None else microbatch
        tc = TrainConfig(microbatch=mb, bf16_params=BF16_PARAMS)
        state_shapes = jax.eval_shape(
            lambda: init_train_state(cfg, tc, jax.random.PRNGKey(0)))
        state_specs = {
            "params": param_specs,
            "opt": partition_opt(param_specs, state_shapes["opt"]),
            "step": P(),
        }
        step = make_train_step(cfg, tc,
                               grad_shardings=to_named(mesh, param_specs))
        in_sh = (to_named(mesh, state_specs),
                 NamedSharding(mesh, inp_spec),
                 NamedSharding(mesh, lab_spec),
                 NamedSharding(mesh, pos_spec))
        rep = NamedSharding(mesh, P())
        metric_sh = {k: rep for k in ("ce", "loss", "grad_norm", "lr_scale")}
        out_sh = (to_named(mesh, state_specs), metric_sh)
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(0,))
        args = (state_shapes,) + input_specs(cfg, shape)
        return fn, args

    if shape.kind == "prefill":
        step = make_prefill_step(cfg)
        inp, _, positions = input_specs(cfg, shape)
        params_sh = to_named(mesh, param_specs)
        in_sh = (params_sh, NamedSharding(mesh, inp_spec),
                 NamedSharding(mesh, pos_spec))
        params_shapes = jax.eval_shape(
            lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
        # out shardings auto: the prefill caches inherit the constraint
        # applied inside attn_forward (dp, tp(seq), -, -)
        fn = jax.jit(step, in_shardings=in_sh, out_shardings=None)
        return fn, (params_shapes, inp, positions)

    # decode
    step = make_decode_step(cfg)
    inp, caches, pos = decode_input_specs(cfg, shape)
    cache_specs = partition_caches(cfg, mesh, dp, shape.global_batch,
                                   shape.seq_len)
    params_shapes = jax.eval_shape(
        lambda: model_lib.init_params(cfg, jax.random.PRNGKey(0)))
    dec_inp_spec = (P(dpa, None) if cfg.input_mode == "tokens"
                    else P(dpa, None, None))
    in_sh = (to_named(mesh, param_specs),
             NamedSharding(mesh, guard_spec(dec_inp_spec, inp.shape, mesh)),
             to_named(mesh, cache_specs),
             NamedSharding(mesh, guard_spec(P(dpa), pos.shape, mesh)))
    out_sh = (NamedSharding(mesh, guard_spec(P(dpa, None, None),
                                             (inp.shape[0], 1, 1), mesh)),
              to_named(mesh, cache_specs))
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(2,))
    return fn, (params_shapes, inp, caches, pos)


def apply_fact(cfg: ModelConfig, fact: str, block: int = 32) -> ModelConfig:
    """Apply the paper's factorization to a config (--fact butterfly etc.).

    ``--fact mixed`` uses the per-site policy the paper's Table-4 ablation
    points at: pixelfly MLPs/experts, butterfly attention, dense head.

    Default block 32: the compression/MXU-efficiency compromise — b=128 is
    fully MXU-aligned but only ~2.7x compression at d_ff~50k; b=32 gives
    ~9x compression and ~9x fewer FLOPs at quarter-tile MXU efficiency
    (the paper's IPU-vs-GPU granularity trade, relived on TPU)."""
    if not fact or fact == "dense":
        return cfg
    from repro.core.policy import uniform_policy
    if fact == "mixed":
        from repro.configs.base import recommended_policy
        return cfg.with_fact(recommended_policy(cfg, block=block))
    return cfg.with_fact(uniform_policy(fact, block_size=block))


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatch=None, save=True, fact: str = "") -> dict:
    cfg = apply_fact(get_config(arch), fact)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_arch = arch + (f"+{fact}" if fact and fact != "dense" else "")
    rec = {"arch": cell_arch, "shape": shape_name, "mesh": mesh_name,
           "target": TARGET_DEVICE_KIND}
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = "long_500k needs sub-quadratic mixing (DESIGN.md s5)"
        if save:
            os.makedirs(OUT_DIR, exist_ok=True)
            fname = f"{cell_arch}__{shape_name}__{mesh_name}.json"
            with open(os.path.join(OUT_DIR, fname), "w") as f:
                json.dump(rec, f, indent=1)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp = dp_axes(mesh)
    try:
        t0 = time.time()
        with pctx.mesh_context(mesh, dp, "model"):
            with mesh:
                fn, args = build_cell(cfg, shape, mesh, microbatch)
                lowered = fn.lower(*args)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
        roof = analyze_compiled(compiled, TARGET_DEVICE_KIND)
        mem = memory_summary(compiled)
        mf = model_flops(cfg, shape.global_batch, shape.seq_len, shape.kind)
        n_chips = mesh.size
        hlo_flops_global = roof.flops_per_device * n_chips
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            chips=n_chips,
            roofline=roof.to_dict(),
            memory=mem,
            model_flops=mf,
            hlo_flops_global=hlo_flops_global,
            useful_flops_ratio=(mf / hlo_flops_global
                                if hlo_flops_global else None),
            active_params=active_param_count(cfg),
        )
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        fname = f"{cell_arch}__{shape_name}__{mesh_name}.json"
        with open(os.path.join(OUT_DIR, fname), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--fact", default="",
                    help="apply the paper's factorization: any registered "
                         "kind (butterfly|pixelfly|...) or 'mixed' for the "
                         "per-site policy")
    ap.add_argument("--bf16-params", action="store_true",
                    help="bf16 params + f32 master (halves grad-AR/FSDP-AG)")
    args = ap.parse_args()
    global BF16_PARAMS
    BF16_PARAMS = args.bf16_params

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.microbatch,
                               fact=args.fact)
                roof = rec.get("roofline", {})
                print(
                    f"{arch:>22s} {shape:>12s} {rec['mesh']:>8s} "
                    f"{rec['status']:>7s} "
                    f"compile={rec.get('compile_s', '-'):>7}s "
                    f"dom={roof.get('dominant', '-'):>10s} "
                    f"bound={roof.get('bound_s', 0) * 1e3:8.2f}ms "
                    f"frac={roof.get('compute_fraction', 0):.3f}",
                    flush=True)
                if rec["status"] == "error":
                    failures += 1
                    print("   ", rec["error"][:300], flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
