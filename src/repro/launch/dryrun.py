import os
# A compile-only rehearsal on 512 virtual CPU devices: it never takes an
# accelerator, neither here nor in the sweep's child processes (they inherit
# this environment), and it keeps whatever XLA_FLAGS the caller set.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512"
                           ).strip()

# The lines above MUST run before any other import (jax locks the platform
# and the device count at first init).  Everything below assumes 512
# virtual devices.

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import compat                       # noqa: E402
from repro import configs                      # noqa: E402
from repro.core import partition as zp         # noqa: E402
from repro.core import roofline, stepfn        # noqa: E402
from repro.core.accumulation import AccumConfig  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import transformer as T      # noqa: E402
from repro.models.common import ModelConfig    # noqa: E402
from repro.optim.adam import AdamConfig        # noqa: E402

SHAPES = {
    "train_4k": {"kind": "train", "seq": 4096, "batch": 256},
    "prefill_32k": {"kind": "prefill", "seq": 32768, "batch": 32},
    "decode_32k": {"kind": "decode", "seq": 32768, "batch": 128},
    "long_500k": {"kind": "decode_long", "seq": 524288, "batch": 1},
}

# long_500k needs sub-quadratic attention: run for SSM/hybrid and the
# sliding-window dense arch; skip pure full-attention archs (DESIGN.md §4).
LONG_OK = {"rwkv6-3b", "zamba2-7b", "gemma2-9b"}


def arch_shape_supported(arch: str, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and arch not in LONG_OK:
        return False, "pure full-attention arch: no sub-quadratic variant (see DESIGN.md)"
    return True, ""


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: str, mesh, *, n_microbatches: int):
    """ShapeDtypeStruct stand-ins (weak-type-correct, shardable, no device
    allocation) for every model input of the given workload shape."""
    info = SHAPES[shape]
    axis = stepfn.axis_ctx(mesh)
    S, B = info["seq"], info["batch"]
    if info["kind"] == "train":
        M = n_microbatches
        bspecs = stepfn.batch_specs(cfg, axis, microbatched=True)
        mb = B // M
        i32 = jnp.int32
        f = jnp.dtype(cfg.dtype)
        shapes = {"labels": ((M, mb, S), i32), "mask": ((M, mb, S), i32)}
        if cfg.input_mode == "embeddings":
            shapes["embeds"] = ((M, mb, S, cfg.d_model), f)
        elif cfg.input_mode == "vlm":
            P_ = cfg.vision_prefix_len
            shapes["tokens"] = ((M, mb, S - P_), i32)
            shapes["vision_embeds"] = ((M, mb, P_, cfg.d_model), f)
        else:
            shapes["tokens"] = ((M, mb, S), i32)
        return {k: jax.ShapeDtypeStruct(v[0], v[1],
                                        sharding=NamedSharding(mesh, bspecs[k]))
                for k, v in shapes.items()}
    if info["kind"] == "prefill":
        bspecs = stepfn.batch_specs(cfg, axis, microbatched=False)
        i32 = jnp.int32
        f = jnp.dtype(cfg.dtype)
        shapes = {"labels": ((B, S), i32), "mask": ((B, S), i32)}
        if cfg.input_mode == "embeddings":
            shapes["embeds"] = ((B, S, cfg.d_model), f)
        elif cfg.input_mode == "vlm":
            P_ = cfg.vision_prefix_len
            shapes["tokens"] = ((B, S - P_), i32)
            shapes["vision_embeds"] = ((B, P_, cfg.d_model), f)
        else:
            shapes["tokens"] = ((B, S), i32)
        return {k: jax.ShapeDtypeStruct(v[0], v[1],
                                        sharding=NamedSharding(mesh, bspecs[k]))
                for k, v in shapes.items()}
    # decode: one token per sequence
    seq_shard = info["kind"] == "decode_long"
    dp = tuple(a for a in (axis.pod, axis.data) if a)
    tok_spec = P(None) if seq_shard else P(dp)
    return jax.ShapeDtypeStruct((B,), jnp.int32,
                                sharding=NamedSharding(mesh, tok_spec))


def params_sds(cfg: ModelConfig, mesh):
    """Serving parameters: bf16, model-sharded; MoE expert weights are
    additionally sharded over `data` (expert dim, all_to_all dispatch)."""
    axis = stepfn.axis_ctx(mesh)
    tmpl = stepfn.full_template(cfg)
    fspecs = T.serve_param_specs(cfg, axis.tp)
    dt = jnp.dtype(cfg.dtype)

    def conv(l, sp):
        return jax.ShapeDtypeStruct(l.shape, dt,
                                    sharding=NamedSharding(mesh, sp))

    return jax.tree.map(conv, tmpl, fspecs)


def storage_sds(cfg: ModelConfig, mesh, partitioned: bool, *,
                span_pods: bool = False, expert_resident: bool = False):
    axis = stepfn.axis_ctx(mesh)
    span = span_pods and axis.pod is not None
    tmpl = stepfn.full_template(cfg)
    fspecs = T.param_specs(cfg, axis.tp)
    if partitioned:
        shapes = zp.partitioned_shapes(tmpl, fspecs,
                                       axis.dp if span else axis.ndata, axis.tp,
                                       expert_resident=expert_resident)
        pspecs = zp.partitioned_specs(fspecs, span_pods=span,
                                      expert_resident=expert_resident)
    else:
        shapes = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.float32), tmpl)
        pspecs = fspecs
    out = jax.tree.map(
        lambda l, sp: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        shapes, pspecs)
    return out, pspecs


def cache_sds(cfg: ModelConfig, mesh, batch: int, max_seq: int, *,
              seq_shard: bool):
    axis = stepfn.axis_ctx(mesh)
    dp = axis.dp
    if seq_shard:
        b_local, s_local = batch, max_seq // dp
    else:
        b_local, s_local = batch // dp, max_seq
    local = jax.eval_shape(lambda: T.init_cache(cfg, b_local, s_local, axis))
    cspecs = stepfn.cache_specs(cfg, axis, seq_shard=seq_shard)
    return stepfn.globalize(local, cspecs, mesh), cspecs


# ---------------------------------------------------------------------------
# One (arch x shape x mesh) dry-run
# ---------------------------------------------------------------------------
def run_one(arch: str, shape: str, *, multi_pod: bool, method: str = "layered",
            partitioned: bool = True, save: str | None = None,
            mesh_shape: str | None = None, expert_parallel: bool = False,
            reduce_dtype: str = "float32", tag_extra: str = "",
            fused: bool = False) -> dict:
    ok, why = arch_shape_supported(arch, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}
    t0 = time.time()
    if mesh_shape:
        # §Perf hillclimb: alternative (data, model) factorisation of the
        # same 256-chip pod
        d, m = (int(v) for v in mesh_shape.split("x"))
        assert d * m == 256, (d, m)
        mesh = compat.make_mesh((d, m), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    axis = stepfn.axis_ctx(mesh)
    cfg = configs.get_config(arch).padded_for_tp(axis.tp)
    info = SHAPES[shape]
    kind = info["kind"]

    if kind == "train":
        # paper-optimal: micro-batch of 1 sequence per data replica
        M = max(info["batch"] // axis.dp, 1)
        acc = AccumConfig(method=method, partitioned=partitioned,
                          n_microbatches=M, span_pods=multi_pod,
                          expert_parallel=expert_parallel,
                          reduce_dtype=reduce_dtype)
        opt_cfg = AdamConfig(moment_dtype="bfloat16",
                             grad_clip=0 if fused else 1.0)
        build = stepfn.build_fused_train_step if fused else stepfn.build_train_step
        step = build(cfg, mesh, acc, opt_cfg, donate=True)
        storage, _ = storage_sds(cfg, mesh, partitioned, span_pods=multi_pod,
                                 expert_resident=expert_parallel and cfg.is_moe)
        moments = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16,
                                           sharding=l.sharding), storage)
        opt = {"mu": moments, "nu": moments,
               "step": jax.ShapeDtypeStruct((), jnp.int32,
                                            sharding=NamedSharding(mesh, P()))}
        batch = input_specs(cfg, shape, mesh, n_microbatches=M)
        args = (storage, opt, batch)
        fn = step
    elif kind == "prefill":
        fn = stepfn.build_prefill_step(cfg, mesh)
        params = params_sds(cfg, mesh)
        cache, _ = cache_sds(cfg, mesh, info["batch"], info["seq"],
                             seq_shard=False)
        batch = input_specs(cfg, shape, mesh, n_microbatches=1)
        args = (params, cache, batch)
    else:
        seq_shard = kind == "decode_long"
        fn = stepfn.build_serve_step(cfg, mesh, seq_shard=seq_shard)
        params = params_sds(cfg, mesh)
        cache, _ = cache_sds(cfg, mesh, info["batch"], info["seq"],
                             seq_shard=seq_shard)
        toks = input_specs(cfg, shape, mesh, n_microbatches=1)
        args = (params, cache, toks)

    lowered = fn.lower(*args)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    try:
        ca = compiled.cost_analysis() or {}
    except Exception:
        ca = {}

    costs = roofline.analyze(fn, *args, mesh=mesh,
                             cond_weight=(1.0 / cfg.hybrid_attn_period
                                          if cfg.hybrid_attn_period else 0.5))
    n_chips = mesh.devices.size
    if kind == "train":
        mf = roofline.model_flops_train(cfg, info["batch"], info["seq"])
    elif kind == "prefill":
        mf = roofline.model_flops_train(cfg, info["batch"], info["seq"]) / 3.0
    else:
        mf = roofline.model_flops_decode(cfg, info["batch"])
    report = {
        "arch": arch, "shape": shape, "multi_pod": multi_pod,
        "method": method if kind == "train" else "n/a",
        "partitioned": partitioned if kind == "train" else False,
        "status": "ok",
        "n_chips": n_chips,
        "seconds": round(time.time() - t0, 1),
        "memory": {
            "device_bytes": mem.temp_size_in_bytes + mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
        },
        "xla_cost_analysis": {k: ca.get(k) for k in ("flops", "bytes accessed")
                              if k in ca},
        "roofline": costs.summary(),
        "coll_counts": {f"{ax}:{nm}": v
                        for (ax, nm), v in costs.coll_counts.items()},
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips) / max(costs.dot_flops, 1.0),
        "notes": costs.notes[:5],
    }
    print(json.dumps(report, indent=1, default=str))
    print("memory_analysis:", mem)
    if save:
        os.makedirs(save, exist_ok=True)
        tag = f"{arch}_{shape}_{'pod2' if multi_pod else 'pod1'}"
        if kind == "train" and method != "layered":
            tag += f"_{method}"
        if tag_extra:
            tag += f"_{tag_extra}"
        with open(os.path.join(save, tag + ".json"), "w") as f:
            json.dump(report, f, indent=1, default=str)
    return report


def run_all(out_dir: str, *, archs=None, shapes=None, meshes=(False, True),
            method: str = "layered") -> None:
    """Subprocess per combo (isolates compile memory; one failure doesn't
    kill the sweep)."""
    archs = archs or configs.list_archs()
    shapes = shapes or list(SHAPES)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'pod2' if mp else 'pod1'}"
                outf = os.path.join(out_dir, tag + ".json")
                if os.path.exists(outf):
                    print(f"[skip existing] {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--method", method,
                       "--save", out_dir]
                if mp:
                    cmd.append("--multi-pod")
                print(f"[run] {tag}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=3600)
                if r.returncode != 0:
                    failures.append(tag)
                    with open(os.path.join(out_dir, tag + ".FAILED"), "w") as f:
                        f.write(r.stdout[-5000:] + "\n" + r.stderr[-10000:])
                    print(f"[FAIL] {tag}: see {tag}.FAILED")
    print(f"done; {len(failures)} failures: {failures}")


def apply_plan(args, passed: set[str]) -> None:
    """Adopt a plan's execution section (launch.plan output): arch, method,
    partition and — when its mesh factors the 256-chip pod — the mesh shape.
    Explicitly passed CLI flags win over the plan (same contract as
    launch.train); the dry-run's workload shapes and micro-batch sizing
    (derived from the shape) stay its own."""
    from repro.planner.plan import execution_of, load_plan

    ex = execution_of(load_plan(args.plan))
    args.arch = args.arch or ex.get("arch")
    if "method" in ex and "--method" not in passed:
        args.method = ex["method"]
    if "partitioned" in ex and "--no-partition" not in passed:
        args.no_partition = not ex["partitioned"]
    d, m = (int(v) for v in ex.get("mesh", "1x1").split("x"))
    if "--mesh-shape" in passed:
        pass
    elif d * m == 256:
        args.mesh_shape = ex["mesh"]
    elif "mesh" in ex:
        print(f"[plan] mesh {ex['mesh']} is not a 256-chip factorisation; "
              f"keeping the default production mesh")


def main() -> None:
    ap = argparse.ArgumentParser(allow_abbrev=False)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--plan", default=None,
                    help="JSON plan from `python -m repro.launch.plan`")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--method", default="layered",
                    choices=["layered", "standard"])
    ap.add_argument("--no-partition", action="store_true")
    ap.add_argument("--mesh-shape", default=None,
                    help="alternative data x model split of 256 chips, e.g. 32x8")
    ap.add_argument("--expert-parallel", action="store_true")
    ap.add_argument("--reduce-dtype", default="float32")
    ap.add_argument("--fused", action="store_true",
                    help="paper §C.3: per-layer fused optimizer update")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save", default=None)
    ap.add_argument("--all", action="store_true",
                    help="run the full (arch x shape x mesh) sweep in "
                         "subprocesses")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    if args.plan:
        apply_plan(args, {a.split("=")[0] for a in sys.argv[1:]
                          if a.startswith("--")})
    if args.all:
        run_all(args.out, method=args.method)
        return
    assert args.arch and args.shape, "--arch and --shape required (or --all)"
    run_one(args.arch, args.shape, multi_pod=args.multi_pod,
            method=args.method, partitioned=not args.no_partition,
            save=args.save, mesh_shape=args.mesh_shape,
            expert_parallel=args.expert_parallel,
            reduce_dtype=args.reduce_dtype, tag_extra=args.tag,
            fused=args.fused)


if __name__ == "__main__":
    main()
