import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, extract memory_analysis / cost_analysis / collective bytes.

Run one cell:   python -m repro.launch.dryrun --arch yi_34b --shape train_4k \
                    --mesh single --out results/
Run everything: python -m repro.launch.dryrun --all [--mesh both]

Each cell writes results/<arch>__<shape>__<mesh>.json incrementally so a
driver can resume.
"""
import argparse
import json
import math
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_config, list_configs
from ..models import build
from ..parallel import sharding as sh
from ..train.optimizer import Schedule, make_optimizer
from ..train.step import make_train_step
from ..train.train_state import TrainState, state_shardings
from .mesh import make_production_mesh

# ---------------------------------------------------------------------------
# cache sharding policy (see DESIGN.md §5: decode shards cache S over
# 'model' (flash-decoding); long-context (B=1) shards S over data+model)
# ---------------------------------------------------------------------------

def cache_pspec(path: str, leaf, long_ctx: bool, mesh) -> P:
    bat = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    # cache leaves under blocks/ carry a leading layer-stack dim (scan dim)
    stacked = bool(re.search(r"(^|/)blocks(/|$)", path))
    nd = leaf.ndim - (1 if stacked else 0)
    shape = leaf.shape[1:] if stacked else leaf.shape
    axsize = {n: s for n, s in zip(mesh.axis_names, mesh.devices.shape)}
    bat_n = int(np.prod([axsize[a] for a in bat])) if bat else 1

    def _p(*spec):
        # divisibility guard (explicit in_shardings require exact division)
        fixed = []
        for i, s in enumerate(spec):
            if s is None:
                fixed.append(None)
                continue
            names = s if isinstance(s, tuple) else (s,)
            ext = int(np.prod([axsize[a] for a in names]))
            fixed.append(s if shape[i] % ext == 0 else None)
        if stacked:
            fixed = [None] + fixed
        return P(*fixed)

    if re.search(r"(^|/)(k|v|cross_k|cross_v)$", path) and nd == 4:
        if long_ctx:
            sp = ("data", "model") if "pod" not in mesh.axis_names \
                else ("pod", "data", "model")
            return _p(None, sp, None, None)
        return _p(bat, "model", None, None)
    if path.endswith("pos") and nd == 1:
        return _p(None)
    if path.endswith("conv") and nd == 3:
        return _p(None if long_ctx else bat, None, "model")
    if path.endswith("ssm") and nd == 3:
        return _p(None if long_ctx else bat, "model", None)
    if path.endswith("wkv") and nd == 4:
        return _p(None if long_ctx else bat, "model", None, None)
    if nd >= 1 and not long_ctx:
        return _p(bat, *([None] * (nd - 1)))
    return _p(*([None] * nd))


def cache_shardings(caches_struct, mesh, long_ctx: bool):
    flat, treedef = jax.tree_util.tree_flatten_with_path(caches_struct)
    out = []
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
        out.append(NamedSharding(mesh, cache_pspec(path, leaf, long_ctx, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# HLO collective-bytes analysis (cost_analysis has no collective term)
# ---------------------------------------------------------------------------

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum PER-DEVICE operand bytes of every collective op in the HLO."""
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"(?:ROOT )?%?[\w.\-]+ = ([^=]+) (all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", ls)
        if not m:
            continue
        result_shapes, op = m.group(1), m.group(2)
        out[op]["count"] += 1
        out[op]["bytes"] += _shape_bytes(result_shapes)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items() if isinstance(v, dict))
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, mesh):
    """-> (fn, args_struct, in_shardings, static description)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    api = build(cfg)
    data_par = math.prod(mesh.shape[a] for a in mesh.axis_names if a != "model")
    n_tokens_step = shape.global_batch * (shape.seq_len if shape.kind == "train" else 1)
    moe_groups = math.gcd(shape.global_batch * (shape.seq_len if shape.kind == "train" else 1),
                          data_par)
    if shape.kind == "train":
        optimizer = make_optimizer(cfg.optimizer, Schedule())
        step = make_train_step(api, optimizer, moe_groups=moe_groups)
        params_s = jax.eval_shape(api.init, jax.random.key(0))
        opt_s = jax.eval_shape(optimizer.init, params_s)
        state_s = TrainState(jax.ShapeDtypeStruct((), jnp.int32), params_s, opt_s)
        batch_s = api.input_specs(shape)
        st_sh = state_shardings(state_s, mesh, cfg.fsdp_pods)
        b_sh = jax.tree.map(lambda s: sh.batch_sharding(mesh, len(s.shape)), batch_s)
        return step, (state_s, batch_s), (st_sh, b_sh), {"moe_groups": moe_groups}
    # inference shapes: SERVING layout -- bf16 TP-resident weights
    # (model-axis only; no FSDP gathers on the latency path)
    def _serving_params():
        p = jax.eval_shape(api.init, jax.random.key(0))
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if s.dtype == jnp.float32 else s.dtype), p)

    if shape.kind == "prefill":
        params_s = _serving_params()
        batch_s = api.input_specs(shape)

        def fn(params, batch):
            return api.prefill(params, batch, cache_len=shape.seq_len,
                               moe_groups=moe_groups)

        p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            sh.param_specs(params_s, serving=True),
                            is_leaf=lambda x: isinstance(x, P))
        b_sh = jax.tree.map(lambda s: sh.batch_sharding(mesh, len(s.shape)), batch_s)
        return fn, (params_s, batch_s), (p_sh, b_sh), {"moe_groups": moe_groups}
    # decode
    B, S = shape.global_batch, shape.seq_len
    long_ctx = B == 1
    params_s = _serving_params()
    if cfg.encdec:
        pre_batch = {"frames": jax.ShapeDtypeStruct(
            (B, cfg.encoder_positions, cfg.d_model), jnp.bfloat16),
            "tokens": jax.ShapeDtypeStruct((B, 8), jnp.int32)}
        caches_s = jax.eval_shape(
            lambda p, b: api.prefill(p, b, cache_len=S, moe_groups=moe_groups),
            params_s, pre_batch)[1]
    else:
        caches_s = jax.eval_shape(lambda: api.init_caches(B, S))
    tok_s = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    pos_s = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, caches, token, pos):
        return api.decode_step(params, caches, token, pos, moe_groups=moe_groups)

    p_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                        sh.param_specs(params_s, serving=True),
                        is_leaf=lambda x: isinstance(x, P))
    c_sh = cache_shardings(caches_s, mesh, long_ctx)
    t_sh = sh.batch_sharding(mesh, 2) if not long_ctx else NamedSharding(mesh, P(None, None))
    return fn, (params_s, caches_s, tok_s, pos_s), \
        (p_sh, c_sh, t_sh, NamedSharding(mesh, P())), {"moe_groups": moe_groups}


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             save_hlo: bool = False, overrides: dict | None = None) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    cfg = get_config(arch)
    if shape_name in cfg.skip_shapes:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": "per-DESIGN.md §6"}
    with sh.use_mesh(mesh):
        fn, args, shardings, extra = build_cell(arch, shape_name, mesh)
        # donate the mutable aggregate (train state / decode caches): the
        # production step runs in-place; without donation memory_analysis
        # double-counts every cache/optimizer buffer as input + temp copy
        shape = SHAPES[shape_name]
        donate = (0,) if shape.kind == "train" else ((1,) if shape.kind == "decode" else ())
        jitted = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from . import hlo_analysis

    corrected = hlo_analysis.totals(hlo)
    n_dev = math.prod(mesh.shape.values()) if hasattr(mesh.shape, "values") else mesh.size
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "status": "ok",
        "n_devices": int(mesh.size),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "cost": {
            "flops": float(cost.get("flops", -1)) if cost else -1,
            "bytes_accessed": float(cost.get("bytes accessed", -1)) if cost else -1,
            "transcendentals": float(cost.get("transcendentals", -1)) if cost else -1,
        },
        "collectives": coll,
        # trip-count-corrected per-device numbers (see hlo_analysis.py):
        # cost_analysis/flat text count while-loop bodies ONCE; these don't.
        "corrected": corrected,
        **extra,
    }
    if save_hlo:
        with open(f"{out_dir}/{arch}__{shape_name}__{mesh_kind}.hlo", "w") as f:
            f.write(hlo)
    print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: "
          f"lower {t_lower:.0f}s compile {t_compile:.0f}s "
          f"temp/dev {result['memory']['temp_bytes']/2**30:.2f} GiB "
          f"args/dev {result['memory']['argument_bytes']/2**30:.2f} GiB "
          f"flops/dev {result['cost']['flops']:.3g} "
          f"coll {coll['total_bytes']/2**20:.1f} MiB")
    print("memory_analysis:", mem)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = ([(a, s) for a in list_configs() for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    failures = 0
    for arch, shape in cells:
        for mk in meshes:
            out_path = f"{args.out}/{arch}__{shape}__{mk}.json"
            if os.path.exists(out_path):
                print(f"[dryrun] skip existing {out_path}")
                continue
            try:
                res = run_cell(arch, shape, mk, args.out, save_hlo=args.save_hlo)
            except Exception as e:  # noqa: BLE001 -- record, continue sweep
                failures += 1
                res = {"arch": arch, "shape": shape, "mesh": mk,
                       "status": "error", "error": f"{type(e).__name__}: {e}"}
                print(f"[dryrun] FAIL {arch} x {shape} x {mk}: {res['error']}",
                      file=sys.stderr)
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
