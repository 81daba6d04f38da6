import os


def force_fake_devices(count: int = 512) -> None:
    """Give this process ``count`` fake host devices for AOT compilation.

    Must run before the first jax backend init — called from the
    ``__main__`` entrypoint below, NOT at import time: pure helpers in this
    module (``collective_bytes_from_hlo``, ``pick_microbatches``,
    ``choose_tp_fold``) are imported by the test suite, and an import-time
    env mutation would silently put the ENTIRE suite (collected before any
    test runs) on a 512-device platform — exactly what tests/conftest.py
    promises never happens to smoke tests and benches.
    """
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + f" --xla_force_host_platform_device_count"
                                 f"={count}")


"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this AOT-compiles the real step function (train_step /
prefill / decode_step) against ShapeDtypeStruct inputs on the production
mesh — no device allocation — and records:

  * memory_analysis()  (per-device bytes — proves the config fits)
  * cost_analysis()    (HLO FLOPs / bytes for the roofline)
  * collective-op operand bytes parsed from the optimized HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute) for the collective roofline term.

Results land in benchmarks/results/dryrun_<mesh>_<arch>_<shape>.json and
EXPERIMENTS.md §Dry-run / §Roofline are generated from them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, SHAPES, get_arch, shape_applicable
from repro.configs.base import ArchConfig, ShapeConfig
from repro.data import make_batch_specs
from repro.launch.mesh import dp_axes_of, make_production_mesh
from repro.models import transformer as T
from repro.models.layers import ShardCtx
from repro.optim import AdamW
from repro.train import make_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__),
                           "../../../benchmarks/results")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def collective_bytes_from_hlo(hlo_text: str) -> dict:
    """Per-device bytes moved by every collective in the optimized HLO.

    The SPMD-partitioned module carries *per-device* shapes; we take the
    RESULT type(s) on the LHS of each collective (for an all-reduce the
    result equals the operand; for an all-gather the result is the full
    gathered block a device materializes — i.e. the bytes it receives).
    A ring all-reduce moves ~2x its payload per link, accounted via the
    ``weighted`` field.
    """
    out = {k: 0.0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    line_re = re.compile(
        r"=\s+(\(?[\w\[\]{},*/ ]*?\)?)\s+(all-gather|all-reduce|"
        r"reduce-scatter|all-to-all|collective-permute)(-start)?\((.*)")

    def _bytes(types: str) -> float:
        total = 0.0
        for dt, dims in shape_re.findall(types):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        return total

    for line in hlo_text.splitlines():
        m = line_re.search(line)
        if not m:
            continue
        result_types, kind, operands = m.group(1), m.group(2), m.group(4)
        # per-device link traffic ~= the FULL (unsharded) payload a device
        # touches: result side for all-gather/all-reduce (gathered block),
        # OPERAND side for reduce-scatter (the result is 1/n of the payload
        # but each device still streams the whole input around the ring).
        if kind == "reduce-scatter":
            total = _bytes(operands)
        else:
            total = _bytes(result_types)
        out[kind] += total
        count[kind] += 1
    # effective per-link traffic: ring AR sends ~2x payload
    out["weighted"] = (2.0 * out["all-reduce"] + out["all-gather"]
                       + out["reduce-scatter"] + out["all-to-all"]
                       + out["collective-permute"])
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["counts"] = count
    return out


def choose_tp_fold(cfg: ArchConfig, shape: ShapeConfig,
                   devices: int = 256) -> bool:
    """TP-fold policy (§Perf iteration 1): a model whose parameters fit a
    single chip many times over pays per-layer TP collectives for nothing —
    fold the 'model' axis into data parallelism for small non-MoE models in
    training.  (MoE keeps TP/EP; decode keeps TP for KV sharding.)

    Guard: folding turns every chip into a DP rank, so the global batch
    must still divide the device count (multi-pod 512 > batch 256 -> keep
    TP)."""
    if shape.kind != "train" or cfg.family == "moe":
        return False
    if shape.global_batch % devices:
        return False
    from repro.launch.model_flops import param_count
    return param_count(cfg) * 2 < 1e9        # < 1 GB of bf16 params


def _strip_model(tree):
    """Replace the 'model' axis with None in every PartitionSpec leaf."""
    def fix(s):
        return P(*(None if a == "model" else a for a in s))
    return jax.tree.map(fix, tree, is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; weak-type-correct, no allocation)
# ----------------------------------------------------------------------------
def input_specs(cfg: ArchConfig, shape: ShapeConfig, mesh,
                tp_fold: bool | None = None, fsdp: bool = False):
    """-> (abstract args tuple, in_shardings tuple) for the step function.

    ``fsdp``: ZeRO-3 — PARAMETERS (not just optimizer state) are sharded
    over the data axes on a leading divisible dim; XLA all-gathers each
    layer's weights on use and the gradient all-reduce becomes a
    reduce-scatter.  Required for yi-34b-class models to fit 16 GB HBM."""
    if tp_fold is None:
        tp_fold = choose_tp_fold(cfg, shape, int(mesh.devices.size))
    dp = dp_axes_of(mesh) + (("model",) if tp_fold else ())
    dps = dp if len(dp) > 1 else dp[0]
    tp = 1 if tp_fold else mesh.shape["model"]
    ns = lambda spec: NamedSharding(mesh, spec)

    pspecs = T.param_specs(cfg, tp)
    if tp_fold:
        pspecs = _strip_model(pspecs)
    aparams = T.abstract_params(cfg)
    psh = jax.tree.map(lambda s: ns(s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "train":
        bspecs = make_batch_specs(cfg, shape, dp)
        batch = {k: v[0] for k, v in bspecs.items()}
        bsh = {k: ns(v[1]) for k, v in bspecs.items()}
        opt = AdamW()
        astate = jax.eval_shape(opt.init, aparams)
        # ZeRO-style optimizer-state sharding: add DP over the leading
        # (layer-stack / vocab) axis on top of the param spec.
        dp_total = mesh.devices.size // tp

        def zero_spec(spec, leaf):
            parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
            for i, (p_, dim) in enumerate(zip(parts, leaf.shape)):
                if p_ is None and dim % dp_total == 0 and dim >= dp_total:
                    parts[i] = dps
                    break
            return P(*parts)
        mv_sh = jax.tree.map(
            lambda s, l: ns(zero_spec(s, l)), pspecs, aparams,
            is_leaf=lambda x: isinstance(x, P))
        opt_sh = type(astate)(step=ns(P()), m=mv_sh, v=mv_sh)
        if fsdp:
            psh = mv_sh        # ZeRO-3: params take the dp-sharded specs
        residual = jnp.zeros(())
        args = ((aparams, astate, jax.ShapeDtypeStruct((), jnp.float32)),
                batch)
        shardings = ((psh, opt_sh, ns(P())), bsh)
        return args, shardings

    if shape.kind == "prefill":
        bspecs = make_batch_specs(cfg, shape, dp)
        bspecs.pop("labels")
        if cfg.embedding_input:
            arg = bspecs["embeds"]
        else:
            arg = bspecs["tokens"]
        return (aparams, arg[0]), (psh, ns(arg[1]))

    # decode
    acache = T.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cspecs = T.cache_specs(cfg, shape.global_batch, dp, tp)
    if isinstance(cspecs, list):
        csh = [jax.tree.map(lambda s: ns(s), c,
                            is_leaf=lambda x: isinstance(x, P))
               for c in cspecs]
    else:
        csh = jax.tree.map(lambda s: ns(s), cspecs,
                           is_leaf=lambda x: isinstance(x, P))
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    tok_sh = ns(P(dps, None)) if shape.global_batch >= mesh.devices.size // tp \
        else ns(P(None, None))
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return (aparams, acache, tok, pos), (psh, csh, tok_sh, ns(P()))


def pick_microbatches(global_batch: int, dp_size: int, seq: int,
                      target_tokens: int = 8192) -> int:
    """Gradient-accumulation factor: bound live activations to ~target
    tokens per device per microbatch (must divide the global batch)."""
    b_local = max(1, global_batch // dp_size)
    want = max(1, (b_local * seq) // target_tokens)
    m = min(want, b_local)
    while global_batch % m or (global_batch // m) % dp_size:
        m -= 1
    return max(m, 1)


def step_callable(cfg: ArchConfig, shape: ShapeConfig, mesh,
                  force_m1: bool = False, tp_fold: bool | None = None,
                  force_m: int | None = None):
    if tp_fold is None:
        tp_fold = choose_tp_fold(cfg, shape, int(mesh.devices.size))
    dp = dp_axes_of(mesh) + (("model",) if tp_fold else ())
    ctx = ShardCtx(mesh=mesh, dp_axes=dp,
                   tp_axis=None if tp_fold else "model")
    if shape.kind == "train":
        opt = AdamW()
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        if force_m1:
            m = 1
        elif force_m:
            m = force_m
        else:
            m = pick_microbatches(shape.global_batch, dp_size, shape.seq_len)
        fn = make_train_step(cfg, ctx, opt, num_microbatches=m)
        return fn
    if shape.kind == "prefill":
        def prefill_fn(params, x):
            if cfg.embedding_input:
                return T.prefill(params, cfg, ctx, embeds=x)
            return T.prefill(params, cfg, ctx, tokens=x)
        return prefill_fn

    def decode_fn(params, cache, tok, pos):
        return T.decode_step(params, cache, tok, pos, cfg, ctx)
    return decode_fn


def _compile_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
                  force_m1: bool = False, force_m: int | None = None,
                  fsdp: bool = False) -> dict:
    """Lower + compile one cell; return raw HLO-derived numbers."""
    t0 = time.perf_counter()
    args, shardings = input_specs(cfg, shape, mesh, fsdp=fsdp)
    fn = step_callable(cfg, shape, mesh, force_m1=force_m1, force_m=force_m)
    # donate the mutable state: train state (params/opt) and decode cache —
    # XLA aliases the buffers so cache/param updates happen in place
    # (§Perf decode iteration 2: an undonated KV cache costs a full
    # read+write copy of the cache per token)
    donate = (0,) if shape.kind == "train" else \
        ((1,) if shape.kind == "decode" else ())
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=shardings,
                          donate_argnums=donate).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    return {
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": float(cost.get("flops", -1.0)),
        "bytes_accessed": float(cost.get("bytes accessed", -1.0)),
        "collective_bytes": collective_bytes_from_hlo(hlo),
        "memory_analysis": _mem_dict(compiled.memory_analysis()),
        "hlo_bytes": len(hlo),
    }


def jaxpr_flops_cell(cfg: ArchConfig, shape: ShapeConfig, mesh) -> float:
    """Exact global FLOPs of the cell's step (loop-aware jaxpr walk)."""
    from repro.launch.flops import flops_of_callable
    args, _ = input_specs(cfg, shape, mesh)
    fn = step_callable(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        return flops_of_callable(fn, *args)


def _extrapolate(r1: dict, r2: dict, L: int) -> dict:
    """XLA's cost_analysis counts a while-loop (layer scan) body ONCE.

    The stack is layer-uniform, so HLO terms are affine in L:
    T(L) = T(1) + (L-1) * (T(2) - T(1)).  Exact for flops/bytes/collectives
    — except when XLA fuses/CSEs the 1- and 2-layer modules differently,
    which can make the slope negative; clamp each term to the max of the
    single-compile values (a safe lower bound) in that case.
    """
    def lin(a, b):
        v = a + (L - 1) * (b - a)
        return v if v >= max(a, b) else max(a, b)

    out = {}
    for k in ("flops", "bytes_accessed"):
        out[k] = lin(r1[k], r2[k])
    c1, c2 = r1["collective_bytes"], r2["collective_bytes"]
    coll = {}
    for k in list(_COLLECTIVES) + ["total", "weighted"]:
        coll[k] = lin(c1[k], c2[k])
    out["collective_bytes"] = coll
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, tp_size: int = 16,
             save_coll: bool = False, force_m: int | None = None,
             variant: str = "", kv_int8: bool = False,
             fsdp: bool = False) -> dict:
    cfg = get_arch(arch)
    if save_coll:
        cfg = cfg.scaled(remat_save_collectives=True)
    if kv_int8:
        cfg = cfg.scaled(kv_cache_dtype="int8")
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod, tp_size=tp_size)
    dp_ = 256 // tp_size
    mesh_tag = (f"2x{dp_}x{tp_size}" if multi_pod else f"{dp_}x{tp_size}")
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": mesh_tag,
        "variant": variant,
        "devices": int(mesh.devices.size),
    }
    if not shape_applicable(cfg, shape):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch at 524k decode "
                         "(needs sub-quadratic attention; DESIGN.md §6)")
        return rec

    try:
        full = _compile_cell(cfg, shape, mesh, force_m=force_m, fsdp=fsdp)
        rec.update(full)
        rec["status"] = "ok"
        rec["jaxpr_flops_global"] = jaxpr_flops_cell(cfg, shape, mesh)
        # scan-body linearization (hybrid decode is an unrolled loop: exact).
        # Accounting compiles run with microbatching OFF: per-step totals of
        # flops/bytes/collectives are schedule-invariant, and M=1 keeps them
        # outside any loop body XLA would count once.
        if not (cfg.family == "hybrid" and shape.kind == "decode"):
            r1 = _compile_cell(cfg.scaled(num_layers=1), shape, mesh,
                               force_m1=True, fsdp=fsdp)
            r2 = _compile_cell(cfg.scaled(num_layers=2), shape, mesh,
                               force_m1=True, fsdp=fsdp)
            rec["extrapolated"] = _extrapolate(r1, r2, cfg.num_layers)
        else:
            rec["extrapolated"] = {
                "flops": full["flops"],
                "bytes_accessed": full["bytes_accessed"],
                "collective_bytes": full["collective_bytes"],
            }
        if verbose:
            e = rec["extrapolated"]
            print(f"[ok] {arch} x {shape_name} x {rec['mesh']}  "
                  f"flops={e['flops']:.3e} bytes={e['bytes_accessed']:.3e} "
                  f"coll={e['collective_bytes']['weighted']:.3e}  "
                  f"(compile {full['compile_s']:.1f}s)")
            print("   memory:", rec["memory_analysis"])
    except Exception as e:          # a failing cell is a bug; record it
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[ERROR] {arch} x {shape_name} x {rec['mesh']}: "
                  f"{rec['error']}")
    return rec


def _mem_dict(mem):
    if mem is None:
        return None
    out = {}
    for attr in ("generated_code_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "temp_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = int(getattr(mem, attr))
    return out or str(mem)


def save_record(rec: dict):
    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = f"__{rec['variant']}" if rec.get("variant") else ""
    name = (f"dryrun_{rec['mesh'].replace('x', '_')}_{rec['arch']}_"
            f"{rec['shape']}{suffix}.json")
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tp", type=int, default=16,
                    help="TP degree (256/tp becomes DP) — §Perf variants")
    ap.add_argument("--save-coll", action="store_true",
                    help="remat policy: save post-psum activations")
    ap.add_argument("--force-m", type=int, default=None,
                    help="override gradient-accumulation factor")
    ap.add_argument("--variant", default="",
                    help="tag for the results file (perf experiments)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8 KV cache (decode shapes)")
    ap.add_argument("--fsdp", action="store_true",
                    help="ZeRO-3: shard PARAMS over dp (fit-HBM variant)")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}
    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                for mp in meshes[args.mesh]:
                    cells.append((a, s, mp))
    else:
        assert args.arch and args.shape
        for mp in meshes[args.mesh]:
            cells.append((args.arch, args.shape, mp))

    failures = 0
    for a, s, mp in cells:
        if args.skip_existing:
            mesh_tag = "2_16_16" if mp else "16_16"
            p = os.path.join(RESULTS_DIR, f"dryrun_{mesh_tag}_{a}_{s}.json")
            if os.path.exists(p):
                with open(p) as f:
                    if json.load(f).get("status") in ("ok", "skipped"):
                        continue
        rec = run_cell(a, s, mp, tp_size=args.tp, save_coll=args.save_coll,
                       force_m=args.force_m, variant=args.variant,
                       kv_int8=args.kv_int8, fsdp=args.fsdp)
        save_record(rec)
        failures += rec["status"] == "error"
    print(f"done; {failures} failing cells")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    force_fake_devices()
    main()
