import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax-importing import: jax locks the device count on
# first init.  512 placeholder host devices back the production meshes
# (16x16 single-pod, 2x16x16 multi-pod).  Set here and ONLY here — smoke
# tests and benchmarks see the real 1-CPU platform.

__doc__ = """Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell:
  lower the step function with production in_shardings,
  compile it (proves the distribution config is coherent: no sharding
  mismatches, no unsupported collectives, no compile-time OOM),
  record memory_analysis / cost_analysis / per-collective bytes
  -> JSON under results/dryrun/.

Usage:
  python -m repro.launch.dryrun --arch grok_1_314b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import (ARCH_NAMES, abstract_params, cell_supported,
                       get_config, input_specs)
from ..models.common import SHAPES, ArchConfig, ShapeConfig
from ..roofline import collective_bytes_from_hlo, model_flops, roofline_terms
from ..sharding import batch_pspecs, cache_pspecs, param_pspecs
from ..sharding.rules import opt_pspecs
from ..train.steps import (TrainState, make_decode_step, make_prefill_step,
                           make_train_step, train_state_init)
from .mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, make_production_mesh

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

NUM_MICROBATCHES = 8   # train_4k: 256-batch -> 8 x 32 (bounds logits memory)


def _spec_to_shardings(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        tree, is_leaf=lambda x: isinstance(x, P))


def _arg_bytes_per_device(mesh, abstract_trees, spec_trees) -> int:
    """Analytic per-device bytes of the inputs under their PartitionSpecs.

    memory_analysis() reports global-unique bytes, which hides the cost of
    REPLICATED tensors; this accounts a replicated leaf once per device."""
    total = 0
    for abs_t, spec_t in zip(abstract_trees, spec_trees):
        leaves = jax.tree.leaves(abs_t)
        specs = jax.tree.leaves(spec_t, is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(leaves, specs):
            shards = 1
            for entry in (spec or ()):  # type: ignore[union-attr]
                if entry is None:
                    continue
                names = entry if isinstance(entry, tuple) else (entry,)
                for a in names:
                    shards *= mesh.shape.get(a, 1)
            nbytes = int(jnp.dtype(leaf.dtype).itemsize)
            for d in leaf.shape:
                nbytes *= d
            total += nbytes // max(shards, 1)
    return total


@dataclasses.dataclass
class Variant:
    """A §Perf hillclimbing variant: sharding profile + config tweaks."""

    name: str = "baseline"
    profile_name: str = "baseline"
    replicate_params: bool = False     # dp_all: replicate params, ZeRO opt
    batch_axes: Any = None             # e.g. ("data", "model") for dp_all
    derived_mesh: bool = False         # ep: reshape to (data, expert, tp)
    cfg_overrides: Dict[str, Any] = dataclasses.field(default_factory=dict)
    remat: bool = True
    num_microbatches: Optional[int] = None


def make_variant(spec: str) -> Variant:
    v = Variant(name=spec)
    for part in spec.split("+"):
        if part in ("", "baseline"):
            continue
        if part == "dp_all":
            v.profile_name = "dp_all"
            v.replicate_params = True
            v.batch_axes = ("data", "model")
        elif part == "sp":
            v.profile_name = "sp"
        elif part == "ep":
            v.profile_name = "ep"
            v.derived_mesh = True
        elif part.startswith("chunk"):
            v.cfg_overrides["ssm_chunk"] = int(part[5:])
        elif part == "noremat":
            v.remat = False
        elif part.startswith("nm"):
            v.num_microbatches = int(part[2:])
        elif part == "pin":
            pass   # moe-buffer pinning (behaviour lives in sharding/ctx)
        elif part.startswith("cf"):
            v.cfg_overrides["capacity_factor"] = float(part[2:])
        else:
            raise ValueError(f"unknown variant part {part!r}")
    return v


def variant_mesh(mesh, variant: Variant):
    if not variant.derived_mesh:
        return mesh
    devs = mesh.devices
    if devs.ndim == 2:          # (data, model) -> (data, expert, tp)
        d0, d1 = devs.shape
        assert d1 % 8 == 0
        return jax.sharding.Mesh(devs.reshape(d0, 8, d1 // 8),
                                 ("data", "expert", "tp"))
    raise ValueError("ep variant is single-pod only (the roofline mesh)")


def _profile_for(variant: Variant, mesh):
    from ..sharding.ctx import ShardProfile
    if variant.profile_name == "baseline":
        return None
    if variant.profile_name == "ep":
        return ShardProfile(name="ep", mesh=mesh, data_axes=("data",),
                            tp_axes=("expert", "tp"), expert_axis="expert")
    return ShardProfile(name=variant.profile_name, mesh=mesh,
                        data_axes=tuple(a for a in ("pod", "data")
                                        if a in mesh.axis_names),
                        tp_axes=("model",))


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *,
               cost_pass: bool = False, variant: Optional[Variant] = None):
    """Build + lower the cell's step function.  Returns (lowered, meta).

    Two flavours:
      * production pass (default): layers scanned, train microbatched —
        what would really run; used for memory_analysis + compile proof.
      * cost pass: layers UNROLLED and a single microbatch — XLA's
        HloCostAnalysis counts while-bodies once, so only the unrolled
        program yields true FLOPs/bytes/collective bytes.  Train totals are
        then scaled by num_microbatches.
    """
    import contextlib

    from ..models.model import unrolled_layers
    from ..sharding.ctx import use_profile
    from ..sharding.rules import replicated_pspecs, zero_opt_pspecs

    variant = variant or Variant()
    cfg = dataclasses.replace(cfg, **variant.cfg_overrides) \
        if variant.cfg_overrides else cfg
    mesh = variant_mesh(mesh, variant)
    profile = _profile_for(variant, mesh)

    decisions: list = []
    params_abs = abstract_params(cfg)
    if variant.replicate_params:
        pspecs = replicated_pspecs(params_abs)
        decisions = ["dp_all: params replicated; opt ZeRO-sharded"]
    elif variant.profile_name == "ep":
        pspecs, decisions = param_pspecs(cfg, params_abs, mesh,
                                         tp=("expert", "tp"),
                                         expert_axis="expert")
    else:
        pspecs, decisions = param_pspecs(cfg, params_abs, mesh)

    ctx = unrolled_layers(True) if cost_pass else contextlib.nullcontext()
    pctx = use_profile(profile)

    if shape.kind == "train":
        nm = variant.num_microbatches or NUM_MICROBATCHES
        if shape.global_batch % nm:
            nm = 1
        state_abs = jax.eval_shape(
            lambda: train_state_init(cfg, jax.random.PRNGKey(0)))
        if variant.replicate_params:
            ospecs = zero_opt_pspecs(state_abs.opt, mesh)
        else:
            ospecs = opt_pspecs(pspecs, state_abs.opt)
        state_specs = TrainState(params=pspecs, opt=ospecs, residual=None)
        batch_abs = input_specs(cfg, shape)
        if cost_pass:
            # one microbatch, costs scaled by nm afterwards
            batch_abs = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (s.shape[0] // nm, *s.shape[1:]), s.dtype), batch_abs)
            step = make_train_step(cfg, num_microbatches=1,
                                   remat=variant.remat)
        else:
            step = make_train_step(cfg, num_microbatches=nm,
                                   remat=variant.remat)
        bspecs = batch_pspecs(cfg, batch_abs, mesh,
                              batch_axes=variant.batch_axes)
        with jax.set_mesh(mesh), ctx, pctx:
            lowered = jax.jit(
                step,
                in_shardings=(_spec_to_shardings(mesh, state_specs),
                              _spec_to_shardings(mesh, bspecs)),
                donate_argnums=(0,),
            ).lower(state_abs, batch_abs)
        return lowered, {"num_microbatches": nm, "decisions": decisions,
                         "cost_scale": nm if cost_pass else 1,
                         "arg_bytes_per_device": _arg_bytes_per_device(
                             mesh, (state_abs, batch_abs),
                             (state_specs, bspecs))}

    if shape.kind == "prefill":
        step = make_prefill_step(cfg)
        batch_abs = input_specs(cfg, shape)
        bspecs = batch_pspecs(cfg, batch_abs, mesh,
                              batch_axes=variant.batch_axes)
        with jax.set_mesh(mesh), ctx, pctx:
            lowered = jax.jit(
                step,
                in_shardings=(_spec_to_shardings(mesh, pspecs),
                              _spec_to_shardings(mesh, bspecs)),
            ).lower(params_abs, batch_abs)
        return lowered, {"decisions": decisions, "cost_scale": 1,
                         "arg_bytes_per_device": _arg_bytes_per_device(
                             mesh, (params_abs, batch_abs),
                             (pspecs, bspecs))}

    # decode
    step = make_decode_step(cfg)
    specs = input_specs(cfg, shape)
    cache_abs = specs["cache"]
    cspecs = cache_pspecs(cfg, cache_abs, mesh)
    tok_spec = batch_pspecs(cfg, {"tokens": specs["tokens"]}, mesh,
                            batch_axes=variant.batch_axes)["tokens"]
    with jax.set_mesh(mesh), ctx, pctx:
        lowered = jax.jit(
            step,
            in_shardings=(_spec_to_shardings(mesh, pspecs),
                          _spec_to_shardings(mesh, cspecs),
                          NamedSharding(mesh, tok_spec),
                          NamedSharding(mesh, P())),
            donate_argnums=(1,),
        ).lower(params_abs, cache_abs, specs["tokens"], specs["pos"])
    return lowered, {"decisions": decisions, "cost_scale": 1,
                     "arg_bytes_per_device": _arg_bytes_per_device(
                         mesh, (params_abs, cache_abs),
                         (pspecs, cspecs))}


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Path = RESULTS_DIR, verbose: bool = True,
             variant: str = "baseline") -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    var = make_variant(variant)
    mesh_name = "multi" if multi_pod else "single"
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "variant": variant,
        "family": cfg.family, "kind": shape.kind,
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    skip = cell_supported(cfg, shape)
    out_dir.mkdir(parents=True, exist_ok=True)
    suffix = "" if variant == "baseline" else f"__{variant}"
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
    if skip:
        rec["status"] = "skipped"
        rec["skip_reason"] = skip
        out_path.write_text(json.dumps(rec, indent=1))
        if verbose:
            print(f"[dryrun] SKIP {arch} x {shape_name} x {mesh_name}: "
                  f"{skip}", flush=True)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec["chips"] = chips
    try:
        # ---- pass 1: production program (scan + microbatches) --------------
        t0 = time.monotonic()
        lowered, meta = lower_cell(cfg, shape, mesh, variant=var)
        rec.update({k: v for k, v in meta.items() if k != "cost_scale"})
        rec["lower_s"] = round(time.monotonic() - t0, 2)
        t1 = time.monotonic()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.monotonic() - t1, 2)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k))
            for k in ("generated_code_size_in_bytes",
                      "argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes")
            if hasattr(mem, k)}
        args_b = rec["memory"].get("argument_size_in_bytes", 0)
        temp_b = rec["memory"].get("temp_size_in_bytes", 0)
        out_b = rec["memory"].get("output_size_in_bytes", 0)
        alias_b = rec["memory"].get("alias_size_in_bytes", 0)
        analytic_args = meta.get("arg_bytes_per_device", 0)
        rec["memory"]["per_device_bytes"] = int(
            analytic_args + (temp_b + max(out_b - alias_b, 0)) /
            max(chips, 1))
        rec["memory"]["arg_bytes_per_device"] = int(analytic_args)
        del compiled, lowered

        # ---- pass 2: cost program (unrolled layers, single microbatch) -----
        # XLA counts while-bodies once, so costs come from UNROLLED programs.
        # Unrolling the full depth is too slow; costs are exactly linear in
        # depth (identical layers), so we unroll L1 and L2 layers and
        # extrapolate: cost(L) = cost(L1) + (L-L1)*(cost(L2)-cost(L1))/(L2-L1)
        t2 = time.monotonic()
        per = max(cfg.shared_attn_period, 1)
        L1, L2 = (per, 2 * per) if cfg.family == "hybrid" else (2, 4)

        def reduced(L: int) -> ArchConfig:
            kw: Dict[str, Any] = {"num_layers": L}
            if cfg.family == "encdec":
                kw["num_encoder_layers"] = L
            return dataclasses.replace(cfg, **kw)

        def measure(c: ArchConfig) -> Dict[str, float]:
            lowered_c, meta_c = lower_cell(c, shape, mesh, cost_pass=True,
                                           variant=var)
            compiled_c = lowered_c.compile()
            scale = meta_c["cost_scale"]
            cost = compiled_c.cost_analysis()
            if isinstance(cost, list):
                cost = cost[0] if cost else {}
            out = {"flops": float(cost.get("flops", 0.0)) * scale,
                   "bytes_accessed":
                       float(cost.get("bytes accessed", 0.0)) * scale}
            coll = collective_bytes_from_hlo(compiled_c.as_text())
            for k, v in coll.items():
                out[f"coll_{k}"] = v * scale
            return out

        m1, m2 = measure(reduced(L1)), measure(reduced(L2))
        L = cfg.num_layers
        ex = {k: m1[k] + (L - L1) * (m2[k] - m1[k]) / (L2 - L1)
              for k in m1}
        rec["cost_pass_s"] = round(time.monotonic() - t2, 2)
        flops = ex["flops"]
        bytes_accessed = ex["bytes_accessed"]
        rec["cost"] = {"flops": flops, "bytes_accessed": bytes_accessed,
                       "extrapolated_from": [L1, L2]}
        coll = {k[5:]: v for k, v in ex.items() if k.startswith("coll_")}
        rec["collectives"] = coll

        # cost_analysis on the CPU backend reports per-partition (per-device)
        # numbers for SPMD programs; normalise to GLOBAL totals.
        global_flops = flops * chips
        global_bytes = bytes_accessed * chips
        coll_global = coll["total"] * chips
        terms = roofline_terms(global_flops, global_bytes, coll_global,
                               chips, PEAK_FLOPS_BF16, HBM_BW, ICI_BW)
        mf = model_flops(cfg, shape)
        terms["model_flops"] = mf
        terms["useful_fraction"] = (mf / global_flops) if global_flops else 0.0
        rec["roofline"] = terms
        rec["status"] = "ok"
    except Exception as exc:  # noqa: BLE001 - record the failure, keep going
        rec["status"] = "error"
        rec["error"] = f"{type(exc).__name__}: {exc}"
        rec["traceback"] = traceback.format_exc(limit=10)
    out_path.write_text(json.dumps(rec, indent=1))
    if verbose:
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (f" dom={r['dominant']} "
                     f"frac={r['roofline_fraction']:.3f} "
                     f"lower={rec['lower_s']}s compile={rec['compile_s']}s")
        print(f"[dryrun] {status.upper():7s} {arch} x {shape_name} x "
              f"{mesh_name}{extra}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--variant", default="baseline",
                    help="hillclimb variant, e.g. dp_all, sp, ep, "
                         "dp_all+chunk128")
    args = ap.parse_args()

    out_dir = Path(args.out)
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    archs = ARCH_NAMES if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))
    print(f"[dryrun] {len(cells)} cells", flush=True)
    t0 = time.monotonic()
    for a, s, m in cells:
        mesh_name = "multi" if m else "single"
        sfx = "" if args.variant == "baseline" else f"__{args.variant}"
        p = out_dir / f"{a}__{s}__{mesh_name}{sfx}.json"
        if args.skip_existing and p.exists():
            try:
                if json.loads(p.read_text()).get("status") in ("ok",
                                                               "skipped"):
                    print(f"[dryrun] cached  {a} x {s} x {mesh_name}",
                          flush=True)
                    continue
            except Exception:  # noqa: BLE001
                pass
        run_cell(a, s, m, out_dir, variant=args.variant)
    print(f"[dryrun] done in {time.monotonic() - t0:.0f}s", flush=True)


if __name__ == "__main__":
    main()
