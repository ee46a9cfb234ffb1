import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^^^ MUST run before any jax import: jax locks the device count on first
# initialisation.  Everything below (including `from repro...`) may import
# jax freely.
#
# Multi-pod dry-run: AOT lower + compile every (architecture x input-shape x
# mesh) cell against the production meshes, print memory_analysis (fits) and
# cost_analysis (FLOPs/bytes for §Roofline), parse collective bytes from the
# optimized HLO, and append everything to a JSON results file.
#
# Usage:
#   python -m repro.launch.dryrun --arch gemma2-2b --shape train_4k
#   python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.hlo import collective_bytes
from repro.configs import ARCH_CONFIGS, ASSIGNED_ARCHS, SHAPES
from repro.configs.base import (ModelConfig, ShapeSpec, input_specs,
                                shape_applicable)
from repro.core import energy
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.models.modules import unroll_mode
from repro.sharding.partition import (logical_to_spec, param_shardings,
                                      resolve_rules, rules_context)
from repro.training.step import (TrainPlan, init_train_state,
                                 make_decode_step, make_train_step)


def _abstract_model(cfg: ModelConfig, mesh, dtype=None, quantize=False):
    """(ShapeDtypeStructs-with-sharding, axes) for the model params —
    no allocation (init traced under eval_shape).  quantize=True builds the
    W8 serve tree ({"q": int8, "s": scale} leaves)."""
    captured = {}

    def f(k):
        p, a = T.init_model(cfg, k)
        if quantize:
            p, a = T.quantize_model_params(p, a, cfg)
        captured["axes"] = a
        return p

    shapes = jax.eval_shape(f, jax.random.key(0))
    axes = captured["axes"]
    shardings = param_shardings(axes, mesh, cfg.sharding_overrides, shapes)

    def mk(s, sh):
        dt = dtype if (dtype is not None and
                       jnp.issubdtype(s.dtype, jnp.floating)) else s.dtype
        return jax.ShapeDtypeStruct(s.shape, dt, sharding=sh)

    structs = jax.tree.map(mk, shapes, shardings)
    return structs, axes, shardings


def _batch_structs(cfg: ModelConfig, shape: ShapeSpec, mesh, overrides=None):
    rules = resolve_rules(mesh, cfg.sharding_overrides
                          if overrides is None else overrides)
    out = {}
    for name, (shp, dt, laxes) in input_specs(cfg, shape).items():
        sh = NamedSharding(mesh, logical_to_spec(laxes, rules, shp, mesh))
        out[name] = jax.ShapeDtypeStruct(shp, dt, sharding=sh)
    return out


def _microbatches_for(cfg: ModelConfig, shape: ShapeSpec, mesh) -> int:
    """Grad-accum count: keep per-device per-microbatch tokens bounded so
    activations (even with full remat the residual-stream checkpoints scale
    with d_model * layers) fit HBM — larger models get more microbatches."""
    dp = 1
    for ax in ("pod", "data"):
        if ax in mesh.axis_names:
            dp *= mesh.shape[ax]
    per_dev_batch = max(1, shape.global_batch // dp)
    target_tokens = 16384 if cfg.d_model < 4096 else 8192
    nm = max(shape.microbatches,
             (per_dev_batch * shape.seq_len + target_tokens - 1) // target_tokens)
    nm = min(nm, per_dev_batch)
    while per_dev_batch % nm and nm > 1:
        nm -= 1
    return nm


def serve_overrides(cfg):
    """(§Perf iteration 4 — REFUTED, kept for the record/tests.)
    TP-only serve weights: replicating the FSDP ('embed'->data) dim was
    hypothesised to remove serve-time gathers; measurement showed the
    d-sharded layout is beneficial 2D weight-parallelism at decode, and the
    16x weight replication pushes mixtral/phi3.5 prefill over HBM.  Serving
    therefore keeps the training sharding (see EXPERIMENTS.md §Perf)."""
    return tuple(cfg.sharding_overrides) + (("embed", None),)


def _lower_step(cfg, shape, mesh, quant_serve):
    """Build step fn + abstract args, return jax.jit(...).lower(...)."""
    extra = {}
    if shape.kind == "train":
        nm = _microbatches_for(cfg, shape, mesh)
        plan = TrainPlan(microbatches=nm)
        params, axes, shardings = _abstract_model(cfg, mesh)
        state_struct = jax.eval_shape(
            lambda p: init_train_state(p, plan), params)
        state_shard = {
            "params": shardings,
            "opt": {"mu": shardings, "nu": shardings,
                    "count": NamedSharding(mesh, P())},
            "step": NamedSharding(mesh, P()),
        }
        state_struct = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state_struct, state_shard)
        batch = _batch_structs(cfg, shape, mesh)
        step = make_train_step(cfg, plan)
        lowered = jax.jit(step, donate_argnums=0).lower(state_struct, batch)
        extra["microbatches"] = nm
    elif shape.kind == "prefill":
        params, axes, shardings = _abstract_model(cfg, mesh, jnp.bfloat16)
        batch = _batch_structs(cfg, shape, mesh)
        from repro.training.step import make_prefill_step
        lowered = jax.jit(make_prefill_step(cfg)).lower(params, batch)
    else:  # decode
        # NOTE (§Perf iteration 4, REFUTED then revised): dropping the FSDP
        # 'embed'->data rule for decode was hypothesised to kill per-step
        # weight gathers; measurement showed the d-sharded weights actually
        # act as beneficial 2D weight-parallelism at decode (weights stay
        # put, tiny activation reduces move) — replication regressed 9/11
        # decode cells up to 7x.  Decode therefore KEEPS the training
        # sharding; prefill (weight reads amortised over 32k tokens) keeps
        # the TP-only override.
        ov = tuple(cfg.sharding_overrides)
        if quant_serve:  # C1 at LM scale: int8 weights + int8 KV cache
            from repro.core.quant import QuantConfig
            scfg = cfg.replace(quant=QuantConfig("w8", quantize_kv=True))
        else:
            scfg = cfg
        params, axes, shardings = _abstract_model(
            scfg, mesh, jnp.bfloat16, quantize=scfg.quant.enabled)
        rules = resolve_rules(mesh, ov)
        cache_struct = {
            k: jax.ShapeDtypeStruct(
                shp, dt, sharding=NamedSharding(
                    mesh, logical_to_spec(laxes, rules, shp, mesh)))
            for k, (shp, dt, laxes) in
            T.cache_spec(scfg, shape.global_batch, shape.seq_len).items()}
        batch = _batch_structs(scfg, shape, mesh, ov)
        step = make_decode_step(scfg)
        lowered = jax.jit(step, donate_argnums=1).lower(
            params, cache_struct, batch)
    return lowered, extra


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             quant_serve: bool = False, skip_cost_pass: bool = False) -> dict:
    cfg: ModelConfig = ARCH_CONFIGS[arch]
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
           "chips": chips, "kind": shape.kind}
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    t0 = time.time()
    with rules_context(mesh, cfg.sharding_overrides):
        lowered, extra = _lower_step(cfg, shape, mesh, quant_serve)
        rec.update(extra)
        compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 1)

    # Cost-exact pass: HloCostAnalysis counts while bodies ONCE, so FLOP
    # accounting re-lowers (no backend compile) with every model loop
    # unrolled.  The UNCOMPILED module is the GLOBAL program (SPMD
    # partitioning happens at compile), so per-device = global / chips.
    if skip_cost_pass:
        ca = compiled.cost_analysis() or {}
        rec["flops_per_device"] = float(ca.get("flops", 0.0))
    else:
        t1 = time.time()
        with rules_context(mesh, cfg.sharding_overrides), unroll_mode():
            lowered_cost, _ = _lower_step(cfg, shape, mesh, quant_serve)
        ca = lowered_cost.cost_analysis() or {}
        rec["cost_lower_s"] = round(time.time() - t1, 1)
        ca_scan = compiled.cost_analysis() or {}
        rec["flops_per_device_scanned_hlo"] = float(ca_scan.get("flops", 0.0))
        rec["flops_global"] = float(ca.get("flops", 0.0))
        rec["flops_per_device"] = rec["flops_global"] / chips
    # 'bytes accessed' on unoptimised HLO counts every op's operands (no
    # fusion) — recorded for reference only; the roofline memory term uses
    # the traffic estimator below (EXPERIMENTS.md §Roofline documents this).
    rec["bytes_unfused_global"] = float(ca.get("bytes accessed", 0.0))
    rec["transcendentals"] = float(ca.get("transcendentals", 0.0))
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gb": ma.argument_size_in_bytes / 2**30,
            "output_gb": ma.output_size_in_bytes / 2**30,
            "temp_gb": ma.temp_size_in_bytes / 2**30,
            "alias_gb": ma.alias_size_in_bytes / 2**30,
            "peak_gb": (ma.argument_size_in_bytes + ma.output_size_in_bytes +
                        ma.temp_size_in_bytes - ma.alias_size_in_bytes) / 2**30,
        }
    except Exception as e:  # pragma: no cover
        rec["memory"] = {"error": str(e)}
    colls = collective_bytes(compiled.as_text())
    rec["collectives"] = colls

    # HBM-traffic estimator (per device), from the compiled memory_analysis:
    #   train:   read+write the state (params fp32 + adam moments) once,
    #            re-read bf16 weights fwd+bwd per microbatch (FSDP-gathered
    #            copies land in HBM), stream activations (~temp) twice per
    #            microbatch.
    #   prefill: read args once + 2x transient activations.
    #   decode:  read weights + KV cache once (the classic decode bound)
    #            + 2x transients.
    mem = rec.get("memory", {})
    arg_b = mem.get("argument_gb", 0.0) * 2**30
    tmp_b = mem.get("temp_gb", 0.0) * 2**30
    n_params = T.num_params(cfg)
    if shape.kind == "train":
        nm = rec.get("microbatches", 1)
        wb = 2 * n_params / chips          # bf16 weight copy per device
        hbm = 2 * arg_b + nm * 2 * wb + nm * 2 * tmp_b
    else:
        hbm = arg_b + 2 * tmp_b
    rec["hbm_bytes_per_device_est"] = hbm

    terms = energy.roofline_terms(rec["flops_per_device"], hbm,
                                  colls.get("total", 0.0))
    rec["roofline"] = terms.asdict()

    # MODEL_FLOPS (useful-compute ratio)
    n = T.num_params(cfg)
    n_act = T.num_active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mf = energy.model_flops_train(n, tokens, n_act)
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mf = energy.model_flops_decode(n, tokens, n_act) / 2 * 2  # fwd only
    else:
        mf = energy.model_flops_decode(n, shape.global_batch, n_act)
    rec["model_flops_total"] = mf
    hlo_total = rec["flops_per_device"] * chips
    rec["useful_flops_ratio"] = (mf / hlo_total) if hlo_total else None
    rec["params"] = n
    rec["active_params"] = n_act
    rec["status"] = "ok"
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant-serve", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    cells = []
    archs = ASSIGNED_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"], r.get("quant", False))
            for r in results}

    for a, s, mp in cells:
        mesh_name = "2x16x16" if mp else "16x16"
        key = (a, s, mesh_name, args.quant_serve)
        if key in done:
            print(f"[skip-cached] {key}", flush=True)
            continue
        print(f"[run] {a} x {s} x {mesh_name}", flush=True)
        try:
            rec = run_cell(a, s, mp, args.quant_serve)
        except Exception as e:
            rec = {"arch": a, "shape": s, "mesh": mesh_name,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
        rec["quant"] = args.quant_serve
        results.append(rec)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"  -> {rec.get('status')} "
              f"compile={rec.get('compile_s', '-')}s "
              f"flops/dev={rec.get('flops_per_device', 0):.3g} "
              f"bound={rec.get('roofline', {}).get('bound', '-')}", flush=True)

    bad = [r for r in results if r.get("status") == "error"]
    print(f"\n{len(results)} cells recorded, {len(bad)} errors")
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
