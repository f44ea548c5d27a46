import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "10")

_DOC = """Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

For each cell this proves, without hardware: (i) the sharding config is
coherent (GSPMD partitions every op), (ii) the program fits (per-device
memory analysis), and (iii) extracts the roofline terms: HLO FLOPs/bytes
from ``cost_analysis()`` and collective bytes parsed from the post-SPMD
HLO text. Artifacts land in ``artifacts/dryrun/*.json``; benchmarks/
bench_roofline.py turns them into the §Roofline table.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite_moe_1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""

# NOTE: no `from __future__` here — the XLA_FLAGS lines must be the very
# first statements (before jax locks the device count).
import argparse
import json
import time
from pathlib import Path

import jax

from ..compile_cache import enable_compile_cache
from ..configs import ARCH_IDS, get_config
from ..models import model as M
from ..models.config import SHAPES, shapes_for
from ..models.sharding_hints import use_hints
from ..optim import AdamWConfig
from . import input_specs as ispec
from . import sharding as shd
from . import steps as steps_mod
from .mesh import arch_mesh, make_production_mesh, plan_for

def _mem_dict(compiled) -> dict:
    out = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes",
                  "host_argument_size_in_bytes",
                  "peak_memory_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception as e:  # pragma: no cover
        out["error"] = str(e)
    return out


OPT_PLAN_OVERRIDES = {
    # §Perf: fewer grad-accum microbatches → FSDP param all-gathers per
    # step drop proportionally (memory headroom bought by chunked attn)
    "nemotron4_340b": 2,
    "jamba15_large_398b": 4,
}


def optimized_config(cfg):
    # dense attention stays in the graph; the flash-kernel substitution is
    # accounted via bytes_accessed_flashproj (kernels/flash_attn realizes
    # it on hardware — the lax.scan "chunked" variant was refuted, see
    # EXPERIMENTS.md §Perf iteration 1)
    import dataclasses
    return dataclasses.replace(cfg, opt_conv_split=True,
                               opt_bf16_grads=True)


def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                out_dir: Path, save_hlo: bool = False,
                opt: bool = False) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    pp = steps_mod.plan_of(arch)
    if opt:
        cfg = optimized_config(cfg)
        if arch in OPT_PLAN_OVERRIDES:
            import dataclasses as _dc
            pp = _dc.replace(pp, microbatches=OPT_PLAN_OVERRIDES[arch])
    plan = plan_for(cfg, multi_pod=multi_pod)
    base = make_production_mesh(multi_pod=multi_pod)
    mesh = arch_mesh(base, plan)
    dp = (2 if multi_pod else 1) * 16

    t0 = time.time()
    rules = shd.logical_rules(plan, pp)
    param_rules = (shd.tp_only_rules(plan)
                   if (opt and pp.fsdp and shape.kind == "train") else None)
    with mesh, use_hints(mesh, rules, param_rules):
        p_sh = shd.param_shardings(mesh, cfg, plan, pp)
        rep = shd.replicated(mesh)
        params_abs = M.abstract_params(cfg)

        if shape.kind == "train":
            mb = ispec.effective_microbatches(pp, shape, dp)
            specs = ispec.train_specs(cfg, shape, mb)
            b_sh = shd.batch_shardings(mesh, cfg, plan, shape)
            opt_cfg = AdamWConfig(m_dtype="bfloat16"
                                  if pp.fsdp else "float32")
            opt_abs = steps_mod.abstract_opt_state(cfg, opt_cfg)
            from ..optim.adamw import AdamWState
            o_sh = AdamWState(m=p_sh, v=p_sh, count=rep)
            step = steps_mod.make_train_step(cfg, opt_cfg)
            met_sh = {"loss": rep, "grad_norm": rep, "lr": rep}
            jitted = jax.jit(step,
                             in_shardings=(p_sh, o_sh, b_sh),
                             out_shardings=(p_sh, o_sh, met_sh),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_abs, opt_abs, specs)
        elif shape.kind == "prefill":
            specs = ispec.prefill_specs(cfg, shape)
            b_sh = shd.batch_shardings(mesh, cfg, plan, shape)
            c_sh = shd.cache_shardings(mesh, cfg, plan, pp, shape)
            logits_sh = shd.replicated(mesh)
            step = steps_mod.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(p_sh, b_sh),
                             out_shardings=(logits_sh, c_sh))
            lowered = jitted.lower(params_abs, specs)
        else:  # decode
            specs = ispec.decode_specs(cfg, shape)
            b_sh = shd.batch_shardings(mesh, cfg, plan, shape)
            c_sh = shd.cache_shardings(mesh, cfg, plan, pp, shape)
            step = steps_mod.make_decode_step(cfg)
            jitted = jax.jit(
                step,
                in_shardings=(p_sh, c_sh, b_sh["tokens"], rep),
                out_shardings=(rep, c_sh, rep),
                donate_argnums=(1,))
            lowered = jitted.lower(params_abs, specs["caches"],
                                   specs["tokens"], specs["index"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    from .hlo_analysis import HloAnalysis
    ana = HloAnalysis(hlo, seq_len=shape.seq_len).summary()
    mem = _mem_dict(compiled)
    rec = {
        "arch": arch,
        "variant": "opt" if opt else "baseline",
        "config_name": cfg.name,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": 512 if multi_pod else 256,
        "plan": {"tp_kv": plan.tp_kv, "tp_g": plan.tp_g, "tp_r": plan.tp_r,
                 "fsdp": pp.fsdp, "fsdp_pod": pp.fsdp_pod,
                 "microbatches": (ispec.effective_microbatches(pp, shape, dp)
                                  if shape.kind == "train" else 1)},
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        # loop-weighted, per-device (from HLO parse; see hlo_analysis.py)
        "flops": float(ana["dot_flops"]),
        "bytes_accessed": float(ana["hbm_bytes"]),
        "bytes_accessed_upper": float(ana["hbm_bytes_upper"]),
        "bytes_accessed_flashproj": float(ana["hbm_bytes_flashproj"]),
        "score_bytes": float(ana["score_bytes"]),
        "transcendentals": float(ana["transcendentals"]),
        # unweighted XLA aggregates, for reference only
        "xla_flops_unweighted": float(cost.get("flops", -1.0)),
        "xla_bytes_unweighted": float(cost.get("bytes accessed", -1.0)),
        "collectives": ana["collectives"],
        "while_trips": ana["while_trips"],
        "memory": mem,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_lines": hlo.count("\n"),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{arch}__{shape_name}__{rec['mesh'].replace('x', '_')}"
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=2))
    if save_hlo:
        (out_dir / f"{name}.hlo.txt").write_text(hlo)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized variant (chunked attention,"
                         " split SSM convs, tuned microbatching)")
    args = ap.parse_args()
    enable_compile_cache()

    out_dir = Path(args.out if not args.opt or args.out != "artifacts/dryrun"
                   else "artifacts/dryrun_opt")
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shape_names = ([args.shape] if args.shape else
                       [s.name for s in shapes_for(cfg)])
        for shape_name in shape_names:
            for mp in meshes:
                tag = f"{arch} × {shape_name} × {'2x16x16' if mp else '16x16'}"
                try:
                    rec = dryrun_cell(arch, shape_name, mp, out_dir,
                                      args.save_hlo, opt=args.opt)
                    print(f"[OK] {tag}: flops={rec['flops']:.3e} "
                          f"coll={rec['collectives']['total_bytes']:.3e}B "
                          f"compile={rec['compile_s']}s", flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e!r}", flush=True)
    if failures:
        raise SystemExit(f"{len(failures)} dry-run cells failed: "
                         + "; ".join(t for t, _ in failures))
    print("all dry-run cells compiled")


if __name__ == "__main__":
    main()
