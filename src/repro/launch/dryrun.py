"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST set the fake-device flag before ANY other import (jax locks the device
count on first init):
"""
import os  # noqa: E402
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, SHAPES, cells_for, get_config  # noqa
from repro.launch import roofline as RL                            # noqa
from repro.launch.mesh import make_production_mesh                 # noqa
from repro.launch.specs import (decode_input_specs, pick_microbatches,  # noqa
                                prefill_input_specs, train_input_specs)
from repro.models import active_param_count, param_count           # noqa
from repro.train.optimizer import OptConfig                        # noqa


def _mem_dict(compiled):
    ma = compiled.memory_analysis()
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {f: int(getattr(ma, f, 0)) for f in fields}
    out["peak_estimate_bytes"] = (out["argument_size_in_bytes"]
                                  + out["temp_size_in_bytes"]
                                  + out["output_size_in_bytes"]
                                  - out["alias_size_in_bytes"])
    return out


def build_cell(cfg, case, mesh, n_micro):
    """Returns (jitted_step, args_sds_tuple) for one cell."""
    if case.kind == "train":
        from repro.train.step import make_train_step
        step, _, _ = make_train_step(cfg, OptConfig(), mesh,
                                     num_microbatches=n_micro)
        return step, train_input_specs(cfg, case, mesh)
    if case.kind == "prefill":
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.lm import prefill
        from repro.serve.decode import cache_pspecs
        from repro.train.step import shardings_for
        args = prefill_input_specs(cfg, case, mesh)
        cache_sh = shardings_for(mesh,
                                 cache_pspecs(cfg, mesh, case.global_batch))

        if cfg.frontend is not None:
            def fn(params, tokens, prefix):
                with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                    return prefill(params, cfg, tokens, prefix,
                                   dtype=jnp.bfloat16)
        else:
            def fn(params, tokens):
                with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                    return prefill(params, cfg, tokens, dtype=jnp.bfloat16)
        step = jax.jit(fn, out_shardings=(
            NamedSharding(mesh, P()), cache_sh))
        return step, args
    # decode
    from repro.serve.decode import make_serve_step
    step, _, _, _ = make_serve_step(cfg, mesh, batch=case.global_batch,
                                    seq_len=case.seq_len)
    return step, decode_input_specs(cfg, case, mesh)


def lower_compile(step, args):
    t0 = time.time()
    lowered = step.lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    return lowered, compiled, t1 - t0, t2 - t1


def unit_cfg(cfg, num_layers):
    return dataclasses.replace(cfg, num_layers=num_layers,
                               scan_layers=False, unroll_inner_scans=True)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             do_roofline: bool, out_dir: str,
             overrides=None) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    case = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_micro = pick_microbatches(cfg, case, mesh)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": list(mesh.devices.shape), "multi_pod": multi_pod,
           "kind": case.kind, "num_microbatches": n_micro,
           "params": param_count(cfg),
           "params_active": active_param_count(cfg)}
    if overrides:
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}

    # --- production compile (the dry-run deliverable) -----------------------
    step, args = build_cell(cfg, case, mesh, n_micro)
    lowered, compiled, t_lower, t_compile = lower_compile(step, args)
    rec["lower_s"] = round(t_lower, 1)
    rec["compile_s"] = round(t_compile, 1)
    rec["memory"] = _mem_dict(compiled)
    print(f"  memory_analysis: {compiled.memory_analysis()}")
    ca = compiled.cost_analysis()
    print(f"  cost_analysis: flops={ca.get('flops')} "
          f"bytes={ca.get('bytes accessed')}")
    rec["cost_analysis_raw"] = {"flops": float(ca.get("flops", 0.0)),
                                "bytes": float(ca.get("bytes accessed", 0.0))}

    # --- roofline (single-pod only): unit compiles + composition ------------
    if do_roofline:
        case_unit = case
        nm = n_micro
        if case.kind == "train":
            micro_b = case.global_batch // n_micro
            case_unit = dataclasses.replace(case, global_batch=micro_b)
        units = []
        for nl in (1, 2):
            ucfg = unit_cfg(cfg, nl)
            ustep, uargs = build_cell(ucfg, case_unit, mesh, 1)
            _, ucomp, _, _ = lower_compile(ustep, uargs)
            # collectives only exist post-SPMD-partitioning -> compiled text
            units.append(RL.unit_metrics(ucomp, ucomp.as_text(), mesh.size))
        total = RL.compose(units[0], units[1], cfg.num_layers, nm)
        terms = total.terms()
        mf = RL.model_flops(cfg, case, rec["params_active"])
        hlo_flops_global = total.flops * mesh.size
        rec["roofline"] = {
            "flops_per_device": total.flops,
            "hbm_bytes_per_device": total.hbm_bytes,
            "wire_bytes_per_device": total.wire_bytes,
            "wire_by_kind": total.wire_by_kind,
            **{k: float(v) for k, v in terms.items()},
            "bottleneck": total.bottleneck(),
            "model_flops_global": mf,
            "useful_flops_ratio": (mf / hlo_flops_global
                                   if hlo_flops_global else 0.0),
        }

    os.makedirs(out_dir, exist_ok=True)
    suffix = "multi" if multi_pod else "single"
    tag = f"{arch}__{shape_name}__{suffix}"
    if overrides:
        tag += "__" + "_".join(f"{k}-{v}" for k, v in overrides.items())
    with open(os.path.join(out_dir, f"{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-roofline", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else args.arch.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    failures = []
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([n for n, _ in cells_for(cfg)] if args.shape == "all"
                  else args.shape.split(","))
        for shape_name in shapes:
            for multi in meshes:
                suffix = "multi" if multi else "single"
                tag = f"{arch}__{shape_name}__{suffix}"
                path = os.path.join(args.out, f"{tag}.json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                # roofline only on the single-pod mesh (per assignment)
                do_roof = (not multi) and (not args.no_roofline)
                print(f"[cell] {tag} ...", flush=True)
                t0 = time.time()
                try:
                    rec = run_cell(arch, shape_name, multi_pod=multi,
                                   do_roofline=do_roof, out_dir=args.out)
                    extra = ""
                    if "roofline" in rec:
                        r = rec["roofline"]
                        extra = (f" bottleneck={r['bottleneck']}"
                                 f" compute={r['compute_s']:.4f}s"
                                 f" mem={r['memory_s']:.4f}s"
                                 f" coll={r['collective_s']:.4f}s")
                    print(f"[ok]   {tag} ({time.time()-t0:.0f}s)"
                          f" peak={rec['memory']['peak_estimate_bytes']/2**30:.2f}GiB"
                          + extra, flush=True)
                except Exception as e:  # record and continue
                    failures.append((tag, repr(e)))
                    print(f"[FAIL] {tag}: {e}", flush=True)
                    traceback.print_exc()
    print(f"\n{len(failures)} failures")
    for tag, err in failures:
        print(f"  {tag}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
