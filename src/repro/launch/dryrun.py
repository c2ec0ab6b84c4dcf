"""Multi-pod dry-run: prove every (architecture x input-shape x mesh) lowers,
compiles, and fits — and extract the roofline terms (deliverables e + g).

MUST set the device-count override before ANY other import (jax locks the
device count on first init).  Do not set this globally: smoke tests and
benches see 1 device.
"""

import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.api import (  # noqa: E402
    ServeConfig, ServeSession, Trainer, TrainerConfig,
)
from repro.configs import ARCH_IDS, get_config           # noqa: E402
from repro.launch.costs import model_flops_6nd, param_counts, roofline  # noqa: E402
from repro.launch.hlo_analysis import (  # noqa: E402
    analyze_collectives, cost_analysis_dict, full_p_tensors, memory_stats,
)
from repro.launch.mesh import (  # noqa: E402
    HW, make_mesh, make_production_mesh, mesh_num_devices,
)
from repro.launch.steps import (                          # noqa: E402
    INPUT_SHAPES,
    shape_supported,
)


def _host_mesh(spec: str):
    """``"DxM"`` -> a (data, model) mesh over the FIRST D*M host devices —
    the CI-scale twin of the production mesh (the 512-device override is
    already in force, so any small shape fits)."""
    d, m = (int(x) for x in spec.split("x"))
    return make_mesh((d, m), ("data", "model"), devices=jax.devices()[: d * m])


def run_one(arch: str, shape_name: str, multi_pod: bool, *,
            parse_hlo: bool = True, optimized: bool = False,
            params_layout: str = "replicated",
            host_mesh: str | None = None) -> dict:
    cfg = get_config(arch)
    ok, why = shape_supported(cfg, shape_name)
    rec: dict = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": host_mesh or ("2x16x16" if multi_pod else "16x16"),
        "params": param_counts(cfg),
        "params_layout": params_layout,
    }
    if not ok:
        rec.update({"status": "skipped", "reason": why})
        return rec

    mesh = (_host_mesh(host_mesh) if host_mesh
            else make_production_mesh(multi_pod=multi_pod))
    chips = mesh_num_devices(mesh)
    kind = INPUT_SHAPES[shape_name]["kind"]
    engine_P = None
    t0 = time.time()
    try:
        with mesh:
            if kind == "train":
                # the ONE session API: an abstract (shapes-only) Trainer
                # lowers the canonical flat train step with its shardings
                session = Trainer.abstract(TrainerConfig(
                    arch=cfg, mesh=mesh,
                    grad_dtype=jnp.bfloat16 if optimized else None,
                    constrain_grads=optimized,
                    params_layout=params_layout,
                ))
                engine_P = session.engine.P
                lowered = session.lower(shape_name)
            else:  # prefill / decode
                spec = INPUT_SHAPES[shape_name]
                session = ServeSession.abstract(ServeConfig(
                    arch=cfg, batch=spec["global_batch"],
                    max_len=spec["seq_len"], mesh=mesh,
                    use_window=(shape_name == "long_500k"
                                and cfg.sliding_window is not None),
                ))
                (args, shardings) = session.input_specs(shape_name)
                step = (session.prefill_fn if kind == "prefill"
                        else session.decode_fn)
                jitted = jax.jit(step, in_shardings=shardings,
                                 out_shardings=(None, shardings[2]),
                                 donate_argnums=(2,))
                lowered = jitted.lower(*args)

            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        rec["status"] = "ok"
        rec["t_lower_s"] = round(t_lower, 1)
        rec["t_compile_s"] = round(t_compile, 1)
        rec["memory"] = memory_stats(compiled)
        ca = cost_analysis_dict(compiled)
        rec["xla_cost"] = {
            "flops": float(ca.get("flops", -1)),
            "bytes": float(ca.get("bytes accessed", -1)),
        }
        if parse_hlo:
            hlo = compiled.as_text()
            rec["hlo_chars"] = len(hlo)
            coll = analyze_collectives(hlo)
            if params_layout == "tp" and engine_P is not None:
                # the TP-native contract: no op may materialize a
                # replicated [P]-sized buffer on any device
                bad = full_p_tensors(hlo, engine_P)
                rec["full_p_tensors"] = bad
                if bad:
                    rec["status"] = "FAILED"
                    rec["error"] = (
                        f"params_layout='tp' lowered {len(bad)} tensor "
                        f"shape(s) >= P={engine_P} elements: {bad[:5]}")
            del hlo
        else:
            coll = {"total_bytes": 0.0, "per_op": {}, "counts": {}}
        rec["collectives"] = coll
        rl = roofline(cfg, shape_name, chips, coll["total_bytes"], HW)
        rec["roofline"] = {
            "t_compute_s": rl.t_compute, "t_memory_s": rl.t_memory,
            "t_collective_s": rl.t_collective, "bottleneck": rl.bottleneck,
            "analytic_flops": rl.flops, "analytic_hbm_bytes": rl.hbm,
            "collective_bytes": rl.collective,
            "model_flops_6nd": rl.model_flops, "useful_ratio": rl.useful_ratio,
        }
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "FAILED"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        jax.clear_caches()
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all",
                    choices=["all"] + list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-hlo", action="store_true",
                    help="skip collective parsing (faster)")
    ap.add_argument("--optimized", action="store_true",
                    help="beyond-paper train options (bf16 grads, "
                         "reduce-scatter constraint) — §Perf variants")
    ap.add_argument("--params-layout", default="replicated",
                    choices=["replicated", "tp"],
                    help="'tp' feeds the forward from the P-shards via the "
                         "TP-native exchange and FAILS the run if the "
                         "lowered HLO contains any full-[P] tensor")
    ap.add_argument("--host-mesh", default=None, metavar="DxM",
                    help="lower on a small (data, model) host mesh (e.g. "
                         "2x4) instead of the production mesh — the CI "
                         "large-config smoke")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.host_mesh:
        meshes = [False]  # the host mesh replaces the production meshes

    os.makedirs(args.out, exist_ok=True)
    n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_tag = (f"host{args.host_mesh}" if args.host_mesh
                            else ("multi" if mp else "single"))
                tag = f"{arch}_{shape}_{mesh_tag}"
                if args.params_layout != "replicated":
                    tag += f"_{args.params_layout}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[skip existing] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                rec = run_one(arch, shape, mp, parse_hlo=not args.no_hlo,
                              optimized=args.optimized,
                              params_layout=args.params_layout,
                              host_mesh=args.host_mesh)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (
                        f" compile={rec['t_compile_s']}s "
                        f"bottleneck={rec['roofline']['bottleneck']}"
                    )
                elif status == "FAILED":
                    n_fail += 1
                    extra = " " + rec["error"][:200]
                print(f"[{status}] {tag}{extra}", flush=True)
    print(f"done; failures={n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
