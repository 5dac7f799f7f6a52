import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# a CPU rehearsal by design: never take an attached accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) and emit
memory/cost/roofline analyses on 512 virtual CPU devices. MUST run as its
own process (the environment above locks the platform and the host device
count at first jax init).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
      --shape decode_32k [--multi-pod] [--out out.json]
"""

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402

import jax               # noqa: E402

from repro.configs import get_config, get_shape   # noqa: E402
from repro.launch.mesh import make_production_mesh, sharding_rules  # noqa: E402
from repro.launch.roofline import model_flops, roofline_report      # noqa: E402
from repro.launch.specs import make_serve_specs, make_train_specs   # noqa: E402
from repro.models import sharding as sharding_mod                    # noqa: E402


def _compile(cfg, shape, mesh, multi_pod, scan_unroll=False):
    if shape.kind == "train":
        step, specs = make_train_specs(cfg, shape, mesh, multi_pod=multi_pod,
                                       scan_unroll=scan_unroll)
        donate = (0, 1)
    else:
        step, specs = make_serve_specs(cfg, shape, mesh, multi_pod=multi_pod,
                                       scan_unroll=scan_unroll)
        donate = (1,)
    with mesh:
        lowered = jax.jit(step, donate_argnums=donate).lower(*specs)
        compiled = lowered.compile()
    return compiled


def _calibration_points(cfg):
    """Reduced-depth configs for the affine cost model total(L) = base +
    L*per_layer. XLA's cost analysis counts while-loop bodies once, so the
    calibration compiles run with the layer scan fully UNROLLED at tiny
    depth and extrapolate (verified: unrolled-L sweep is affine in L and
    matches straight-line code exactly)."""
    import dataclasses as dc
    if cfg.enc_dec:
        # vary decoder and encoder depth independently
        return [
            ("f11", dc.replace(cfg, n_layers=1, n_enc_layers=1)),
            ("f21", dc.replace(cfg, n_layers=2, n_enc_layers=1)),
            ("f12", dc.replace(cfg, n_layers=1, n_enc_layers=2)),
        ]
    if cfg.is_moe and cfg.moe_dense_layers:
        return [
            ("fa", dc.replace(cfg, n_layers=cfg.moe_dense_layers + 1)),
            ("fb", dc.replace(cfg, n_layers=cfg.moe_dense_layers + 2)),
        ]
    return [("fa", dc.replace(cfg, n_layers=1)),
            ("fb", dc.replace(cfg, n_layers=2))]


def _counts(compiled):
    from repro.launch.roofline import collective_bytes
    ca = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total"])}


def calibrated_counts(cfg, shape, mesh, multi_pod) -> dict:
    """Extrapolated per-device (flops, bytes, collective-bytes) for the full
    depth, from unrolled reduced-depth compiles."""
    pts = _calibration_points(cfg)
    counts = {}
    for name, c in pts:
        counts[name] = _counts(_compile(c, shape, mesh, multi_pod,
                                        scan_unroll=True))
    out = {}
    for key in ("flops", "bytes", "coll"):
        if cfg.enc_dec:
            f11, f21, f12 = (counts["f11"][key], counts["f21"][key],
                             counts["f12"][key])
            d_dec, d_enc = f21 - f11, f12 - f11
            out[key] = (f11 + (cfg.n_layers - 1) * d_dec
                        + (cfg.n_enc_layers - 1) * d_enc)
        else:
            a_l = pts[0][1].n_layers
            fa, fb = counts["fa"][key], counts["fb"][key]
            per_layer = fb - fa
            out[key] = fa + (cfg.n_layers - a_l) * per_layer
    return out


def run_one(arch: str, shape_name: str, multi_pod: bool,
            verbose: bool = True, calibrate: bool = True) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    sharding_mod.set_rules(sharding_rules(multi_pod, cfg), mesh)
    try:
        t0 = time.time()
        compiled = _compile(cfg, shape, mesh, multi_pod)
        t_compile = time.time() - t0

        report = roofline_report(compiled, n_chips, model_flops(cfg, shape))
        if calibrate:
            t1 = time.time()
            cal = calibrated_counts(cfg, shape, mesh, multi_pod)
            from repro.launch.roofline import HBM_BW, ICI_BW, PEAK_FLOPS
            report.update({
                "flops_per_device": cal["flops"],
                "flops_global": cal["flops"] * n_chips,
                "bytes_per_device": cal["bytes"],
                "collective_bytes_per_device": cal["coll"],
                "t_compute": cal["flops"] / PEAK_FLOPS,
                "t_memory": cal["bytes"] / HBM_BW,
                "t_collective": cal["coll"] / ICI_BW,
                "calibrated": True,
                "t_calibrate_s": round(time.time() - t1, 2),
            })
            terms = {"compute": report["t_compute"],
                     "memory": report["t_memory"],
                     "collective": report["t_collective"]}
            report["bottleneck"] = max(terms, key=terms.get)
            report["useful_flops_ratio"] = (
                report["model_flops_global"] / report["flops_global"]
                if report["flops_global"] else float("nan"))
        report.update({
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "status": "ok",
            "t_compile_s": round(t_compile, 2),
        })
        if verbose:
            ma = compiled.memory_analysis()
            print(f"[{arch} x {shape_name} x {report['mesh']}] OK "
                  f"compile={t_compile:.1f}s "
                  f"calibrate={report.get('t_calibrate_s', 0)}s")
            print(f"  memory_analysis: {ma}")
            ca = compiled.cost_analysis()
            print(f"  cost_analysis: flops={ca.get('flops', 0):.3e} "
                  f"bytes={ca.get('bytes accessed', 0):.3e}")
            print(f"  roofline: compute={report['t_compute']*1e3:.3f}ms "
                  f"memory={report['t_memory']*1e3:.3f}ms "
                  f"collective={report['t_collective']*1e3:.3f}ms "
                  f"-> {report['bottleneck']}-bound "
                  f"useful_flops={report['useful_flops_ratio']:.3f}")
        return report
    finally:
        sharding_mod.set_rules(None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    report = run_one(args.arch, args.shape, args.multi_pod)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, default=str)
    return 0 if report["status"] == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
