import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""§Perf hillclimbing harness.

Three cells (chosen from the baseline roofline table — see EXPERIMENTS.md):

  * qwen2_1_5b   x train_4k     — canonical 6ND train step (represents the
                                  framework's main workload)
  * minitron_4b  x prefill_32k  — most collective-bound baseline
  * granite_moe_3b_a800m x train_4k — worst roofline fraction (MFU 0.005)

For each cell the harness lowers a sequence of variants (baseline first) on
the single-pod mesh and records the three roofline terms per variant into
``artifacts/perf/<cell>.json``.  The hypothesis -> change -> measure log
lives in EXPERIMENTS.md §Perf.

Run: PYTHONPATH=src python -m repro.launch.perf [cell ...]
"""

import dataclasses
import json
import sys
import time
from pathlib import Path

from repro.configs import SHAPES, get_config
from repro.launch.dryrun import _compile_one
from repro.launch.hloanalysis import analyze
from repro.launch.mesh import make_production_mesh

ART = Path(__file__).resolve().parents[3] / "artifacts" / "perf"

PEAK, HBM, ICI = 197e12, 819e9, 4 * 50e9


def terms(stats: dict, rec_extra: dict) -> dict:
    coll = sum(stats["coll"].values())
    c, m, n = stats["flops"] / PEAK, stats["bytes"] / HBM, coll / ICI
    step = max(c, m, n)
    out = dict(compute=c, memory=m, collective=n, step_time=step,
               dominant=max(("compute", c), ("memory", m),
                            ("collective", n), key=lambda kv: kv[1])[0],
               flops=stats["flops"], bytes=stats["bytes"],
               coll_bytes=coll, **rec_extra)
    return out


def _flash_kernel_traffic(cfg, spec, *, train: bool, dp: int = 16) -> float:
    """Analytic per-device HBM traffic of the FUSED Pallas flash kernel:
    q/k/v/o (+grads) cross HBM once per pass; block intermediates live in
    VMEM scratch.  Used to project the TPU-kernel memory term from the
    attention-ablated compile (see EXPERIMENTS.md §Perf methodology)."""
    if cfg.num_heads == 0:
        return 0.0
    b_loc = spec["global_batch"] / dp
    S = spec["seq_len"]
    e = 2  # bf16
    q_sz = b_loc * S * cfg.num_heads * cfg.hd * e
    kv_sz = b_loc * S * cfg.num_kv_heads * cfg.hd * e
    lse = b_loc * S * cfg.num_heads * 4
    fwd = q_sz + 2 * kv_sz + q_sz + lse                  # r q,k,v; w o,lse
    bwd = (2 * q_sz + 2 * kv_sz + lse) + (q_sz + 2 * kv_sz)  # r + w grads
    per_layer = fwd + (fwd + bwd if train else 0.0)      # remat recompute
    n_attn = (cfg.num_layers if cfg.family in ("dense", "moe", "vlm")
              else cfg.num_layers // cfg.attn_every if cfg.family == "hybrid"
              else cfg.num_layers)
    return per_layer * n_attn


def run_variants(arch: str, shape: str, variants: list[tuple[str, dict]],
                 *, project_kernel_from: str | None = None):
    spec = SHAPES[shape]
    mesh = make_production_mesh()
    base_cfg = get_config(arch)
    results = []
    model_flops = None

    def report(t):
        print(f"[perf] {arch}/{shape} {t['variant']:28s} "
              f"dom={t['dominant']:10s} step={t['step_time']:8.3f}s "
              f"c={t['compute']:.3f} m={t['memory']:.3f} "
              f"n={t['collective']:.3f} mfu={t['mfu']:.4f}", flush=True)

    for name, overrides in variants:
        overrides = dict(overrides)
        vmesh = mesh
        if "_mesh" in overrides:
            import jax
            d, m = overrides.pop("_mesh")
            vmesh = jax.make_mesh((d, m), ("data", "model"))
        cfg = dataclasses.replace(base_cfg, **overrides)
        t0 = time.time()
        compiled, _ = _compile_one(cfg, spec, vmesh)
        stats = analyze(compiled.as_text())
        if model_flops is None:
            # train: 6ND (fwd+bwd); prefill/decode: 2ND (fwd only)
            mult = 6 if spec["kind"] == "train" else 2
            D = (spec["seq_len"] * spec["global_batch"]
                 if spec["kind"] != "decode" else spec["global_batch"])
            model_flops = mult * cfg.param_count(active_only=True) * D
        t = terms(stats, {"variant": name, "overrides": overrides,
                          "mesh_shape": tuple(vmesh.devices.shape),
                          "compile_s": round(time.time() - t0, 1)})
        t["mfu"] = model_flops / (256 * PEAK * t["step_time"])
        results.append(t)
        report(t)

    if project_kernel_from is not None:
        # lower the attention-ablated variant -> non-attention floor, then
        # add the analytic fused-kernel traffic
        src = next(r for r in results if r["variant"] == project_kernel_from)
        import jax
        pmesh = (mesh if tuple(src["mesh_shape"]) == tuple(mesh.devices.shape)
                 else jax.make_mesh(tuple(src["mesh_shape"]),
                                    ("data", "model")))
        cfg = dataclasses.replace(base_cfg, ablate_attention=True,
                                  **src["overrides"])
        compiled, _ = _compile_one(cfg, spec, pmesh)
        floor = analyze(compiled.as_text())
        ktraffic = _flash_kernel_traffic(base_cfg, spec,
                                         train=spec["kind"] == "train",
                                         dp=src["mesh_shape"][0])
        m = (floor["bytes"] + ktraffic) / HBM
        c, n = src["compute"], src["collective"]
        step = max(c, m, n)
        t = dict(compute=c, memory=m, collective=n, step_time=step,
                 dominant=max(("compute", c), ("memory", m),
                              ("collective", n), key=lambda kv: kv[1])[0],
                 flops=src["flops"], bytes=floor["bytes"] + ktraffic,
                 coll_bytes=src["coll_bytes"],
                 variant="+pallas_fused(projected)",
                 overrides={"note": "attention-ablated compile + analytic "
                                    "fused-kernel traffic"},
                 mfu=model_flops / (256 * PEAK * step))
        results.append(t)
        report(t)

    ART.mkdir(parents=True, exist_ok=True)
    (ART / f"{arch}__{shape}.json").write_text(json.dumps(results, indent=1))
    return results


CELLS = {
    "qwen_train": lambda: run_variants("qwen2_1_5b", "train_4k", [
        ("baseline", {}),
        ("+bf16_params", dict(param_dtype="bfloat16")),
        ("+no_remat", dict(param_dtype="bfloat16", remat=False)),
        ("+mesh_32x8", dict(param_dtype="bfloat16", _mesh=(32, 8))),
        ("+mesh_64x4", dict(param_dtype="bfloat16", _mesh=(64, 4))),
        ("+mesh_128x2", dict(param_dtype="bfloat16", _mesh=(128, 2))),
        ("+mesh_256x1_pure_dp", dict(param_dtype="bfloat16", _mesh=(256, 1))),
    ], project_kernel_from="+mesh_128x2"),
    "minitron_prefill": lambda: run_variants("minitron_4b", "prefill_32k", [
        ("baseline", {}),
        ("+bf16_params", dict(param_dtype="bfloat16")),
        ("+mesh_32x8", dict(param_dtype="bfloat16", _mesh=(32, 8))),
    ], project_kernel_from="+mesh_32x8"),
    "granite_train": lambda: run_variants("granite_moe_3b_a800m", "train_4k", [
        ("baseline", {}),
        ("+bf16_params", dict(param_dtype="bfloat16")),
        ("+mesh_32x8_ep8", dict(param_dtype="bfloat16", _mesh=(32, 8))),
        ("+mesh_64x4_ep4", dict(param_dtype="bfloat16", _mesh=(64, 4))),
    ], project_kernel_from="+mesh_32x8_ep8"),
}


def main():
    names = sys.argv[1:] or list(CELLS)
    for n in names:
        CELLS[n]()


if __name__ == "__main__":
    main()
