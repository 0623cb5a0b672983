import os
os.environ["XLA_FLAGS"] = os.environ.get(
    "REPRO_DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)
# ^^ MUST be the first lines: jax locks the device count at first init.
#    REPRO_DRYRUN_XLA_FLAGS lets tests shrink the fake-device pool.

"""Multi-pod dry-run driver (deliverable e).

For every (architecture x input shape x mesh) cell:
    jit(step, in_shardings, out_shardings).lower(**ShapeDtypeStructs)
        .compile()
then record memory_analysis() (fits-per-device proof), cost_analysis()
(FLOPs/bytes for the roofline), and the collective schedule parsed from the
optimized HLO. Results append to a JSONL cache keyed by cell id, so sweeps
resume after interruption.

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-67b \
        --shape train_4k --mesh pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
"""

import argparse
import gc
import json
import time
import traceback
from pathlib import Path

import jax
import numpy as np

from repro import models
from repro.models.runtime import unroll_scans
from repro.configs import ARCHS, SHAPES, get_config, get_shape
from repro.dist.hlo_analysis import (
    Roofline,
    collective_stats,
    cost_analysis_terms,
    peaks_for,
)
from repro.dist.sharding import ShardingRules
from repro.launch.mesh import mesh_from_spec
from repro.launch.steps import build_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results"

# The chip whose peaks turn cost terms into seconds. The devices this
# process lowers on are fake CPU devices, so their kind says nothing.
CHIP = "TPU v5 lite"


def default_microbatches(shape) -> int:
    return max(1, shape.global_batch // 64) if shape.kind == "train" else 1


def model_flops_per_chip(cfg, shape, n_devices: int) -> float:
    """6*N*D train (fwd+bwd), 2*N*D inference; N = active params."""
    n_active = models.n_active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / n_devices


def cell_id(arch: str, shape: str, mesh: str, variant: str = "base") -> str:
    return f"{arch}|{shape}|{mesh}|{variant}"


def skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return (
            "pure full-attention arch: 524k-token context requires a "
            "quadratic prefill it does not claim (DESIGN.md §4)"
        )
    return None


def run_cell(
    arch: str,
    shape_name: str,
    mesh_spec: str,
    *,
    num_microbatches: int | None = None,
    impl: str = "chunked",
    variant: str = "base",
    rules: ShardingRules | None = None,
    overrides: dict | None = None,
) -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    rec: dict = {
        "cell": cell_id(arch, shape_name, mesh_spec, variant),
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_spec,
        "variant": variant,
        "kind": shape.kind,
        "chip": CHIP,
    }
    reason = skip_reason(cfg, shape)
    if reason:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = mesh_from_spec(mesh_spec)
    n_dev = mesh.devices.size
    nm = num_microbatches or default_microbatches(shape)
    rec["num_microbatches"] = nm
    t0 = time.perf_counter()
    try:
        bundle = build_step(
            cfg, shape, mesh, num_microbatches=nm, impl=impl, rules=rules
        )
        with mesh:
            lowered = bundle.jitted.lower(*bundle.args)
            t_lower = time.perf_counter() - t0
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t1

        mem = compiled.memory_analysis()
        mem_rec = {}
        if mem is not None:
            for f in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "alias_size_in_bytes",
                "generated_code_size_in_bytes",
            ):
                v = getattr(mem, f, None)
                if v is not None:
                    mem_rec[f] = int(v)
            print(f"[memory_analysis] {rec['cell']}: {mem_rec or mem}")
        flops, hbm = cost_analysis_terms(compiled)
        hlo = compiled.as_text()
        coll = collective_stats(hlo)
        roof = Roofline(
            flops=flops,
            hbm_bytes=hbm,
            coll_bytes=coll.total_bytes,
            peaks=peaks_for(CHIP),
            model_flops=model_flops_per_chip(cfg, shape, n_dev),
        )
        print(
            f"[cost_analysis] {rec['cell']}: flops/chip={flops:.3e} "
            f"bytes/chip={hbm:.3e} coll_bytes/chip={coll.total_bytes:.3e}"
        )
        rec.update(
            status="ok",
            n_devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory_analysis=mem_rec,
            roofline=roof.to_json(),
            collectives={
                "count": coll.count,
                "by_op": coll.by_op,
                "schedule_head": coll.schedule[:16],
            },
            hlo_lines=hlo.count("\n"),
        )
        del compiled, lowered, bundle, hlo
    except Exception as e:  # a failing cell is a bug — record loudly
        rec.update(
            status="error",
            error=f"{type(e).__name__}: {e}",
            traceback=traceback.format_exc()[-2000:],
        )
    gc.collect()
    return rec


def cost_samples(cfg):
    """Sample configs + layer-type count vectors for affine extrapolation.

    XLA's HloCostAnalysis counts while-loop bodies once, so the scanned
    production program under-reports FLOPs/bytes/collectives by the trip
    count. Cost probes lower tiny UNROLLED configs (models.runtime.
    unroll_scans) whose cost is exactly affine in per-layer-type counts,
    solve for the coefficients, and evaluate at the full config.
    """
    if cfg.family == "audio" and cfg.is_encdec:
        mk = lambda e, d: cfg.replace(encoder_layers=e, num_layers=d)
        samples = [
            (mk(1, 1), (1, 1)),
            (mk(2, 1), (2, 1)),
            (mk(1, 2), (1, 2)),
        ]
        full = (cfg.encoder_layers, cfg.num_layers)
    elif cfg.family == "hybrid":
        mk = lambda L: cfg.replace(num_layers=L)
        inv = lambda L: (L + cfg.attn_every - 1) // cfg.attn_every
        Ls = [1, 2, cfg.attn_every + 1]
        samples = [(mk(L), (L, inv(L))) for L in Ls]
        full = (cfg.num_layers, inv(cfg.num_layers))
    elif cfg.sliding_window and cfg.global_every:
        from repro.models.transformer import _layer_windows

        mk = lambda L: cfg.replace(num_layers=L)
        counts = lambda c: (
            sum(1 for w in _layer_windows(c) if w > 0),
            sum(1 for w in _layer_windows(c) if w == 0),
        )
        Ls = [1, 2, cfg.global_every]
        samples = [(mk(L), counts(mk(L))) for L in Ls]
        full = counts(cfg)
    else:
        mk = lambda L: cfg.replace(num_layers=L)
        samples = [(mk(1), (1,)), (mk(2), (2,))]
        full = (cfg.num_layers,)
    return samples, full


def run_cost_probe(
    arch: str,
    shape_name: str,
    mesh_spec: str,
    *,
    rules: ShardingRules | None = None,
    overrides: dict | None = None,
) -> dict:
    """Exact roofline terms via unrolled small-L probes + affine solve."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    mesh = mesh_from_spec(mesh_spec)
    n_dev = mesh.devices.size
    samples, full = cost_samples(cfg)
    impl = "naive" if shape.kind in ("train", "prefill") else "chunked"

    rows, ys = [], []
    probe_info = []
    for cfg_s, counts in samples:
        t0 = time.perf_counter()
        bundle = build_step(cfg_s, shape, mesh, num_microbatches=1, impl=impl,
                            rules=rules)
        with mesh, unroll_scans():
            lowered = bundle.jitted.lower(*bundle.args)
            compiled = lowered.compile()
        flops, hbm = cost_analysis_terms(compiled)
        coll = collective_stats(compiled.as_text()).total_bytes
        rows.append([1.0, *[float(c) for c in counts]])
        ys.append([flops, hbm, float(coll)])
        probe_info.append(
            {"counts": list(counts), "flops": flops, "hbm": hbm,
             "coll": coll, "s": round(time.perf_counter() - t0, 1)}
        )
        del compiled, lowered, bundle
        gc.collect()

    A = np.asarray(rows)
    Y = np.asarray(ys)
    coef, *_ = np.linalg.lstsq(A, Y, rcond=None)
    full_row = np.asarray([1.0, *[float(c) for c in full]])
    est = np.maximum(full_row @ coef, 0.0)
    roof = Roofline(
        flops=float(est[0]),
        hbm_bytes=float(est[1]),
        coll_bytes=float(est[2]),
        peaks=peaks_for(CHIP),
        model_flops=model_flops_per_chip(cfg, shape, n_dev),
    )
    return {"roofline": roof.to_json(), "probes": probe_info,
            "full_counts": list(full)}


def load_cache(path: Path) -> dict[str, dict]:
    cache = {}
    if path.exists():
        for line in path.read_text().splitlines():
            if line.strip():
                r = json.loads(line)
                cache[r["cell"]] = r
    return cache


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--mesh", default="pod", help="pod | multipod | AxB[xC]")
    ap.add_argument("--all", action="store_true", help="sweep all 40 cells")
    ap.add_argument("--variant", default="base")
    ap.add_argument("--impl", default="chunked")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--out", default=None, help="JSONL cache (resume-safe)")
    ap.add_argument("--force", action="store_true", help="ignore cache")
    ap.add_argument(
        "--probe",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="run unrolled cost probes (default: on for --mesh pod)",
    )
    ap.add_argument(
        "--rules", default="default",
        help="sharding rule set: default | long | decode_tp | decode_2d_tp",
    )
    ap.add_argument(
        "--override", action="append", default=[],
        help="config override key=value (int/float), e.g. ssm_chunk=64",
    )
    args = ap.parse_args()
    do_probe = args.probe if args.probe is not None else (args.mesh == "pod")

    from repro.dist.sharding import RULESETS

    rules = RULESETS[args.rules]()
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            overrides[k] = int(v)
        except ValueError:
            try:
                overrides[k] = float(v)
            except ValueError:
                overrides[k] = v
    if (args.rules != "default" or overrides) and args.variant == "base":
        args.variant = args.rules + (
            "+" + ",".join(f"{k}{v}" for k, v in overrides.items())
            if overrides
            else ""
        )

    out = Path(args.out) if args.out else (
        RESULTS_DIR / f"dryrun_{args.mesh}.jsonl"
    )
    out.parent.mkdir(parents=True, exist_ok=True)
    cache = {} if args.force else load_cache(out)

    if args.all:
        cells = [(a, s) for a in ARCHS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_ok = n_skip = n_err = 0
    for arch, shape in cells:
        cid = cell_id(arch, shape, args.mesh, args.variant)
        cached = cache.get(cid)
        need_probe = do_probe and not (cached or {}).get("cost_probe")
        if cached and cached["status"] == "ok" and not need_probe:
            rec = cached
            print(f"[cached] {cid}: {rec['status']}")
        elif cached and cached["status"] == "skipped":
            rec = cached
            print(f"[cached] {cid}: skipped")
        else:
            if cached and cached["status"] == "ok":
                rec = cached  # base ok; only the probe is missing
            else:
                rec = run_cell(
                    arch,
                    shape,
                    args.mesh,
                    num_microbatches=args.microbatches,
                    impl=args.impl,
                    variant=args.variant,
                    rules=rules,
                    overrides=overrides,
                )
            if do_probe and rec["status"] == "ok":
                try:
                    rec["cost_probe"] = run_cost_probe(
                        arch, shape, args.mesh, rules=rules,
                        overrides=overrides,
                    )
                    r = rec["cost_probe"]["roofline"]
                    print(
                        f"[probe] {cid}: flops/chip={r['flops']:.3e} "
                        f"dominant={r['dominant']} "
                        f"useful={r['useful_flops_frac']:.2f}"
                    )
                except Exception as e:
                    rec["cost_probe"] = {"error": f"{type(e).__name__}: {e}"}
                    print(f"[probe ERROR] {cid}: {e}")
            with out.open("a") as f:
                f.write(json.dumps(rec) + "\n")
        if rec["status"] == "ok":
            n_ok += 1
            r = rec["roofline"]
            print(
                f"[ok] {cid}: dominant={r['dominant']} "
                f"compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
                f"collective={r['collective_s']:.4f}s "
                f"useful={r['useful_flops_frac']:.2f} "
                f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)"
            )
        elif rec["status"] == "skipped":
            n_skip += 1
            print(f"[skip] {cid}: {rec['reason']}")
        else:
            n_err += 1
            print(f"[ERROR] {cid}: {rec['error']}")
    print(f"\ndry-run summary: ok={n_ok} skipped={n_skip} errors={n_err}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
