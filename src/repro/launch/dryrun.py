import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("REPRO_EXTRA_XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"  # the 512 fake devices are CPU devices
# ^ MUST precede every other import: jax locks the device count on first init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent without real
hardware: the jitted step lowers, the SPMD partitioner accepts the shardings,
the compiled module's memory analysis fits per-chip HBM, and cost analysis +
the optimized HLO's collective ops yield the §Roofline terms.

Results are cached as JSON under experiments/dryrun/ so reruns skip finished
cells; benchmarks/roofline.py renders the table from these files.

Usage:
    python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax


COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

# `%name = <result shapes> <collective-op>(operands...)` in optimized HLO
_COLL_RE = re.compile(
    r"=\s*(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\("
)


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every dtype[dims] shape literal in `text`."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device bytes moved per collective kind, from optimized HLO.

    Each collective instruction's *result shapes* (printed between `=` and
    the op name) are the per-device payload; `-done` ops of async pairs carry
    no shapes of their own and are skipped by the regex ("-done(" never
    follows a shape list in the same form).
    """
    out = {k: {"count": 0, "bytes": 0} for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shapes, kind = m.group(1), m.group(2)
        out[kind]["count"] += 1
        out[kind]["bytes"] += _shape_bytes(shapes)
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items() if isinstance(v, dict))
    return out


def _compile_and_analyze(cfg, shape, mesh):
    """Lower + compile one step; return (compiled artifacts summary)."""
    from repro.launch.specs import step_and_specs

    t0 = time.time()
    step_fn, arg_specs, in_shardings = step_and_specs(cfg, shape, mesh)
    with jax.set_mesh(mesh):
        jitted = jax.jit(step_fn, in_shardings=in_shardings)
        lowered = jitted.lower(*arg_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for field in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            if hasattr(ma, field):
                mem[field] = int(getattr(ma, field))
    except Exception as e:  # noqa: BLE001
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
            if k in ca:
                cost[k] = float(ca[k])
    except Exception as e:  # noqa: BLE001
        cost["error"] = str(e)

    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    return {
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "cost": cost,
        "collectives": coll,
        "hlo_bytes": len(hlo),
    }


def _unrolled_cfgs(cfg):
    """(1-unit cfg, 2-unit cfg, scale): the layer-delta cost model.

    XLA's HloCostAnalysis counts while/scan bodies ONCE regardless of trip
    count, and the scanned layer's collectives likewise appear once in the
    optimized HLO text. So roofline numbers come from two small *unrolled*
    compiles: per-unit cost = cost(2 units) - cost(1 unit); total = cost(1) +
    (scale - 1) * per-unit. A "unit" is one decoder layer (dense/moe/ssm), one
    Mamba-group + shared-attention block (zamba2), or one encoder+decoder
    layer pair (whisper). Remat stays ON so recompute FLOPs are counted.
    """
    import dataclasses

    if cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        one = dataclasses.replace(cfg, n_layers=every, scan_layers=False)
        two = dataclasses.replace(cfg, n_layers=2 * every, scan_layers=False)
        scale = cfg.n_layers // every
    elif cfg.arch_kind == "encdec":
        one = dataclasses.replace(cfg, n_layers=1, n_encoder_layers=1, scan_layers=False)
        two = dataclasses.replace(cfg, n_layers=2, n_encoder_layers=2, scan_layers=False)
        scale = cfg.n_layers
    else:
        one = dataclasses.replace(cfg, n_layers=1, scan_layers=False)
        two = dataclasses.replace(cfg, n_layers=2, scan_layers=False)
        scale = cfg.n_layers
    return one, two, scale


def _combine_cost_model(r1: dict, r2: dict, scale: int) -> dict:
    """total = base(1 unit) + (scale-1) * (unit delta), clamped at >= r1."""

    def tot(get):
        a, b = get(r1), get(r2)
        return a + max(b - a, 0.0) * (scale - 1)

    coll = {}
    for kind in COLLECTIVES:
        coll[kind] = {
            "count": int(tot(lambda r, k=kind: r["collectives"][k]["count"])),
            "bytes": int(tot(lambda r, k=kind: r["collectives"][k]["bytes"])),
        }
    coll["total_bytes"] = sum(coll[k]["bytes"] for k in COLLECTIVES)
    return {
        "flops": tot(lambda r: r["cost"].get("flops", 0.0)),
        "bytes_accessed": tot(lambda r: r["cost"].get("bytes accessed", 0.0)),
        "collectives": coll,
        "unit_compile_s": [r1["compile_s"], r2["compile_s"]],
        "scale": scale,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             force: bool = False, opts: tuple[str, ...] = ()) -> dict:
    import dataclasses

    import repro.configs as configs
    from repro.configs.base import LM_SHAPES
    from repro.launch.mesh import (
        HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16, make_production_mesh,
    )
    from repro.launch.specs import uses_bangkv

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    tag = ("__opt-" + "-".join(o.removeprefix("opt_") for o in opts)) if opts else ""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_name}{tag}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = configs.get(arch)
    if opts:
        cfg = dataclasses.replace(cfg, **{o: True for o in opts})
    shape = LM_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "n_chips": n_chips, "kind": shape.kind, "opts": list(opts),
        "bangkv": uses_bangkv(cfg, shape), "status": "error",
    }
    try:
        # 1) The production program (scan over layers): proof of compile +
        #    memory analysis at full depth.
        full = _compile_and_analyze(cfg, shape, mesh)
        record["full_program"] = full

        # 2) Layer-delta cost model from two unrolled shallow compiles.
        one, two, scale = _unrolled_cfgs(cfg)
        r1 = _compile_and_analyze(one, shape, mesh)
        r2 = _compile_and_analyze(two, shape, mesh)
        cm = _combine_cost_model(r1, r2, scale)
        record["cost_model"] = cm

        flops = cm["flops"]
        bytes_acc = cm["bytes_accessed"]
        compute_s = flops / PEAK_FLOPS_BF16
        memory_s = bytes_acc / HBM_BW
        collective_s = cm["collectives"]["total_bytes"] / ICI_BW_PER_LINK
        dominant = max(
            ("compute", compute_s), ("memory", memory_s), ("collective", collective_s),
            key=lambda kv: kv[1],
        )[0]

        # model FLOPs: 6*N*D (dense) / 6*N_active*D (MoE), global per step
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 6.0 * cfg.active_param_count() * tokens
        elif shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            model_flops = 2.0 * cfg.active_param_count() * tokens
        else:
            tokens = shape.global_batch
            model_flops = 2.0 * cfg.active_param_count() * tokens

        record.update(
            status="ok",
            compile_s=full["compile_s"],
            roofline={
                "compute_s": compute_s,
                "memory_s": memory_s,
                "collective_s": collective_s,
                "dominant": dominant,
                "model_flops_global": model_flops,
                "hlo_flops_per_chip": flops,
                "useful_flop_ratio": (
                    model_flops / (flops * n_chips) if flops else None
                ),
            },
        )
    except Exception:  # noqa: BLE001
        record["traceback"] = traceback.format_exc()
    record["wall_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--opts", default="",
                    help="comma list of ModelConfig opt_* flags to enable "
                         "(results tagged; use --out experiments/perf)")
    args = ap.parse_args()
    opts = tuple(o if o.startswith("opt_") else f"opt_{o}"
                 for o in args.opts.split(",") if o)

    import repro.configs as configs
    from repro.configs.base import LM_SHAPES

    archs = sorted(configs.ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(LM_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, args.out, force=args.force, opts=opts)
                ok = rec["status"] == "ok"
                failures += 0 if ok else 1
                dom = rec.get("roofline", {}).get("dominant", "-")
                print(
                    f"[{'OK' if ok else 'FAIL':4s}] {arch:26s} {shape:12s} "
                    f"{rec['mesh']:10s} compile={rec.get('compile_s', '-')}s "
                    f"dominant={dom}",
                    flush=True,
                )
                if not ok:
                    tb = rec.get("traceback", "")
                    print(tb.splitlines()[-1] if tb else "?", flush=True)
    print(f"dry-run complete: {failures} failures", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
