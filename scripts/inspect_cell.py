import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # the 512 fake devices are CPU devices
"""Hillclimb profiler: compile the 1-unit unrolled program for a cell and
print the largest collectives + a bytes-by-op-kind breakdown from the
optimized HLO. This is the 'profile' of the dry-run methodology.

    PYTHONPATH=src python scripts/inspect_cell.py glm4-9b long_500k [--multi-pod]
"""
import argparse
import re
import sys
from collections import defaultdict

import jax

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.launch.dryrun import _COLL_RE, _shape_bytes, _unrolled_cfgs  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--units", type=int, default=1, choices=(1, 2))
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    import repro.configs as configs
    from repro.configs.base import LM_SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import step_and_specs

    cfg = configs.get(args.arch)
    one, two, scale = _unrolled_cfgs(cfg)
    cfg_u = one if args.units == 1 else two
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    shape = LM_SHAPES[args.shape]
    step, specs, shardings = step_and_specs(cfg_u, shape, mesh)
    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(step, in_shardings=shardings)
            .lower(*specs).compile()
        )
    hlo = compiled.as_text()

    # -------- collectives, individually, sorted by payload
    colls = []
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if m:
            meta = re.search(r'op_name="([^"]+)"', line)
            colls.append(
                (_shape_bytes(m.group(1)), m.group(2),
                 (meta.group(1) if meta else "?")[-90:])
            )
    colls.sort(reverse=True)
    print(f"== top {args.top} collectives (per-device payload), {len(colls)} total ==")
    for b, kind, name in colls[: args.top]:
        print(f"  {b/2**20:9.1f} MiB  {kind:20s} {name}")
    by_kind = defaultdict(int)
    for b, kind, _ in colls:
        by_kind[kind] += b
    print("== totals by kind ==")
    for kind, b in sorted(by_kind.items(), key=lambda kv: -kv[1]):
        print(f"  {b/2**30:8.2f} GiB  {kind}")

    # -------- biggest result buffers by op kind (memory-term suspects)
    op_re = re.compile(r"=\s*((?:\([^)]*\)|\S+))\s+([a-z][\w-]*)\(")
    by_op = defaultdict(int)
    biggest = []
    for line in hlo.splitlines():
        m = op_re.search(line)
        if not m:
            continue
        b = _shape_bytes(m.group(1))
        if b > 0:
            by_op[m.group(2)] += b
            if b > 64 * 2**20:
                meta = re.search(r'op_name="([^"]+)"', line)
                biggest.append((b, m.group(2), (meta.group(1) if meta else "?")[-90:]))
    print(f"== result-buffer bytes by op kind (top {args.top}) ==")
    for kind, b in sorted(by_op.items(), key=lambda kv: -kv[1])[: args.top]:
        print(f"  {b/2**30:8.2f} GiB  {kind}")
    biggest.sort(reverse=True)
    print(f"== individual result buffers > 64 MiB (top {args.top}) ==")
    for b, kind, name in biggest[: args.top]:
        print(f"  {b/2**20:9.1f} MiB  {kind:16s} {name}")

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    print(f"== cost: flops={ca.get('flops', 0):.3e} bytes={ca.get('bytes accessed', 0):.3e}")


if __name__ == "__main__":
    main()
