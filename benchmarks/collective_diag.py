"""Diagnostic: lower one cell and print the top collectives by scaled link
traffic, with their HLO metadata op_name (which model op produced them).

    PYTHONPATH=src python -m benchmarks.collective_diag --arch X --shape Y
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")
import argparse
import re

from repro.launch import hlo_analysis as ha


def diagnose(hlo: str, top: int = 15):
    comps = ha.split_computations(hlo)
    mult = ha.computation_multipliers(comps)
    rows = []
    for name, lines in comps.items():
        m = max(mult.get(name, 1.0), 1.0)
        for line in lines:
            cm = ha._COLL_RE.search(line)
            if not cm:
                continue
            op = ha.CollectiveOp(kind=cm.group(1),
                                 result_bytes=ha._shape_bytes(line),
                                 group_size=ha._group_size(line),
                                 multiplier=m)
            meta = re.search(r'op_name="([^"]*)"', line)
            shape = re.search(r"=\s*(\(?[a-z0-9]+\[[^\]]*\])", line)
            rows.append((op.per_chip_link_bytes, op.kind,
                         shape.group(1) if shape else "?", op.group_size, m,
                         (meta.group(1)[-110:] if meta else "?")))
    rows.sort(key=lambda r: -r[0])
    total = sum(r[0] for r in rows)
    print(f"total link bytes/chip: {total / 1e9:.1f} GB")
    for r in rows[:top]:
        print(f"{r[0] / 1e9:8.2f}GB {r[1]:18s} {r[2]:28s} grp={r[3]:<4d} "
              f"x{r[4]:<6.0f} {r[5]}")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel activation constraints")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.dist import sharding as act_sharding
    from repro.launch.dryrun import (_lower_decode, _lower_prefill,
                                     _lower_train)
    from repro.launch.mesh import batch_axes, make_production_mesh
    from repro.launch.shapes import SHAPES
    from repro.models import get_model

    cfg = get_config(args.arch)
    model = get_model(cfg)
    mesh = make_production_mesh(multi_pod=args.multi)
    act_sharding.enable(batch_axes(mesh), sp=args.sp, mesh=mesh)
    shape = SHAPES[args.shape]
    with mesh:
        if shape.kind == "train":
            lowered, _ = _lower_train(model, shape, mesh)
        elif shape.kind == "prefill":
            lowered, _ = _lower_prefill(model, shape, mesh)
        else:
            lowered, _ = _lower_decode(model, shape, mesh)
        hlo = lowered.compile().as_text()
    diagnose(hlo, args.top)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
