"""HLO byte/op profiler — the dry-run 'profiler' (no real hardware).

Aggregates result-shape bytes by op kind over the optimized per-device HLO
(`repro.obs.profile.profile_hlo`), weighting ops inside while loops (the
layer scan) by the trip count.  This is what grounds the §Perf napkin
math: 'which op family moves the most HBM bytes?'.  The command line
forces 512 host devices through XLA_FLAGS; importing this module sets
nothing.

    PYTHONPATH=src python -m repro.launch.hlo_profile --arch deepseek-v2-236b \
        --shape train_4k --top 25
"""

import argparse
import os

from repro.obs.profile import profile_hlo


def main():
    # before the first device query: the backend reads XLA_FLAGS once
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--serve-rules", default="train")
    args = ap.parse_args()

    import jax

    from repro.configs import get_config
    from repro.launch import sharding as shd
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES
    from repro.launch.steps import plan_decode, plan_prefill, plan_train

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    rules = shd.rules_serve_stationary(mesh) if args.serve_rules == "stationary" else None
    if shape.kind == "train":
        fn, in_sh, out_sh, inputs = plan_train(cfg, shape, mesh, remat=args.remat)
    elif shape.kind == "prefill":
        fn, in_sh, out_sh, inputs = plan_prefill(cfg, shape, mesh, rules=rules)
    else:
        fn, in_sh, out_sh, inputs = plan_decode(cfg, shape, mesh, rules=rules)
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*inputs).compile()
    n_sites, total = cfg.scan_sites(shape.kind)
    agg = profile_hlo(compiled.as_text(), scan_factor=total / n_sites)
    total_b = sum(agg.values())
    print(f"{'op':24s} {'GB':>12s} {'share':>7s}")
    for op, b in sorted(agg.items(), key=lambda kv: -kv[1])[: args.top]:
        print(f"{op:24s} {b/1e9:12.1f} {b/total_b:7.1%}")
    print(f"{'TOTAL':24s} {total_b/1e9:12.1f}")


if __name__ == "__main__":
    main()
