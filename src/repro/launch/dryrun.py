"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
against ShapeDtypeStruct inputs, record memory/cost analysis + collective
bytes parsed from the optimized HLO.

    PYTHONPATH=src python -m repro.launch.dryrun --all
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k --mesh multi

Results are cached incrementally under benchmarks/results/dryrun/ so reruns
skip completed cells (--force recomputes).  The command line forces 512
host devices through XLA_FLAGS; importing this module sets nothing.
"""

import argparse
import os
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, applicability
from repro.launch.steps import plan_decode, plan_prefill, plan_train
from repro.obs.profile import shape_bytes

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\b"
)
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in (optimized) HLO text.

    HLO lines look like:
      %ag = bf16[16,1024]{1,0} all-gather(bf16[1,1024]{1,0} %p), dims=...
    We count the *operand* sizes (the data each chip injects into the
    network), falling back to the result size when operands aren't typed.
    """
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None or "= " not in line:
            continue
        op = m.group(1)
        if f" {op}(" not in line and f"{op}-start(" not in line and f"{op}(" not in line:
            continue
        # operands: typed shapes inside the call parens
        call = line.split(op, 1)[1]
        shapes = _SHAPE_RE.findall(call)
        if shapes:
            nbytes = sum(shape_bytes(dt, dims) for dt, dims in shapes)
        else:  # fall back to the result shape (before the '=')
            res = _SHAPE_RE.findall(line.split("=", 1)[1])
            nbytes = shape_bytes(*res[0]) if res else 0
        out[op] = out.get(op, 0) + nbytes
    return out


#: ops that alias/bookkeep rather than touch HBM on TPU (while-loop state
#: threading, tuple plumbing, layout bitcasts).  XLA:CPU's cost analysis
#: charges them bytes; a TPU execution would not.  The roofline memory term
#: uses bytes excluding these (raw kept alongside).
_ALIAS_OPS = ("get-tuple-element", "parameter", "bitcast", "tuple", "copy")

_HLO_OP_RE = re.compile(r"=\s+([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+([a-z0-9-]+)")


def bytes_by_op(hlo_text: str) -> dict:
    """Result-shape bytes aggregated by op kind over the per-device HLO."""
    agg: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_RE.search(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        agg[op] = agg.get(op, 0.0) + shape_bytes(dtype, dims)
    return agg


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
        "peak_memory_in_bytes",
    )
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    keep = ("flops", "transcendentals", "bytes accessed", "optimal_seconds")
    return {k: float(v) for k, v in ca.items() if k in keep}


def run_cell(arch: str, shape_name: str, mesh_kind: str, remat: str = "none",
             serve_rules: str = "train", moe_impl: str | None = None,
             mla_decode_impl: str | None = None, pin_cache: bool = False,
             capacity_factor: float | None = None, ssm_chunk: int | None = None,
             tag: str = "") -> dict:
    import dataclasses as _dc

    cfg = get_config(arch)
    if moe_impl:
        cfg = cfg.replace(moe_impl=moe_impl)
    if mla_decode_impl:
        cfg = cfg.replace(mla_decode_impl=mla_decode_impl)
    if capacity_factor is not None and cfg.moe is not None:
        cfg = cfg.replace(moe=_dc.replace(cfg.moe, capacity_factor=capacity_factor))
    if ssm_chunk is not None and cfg.ssm is not None:
        cfg = cfg.replace(ssm=_dc.replace(cfg.ssm, chunk=ssm_chunk))
    shape = SHAPES[shape_name]
    ok, reason = applicability(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "remat": remat, "serve_rules": serve_rules,
    }
    if not ok:
        rec.update(status="SKIP", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = None
    if shape.kind != "train" and serve_rules == "stationary":
        rules = shd.rules_serve_stationary(mesh)

    def lower_compile(cfg_v):
        t0 = time.time()
        if shape.kind == "train":
            fn, in_sh, out_sh, inputs = plan_train(cfg_v, shape, mesh, remat=remat)
        elif shape.kind == "prefill":
            fn, in_sh, out_sh, inputs = plan_prefill(cfg_v, shape, mesh, rules=rules)
        else:
            fn, in_sh, out_sh, inputs = plan_decode(
                cfg_v, shape, mesh, rules=rules, pin_cache=pin_cache
            )
        lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*inputs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        return compiled, t_lower, time.time() - t0

    compiled, t_lower, t_compile = lower_compile(cfg)
    text1 = compiled.as_text()
    cost1 = _cost_analysis_dict(compiled)
    coll1 = collective_bytes(text1)
    ops1 = bytes_by_op(text1)

    # --- loop-body cost correction -------------------------------------
    # XLA's HloCostAnalysis counts a while-loop body ONCE regardless of the
    # trip count, so everything inside the layer scan is undercounted.
    # Re-lowering with scan unroll=2 duplicates each scan body exactly once;
    # the delta is the summed per-layer body cost across scan sites, and
    #   corrected = A1 + (A2 - A1) * (total_layers - n_sites) / n_sites
    # (valid because each arch's scan bodies have equal per-layer cost; see
    # ModelConfig.scan_sites).
    n_sites, total_layers = cfg.scan_sites(shape.kind)
    compiled2, _, t_compile2 = lower_compile(cfg.replace(scan_unroll=2))
    text2 = compiled2.as_text()
    cost2 = _cost_analysis_dict(compiled2)
    coll2 = collective_bytes(text2)
    ops2 = bytes_by_op(text2)
    factor = (total_layers - n_sites) / n_sites

    def correct(a1: dict, a2: dict) -> dict:
        keys = set(a1) | set(a2)
        return {
            k: a1.get(k, 0.0) + (a2.get(k, 0.0) - a1.get(k, 0.0)) * factor
            for k in keys
        }

    ops_corrected = correct(ops1, ops2)
    adjusted = sum(v for k, v in ops_corrected.items() if k not in _ALIAS_OPS)
    rec.update(
        status="OK",
        n_devices=mesh.devices.size,
        device_kind=mesh.devices.flat[0].device_kind,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile + t_compile2, 2),
        memory=_memory_analysis_dict(compiled),
        cost_raw=cost1,
        cost=correct(cost1, cost2),
        collectives_raw=coll1,
        collectives={k: int(v) for k, v in correct(coll1, coll2).items()},
        bytes_by_op={k: int(v) for k, v in sorted(ops_corrected.items(), key=lambda kv: -kv[1])[:12]},
        bytes_adjusted=int(adjusted),
        scan_sites=[n_sites, total_layers],
    )
    return rec


def _cell_path(arch, shape, mesh_kind, tag="") -> Path:
    suffix = f"__{tag}" if tag else ""
    return RESULTS_DIR / f"{arch}__{shape}__{mesh_kind}{suffix}.json"


def main():
    # before the first device query: the backend reads XLA_FLAGS once
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default=None)
    ap.add_argument("--shape", choices=list(SHAPES), default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="none", choices=["none", "full", "dots"])
    ap.add_argument("--serve-rules", default="train", choices=["train", "stationary"])
    ap.add_argument("--moe-impl", default=None, choices=[None, "gather", "dense"])
    ap.add_argument("--mla-decode-impl", default=None, choices=[None, "naive", "absorbed"])
    ap.add_argument("--pin-decode-cache", action="store_true")
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--tag", default="", help="variant tag for §Perf iterations")
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_ok = n_skip = n_fail = n_cached = 0
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = _cell_path(arch, shape, mesh_kind, args.tag)
                if path.exists() and not args.force:
                    prev = json.loads(path.read_text())
                    if prev.get("status") in ("OK", "SKIP"):
                        n_cached += 1
                        continue
                try:
                    rec = run_cell(
                        arch, shape, mesh_kind, remat=args.remat,
                        serve_rules=args.serve_rules, moe_impl=args.moe_impl,
                        mla_decode_impl=args.mla_decode_impl,
                        pin_cache=args.pin_decode_cache,
                        capacity_factor=args.capacity_factor,
                        ssm_chunk=args.ssm_chunk, tag=args.tag,
                    )
                except Exception as e:  # a failure here is a sharding bug
                    rec = {
                        "arch": arch, "shape": shape, "mesh": mesh_kind,
                        "tag": args.tag, "status": "FAIL",
                        "error": f"{type(e).__name__}: {e}",
                        "traceback": traceback.format_exc()[-2000:],
                    }
                path.write_text(json.dumps(rec, indent=1))
                st = rec["status"]
                n_ok += st == "OK"
                n_skip += st == "SKIP"
                n_fail += st == "FAIL"
                extra = ""
                if st == "OK":
                    fl = rec["cost"].get("flops", 0)
                    extra = f"flops={fl:.3e} compile={rec['compile_s']}s"
                elif st == "FAIL":
                    extra = rec["error"][:140]
                print(f"[{st}] {arch} x {shape} x {mesh_kind} {extra}", flush=True)
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail} cached={n_cached}")


if __name__ == "__main__":
    main()
