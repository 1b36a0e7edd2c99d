"""Roofline analysis over dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch x shape x mesh) cell, from the compiled per-device HLO:

  compute term    = HLO_FLOPs_global / (chips x peak FLOP/s)
  memory term     = HLO_bytes_global / (chips x peak HBM B/s)
  collective term = collective_bytes_per_device / peak B/s per link

with the peaks of the device kind the cell was compiled for
(`repro.launch.mesh.peaks`; a kind without published peaks is an error).

cost_analysis() on the partitioned module reports PER-DEVICE numbers, so
globals are per-device x chips; the collective term uses per-device bytes
directly (each chip drives its own ICI links).

MODEL_FLOPS uses the standard 6·N·D training estimate (2·N·D fwd for
prefill; 2·N_active·B per decoded token), with N_active for MoE.  The ratio
MODEL_FLOPS / HLO_FLOPs shows how much compiled compute is 'useful'.
"""

from __future__ import annotations

import json
from pathlib import Path

from repro.configs import get_config
from repro.launch.mesh import peaks
from repro.launch.shapes import SHAPES

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

_PARAM_CACHE: dict[str, tuple[int, int]] = {}


def param_counts(arch: str) -> tuple[int, int]:
    """(total, active) parameter counts, cached (abstract init, no alloc)."""
    if arch not in _PARAM_CACHE:
        cfg = get_config(arch)
        _PARAM_CACHE[arch] = (cfg.param_count(), cfg.active_param_count())
    return _PARAM_CACHE[arch]


def model_flops(arch: str, shape_name: str) -> float:
    """Useful-compute estimate for the cell."""
    shape = SHAPES[shape_name]
    total, active = param_counts(arch)
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * active * tokens  # fwd + bwd
    if shape.kind == "prefill":
        return 2.0 * active * tokens
    # decode: one token per sequence
    return 2.0 * active * shape.global_batch


def analyze_cell(rec: dict) -> dict:
    arch, shape_name = rec["arch"], rec["shape"]
    chips = rec["n_devices"]
    flops_dev = rec["cost"].get("flops", 0.0)
    bytes_raw = rec["cost"].get("bytes accessed", 0.0)
    # memory term from result bytes excluding while-loop aliasing plumbing
    # (see dryrun._ALIAS_OPS); fall back to raw cost-analysis bytes
    bytes_dev = rec.get("bytes_adjusted", bytes_raw)
    coll_dev = sum(rec.get("collectives", {}).values())
    peak = peaks(rec["device_kind"])

    t_compute = flops_dev * chips / (chips * peak["flops_bf16"])  # = flops_dev / peak
    t_memory = bytes_dev * chips / (chips * peak["hbm_bw"])
    t_collective = coll_dev / peak["ici_bw"]

    terms = {"compute": t_compute, "memory": t_memory, "collective": t_collective}
    dominant = max(terms, key=terms.get)
    mf = model_flops(arch, shape_name)
    hlo_global = flops_dev * chips
    bound = max(terms.values())
    # roofline fraction: useful-FLOPs time at peak vs the dominant term
    t_useful = mf / (chips * peak["flops_bf16"])
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": rec["mesh"],
        "device_kind": rec["device_kind"],
        "tag": rec.get("tag", ""),
        "chips": chips,
        "hlo_flops_per_dev": flops_dev,
        "hlo_bytes_per_dev": bytes_dev,
        "hlo_bytes_raw_per_dev": bytes_raw,
        "collective_bytes_per_dev": coll_dev,
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_collective,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "roofline_fraction": t_useful / bound if bound > 0 else 0.0,
        "collectives": rec.get("collectives", {}),
    }


def load_all(tag: str = "") -> list[dict]:
    out = []
    for p in sorted(RESULTS_DIR.glob("*.json")):
        rec = json.loads(p.read_text())
        if rec.get("status") != "OK" or rec.get("tag", "") != tag:
            continue
        out.append(analyze_cell(rec))
    return out


def table(rows: list[dict]) -> str:
    hdr = (
        f"{'arch':24s} {'shape':12s} {'mesh':6s} {'comp(s)':>9s} {'mem(s)':>9s} "
        f"{'coll(s)':>9s} {'dom':>5s} {'useful':>7s} {'roofl':>6s}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:6s} "
            f"{r['t_compute_s']:9.3g} {r['t_memory_s']:9.3g} {r['t_collective_s']:9.3g} "
            f"{r['dominant'][:5]:>5s} {r['useful_ratio']:7.2f} {r['roofline_fraction']:6.3f}"
        )
    return "\n".join(lines)


def markdown_table(rows: list[dict]) -> str:
    lines = [
        "| arch | shape | mesh | compute (s) | memory (s) | collective (s) | dominant | useful ratio | roofline frac |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r['t_compute_s']:.3g} "
            f"| {r['t_memory_s']:.3g} | {r['t_collective_s']:.3g} | {r['dominant']} "
            f"| {r['useful_ratio']:.2f} | {r['roofline_fraction']:.4f} |"
        )
    return "\n".join(lines)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", default="")
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    rows = load_all(args.tag)
    if args.mesh:
        rows = [r for r in rows if r["mesh"] == args.mesh]
    print(markdown_table(rows) if args.markdown else table(rows))


if __name__ == "__main__":
    main()
