"""Roofline summary over the dry-run artifacts (see EXPERIMENTS.md).
Requires `python -m repro.launch.dryrun --all` to have populated
benchmarks/results/dryrun/."""

from __future__ import annotations


def run():
    from repro.launch import roofline

    rows = []
    cells = roofline.load_all()
    if not cells:
        return [("roofline", 0.0, "no dryrun artifacts; run repro.launch.dryrun --all")]
    for mesh in ("single", "multi"):
        sub = [c for c in cells if c["mesh"] == mesh]
        if not sub:
            continue
        n_cells = len(sub)
        dom = {}
        for c in sub:
            dom[c["dominant"]] = dom.get(c["dominant"], 0) + 1
        best = max(sub, key=lambda c: c["roofline_fraction"])
        worst = min(sub, key=lambda c: c["roofline_fraction"])
        rows.append(
            (
                f"roofline_{mesh}",
                0.0,
                f"cells={n_cells};dominant={dom};best={best['arch']}/{best['shape']}"
                f"={best['roofline_fraction']:.3f};worst={worst['arch']}/{worst['shape']}"
                f"={worst['roofline_fraction']:.4f}",
            )
        )
    return rows
