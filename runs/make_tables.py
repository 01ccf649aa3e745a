"""Render a markdown summary table of the partition run manifests.

    PYTHONPATH=src python runs/make_tables.py
"""

import glob
import os


def manifest_table(dirname="runs"):
    """Partition-run manifests (repro.obs JSONL, written by
    ``PartitionContext.export_manifest`` / ``REPRO_OBS_DIR``) → one summary
    row each: name, commit, wall, the top stages by wall share, and the
    solver totals the trace aggregated.  Reads the manifests through
    ``obs.load_manifest`` instead of re-parsing span lines by hand."""
    try:
        from repro.obs import load_manifest
    except ImportError:       # run without PYTHONPATH=src: skip quietly
        return
    files = sorted(glob.glob(os.path.join(dirname, "*.jsonl")))
    rows = []
    for f in files:
        try:
            header, root = load_manifest(f)
        except (ValueError, OSError):
            continue
        total = max(root.seconds, 1e-12)
        stages = sorted(((c.seconds / total, c.name) for c in root.children),
                        reverse=True)
        top = ", ".join(f"{n} {s:.0%}" for s, n in stages[:3])
        m = header.get("totals", {}).get("metrics", {})
        solves = m.get("fiedler_solves")
        iters = (m.get("lanczos_restarts", 0)
                 + m.get("inverse_outer_iters", 0))
        rows.append((header.get("created", ""), header.get("name", "?"),
                     header.get("git_sha", "?")[:9], total, top,
                     "—" if solves is None else f"{solves:.0f}",
                     f"{iters:.0f}" if iters else "—"))
    if not rows:
        return
    print("\n### Partition run manifests (runs/*.jsonl)\n")
    print("| created | run | commit | wall s | top stages (share) | "
          "solves | iters |")
    print("|---|---|---|---|---|---|---|")
    for created, name, sha, total, top, solves, iters in sorted(rows):
        print(f"| {created} | {name} | {sha} | {total:.3f} | {top} | "
              f"{solves} | {iters} |")



if __name__ == "__main__":
    manifest_table()
