"""Emit the §Roofline table from the dry-run artifacts (no recompiles).

``--hw`` re-derives the three terms from the recorded per-device FLOPs /
bytes / wire-bytes counters under a different ``HardwareSpec`` (the
counters are hardware-independent; only the rates change), so one set of
dry-run artifacts can be read as a v5e, v4, A100 or CPU table.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from benchmarks.common import emit
from repro.launch import roofline as rl

ARTIFACTS = Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hw", choices=sorted(rl.HARDWARE), default=None,
                    help="re-rate the recorded counters for this hardware "
                         "(default: report the terms as recorded)")
    # benchmarks/run.py calls main() with no argv; don't swallow its flags
    args = ap.parse_args(argv if argv is not None else [])
    hw = rl.HARDWARE[args.hw] if args.hw else None
    if not ARTIFACTS.exists():
        emit("roofline_table_missing", 0.0,
             "run python -m repro.launch.dryrun --all --mesh both first")
        return
    for p in sorted(ARTIFACTS.glob("*.json")):
        r = json.loads(p.read_text())
        if r.get("status") != "ok":
            if r.get("status") == "skipped":
                emit(f"roofline_{r['cell']}", 0.0, "skipped")
            continue
        t = r["roofline"]
        if hw is not None:
            t = dict(t)
            rerated = rl.terms_from_cost(
                t["flops_per_device"], t["bytes_per_device"],
                t["wire_bytes_per_device"], hw)
            t.update(t_compute=rerated.t_compute, t_memory=rerated.t_memory,
                     t_collective=rerated.t_collective,
                     bottleneck=rerated.bottleneck)
        dom = max(t["t_compute"], t["t_memory"], t["t_collective"])
        frac = t["t_compute"] / max(dom, 1e-12)
        hw_tag = f";hw={hw.name}" if hw is not None else ""
        emit(f"roofline_{r['cell']}", dom * 1e6,
             f"T_comp={t['t_compute'] * 1e3:.1f}ms;"
             f"T_mem={t['t_memory'] * 1e3:.1f}ms;"
             f"T_coll={t['t_collective'] * 1e3:.1f}ms;"
             f"bound={t['bottleneck']};roofline_frac={frac:.3f};"
             f"useful_ratio={t['useful_flops_ratio'] or 0:.2f};"
             f"mem_GB={r['memory']['peak_est_bytes'] / 1e9:.1f}{hw_tag}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import sys
    main(sys.argv[1:])
