"""§Roofline: three-term roofline per (arch × shape × mesh) from the
dry-run artifacts (artifacts/dryrun/*.json).

    compute    = flops / peak_FLOP/s            (per chip)
    memory     = hbm_bytes / HBM_bw             (per chip)
    collective = wire_bytes / link_bw           (per chip; ICI links)

Also reports MODEL_FLOPS/HLO_FLOPs (useful-compute ratio) and the
dominant term.  Run after ``python -m repro.launch.dryrun --all``.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional

from benchmarks.common import Table
from repro.core.topology import DEVICES, V5E

PEAK_FLOPS_BF16 = DEVICES[V5E].peak_flops_bf16
HBM_BW = DEVICES[V5E].hbm_bw
ICI_BW = DEVICES[V5E].ici.bandwidth
DCN_BW = DEVICES[V5E].dcn.bandwidth

# ~50 GB/s/link; a v5e chip drives 4 ICI links concurrently on the torus,
# but a single collective schedule typically saturates 2 (bidirectional
# ring on one axis).  We charge the conservative single-axis figure.
EFFECTIVE_LINK_BW = 2 * ICI_BW


def load_records(art_dir: str = "artifacts/dryrun",
                 variants: bool = False) -> List[Dict]:
    recs = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        is_variant = "@" in os.path.basename(path)
        if is_variant != variants:
            continue
        with open(path) as f:
            r = json.load(f)
        if r.get("ok"):
            recs.append(r)
    return recs


def roofline_terms(rec: Dict) -> Dict[str, float]:
    an = rec["analysis"]
    devices = rec["devices"]
    compute = an["flops"] / PEAK_FLOPS_BF16
    memory = an.get("hbm_bytes_kernel_adjusted", an["hbm_bytes"]) / HBM_BW
    if "wire_bytes_ici" in an:
        collective = (an["wire_bytes_ici"] / EFFECTIVE_LINK_BW
                      + an.get("wire_bytes_dcn", 0.0) / DCN_BW)
    else:
        collective = an["wire_bytes"] / EFFECTIVE_LINK_BW
    dominant = max(("compute", compute), ("memory", memory),
                   ("collective", collective), key=lambda kv: kv[1])
    model_fl = rec.get("model_flops_global", 0.0) / devices
    return {
        "compute_s": compute, "memory_s": memory, "collective_s": collective,
        "dominant": dominant[0], "bound_s": dominant[1],
        "useful_ratio": (model_fl / an["flops"]) if an["flops"] else 0.0,
        "roofline_fraction": (model_fl / PEAK_FLOPS_BF16) / dominant[1]
        if dominant[1] else 0.0,
    }


def report(art_dir: str = "artifacts/dryrun",
           mesh: Optional[str] = "single") -> Table:
    t = Table(f"§Roofline ({mesh} pod; seconds/step/device)",
              ["arch", "shape", "compute", "memory", "collective",
               "bound", "useful", "roofline%"])
    for rec in load_records(art_dir):
        if mesh and rec["mesh"] != mesh:
            continue
        r = roofline_terms(rec)
        t.add(rec["arch"], rec["shape"],
              f"{r['compute_s']:.3e}", f"{r['memory_s']:.3e}",
              f"{r['collective_s']:.3e}", r["dominant"],
              f"{r['useful_ratio']:.2f}",
              f"{100 * r['roofline_fraction']:.1f}")
    return t


def main():
    for mesh in ("single", "multi"):
        report(mesh=mesh).print()
        print()


if __name__ == "__main__":
    main()
