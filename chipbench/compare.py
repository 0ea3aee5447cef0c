"""The comparison that decides `correct`: served readouts against the plain
reference, unit by unit (a session, or a chunk of a stream).

Each unit's error is its largest absolute difference over its largest
reference magnitude. The numbers, over the compared units:

  rel_err_median   the median unit's error. A hidden spike that flips at
                   the threshold moves one unit's readout a lot, and such
                   flips are rare in a sound run, so the median reads the
                   rounding of the whole path and not the flips.
  diverged_share   share of units whose error exceeds `diverged_rel`
                   (set per configuration): how many units a flip, a
                   coarser rounding or a fault reached.

and, reported beside them, the largest absolute error, quantiles of the
units' errors and the shares above a ladder of thresholds.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

LADDER = (1e-6, 3e-6, 1e-5, 1e-4, 1e-3)


def readouts(outs: Sequence[np.ndarray], refs: Sequence[np.ndarray],
             diverged_rel: float) -> Dict[str, float]:
    if len(outs) != len(refs) or not outs:
        raise ValueError(f"{len(outs)} outputs against {len(refs)} "
                         "references")
    rel, worst = [], 0.0
    for o, r in zip(outs, refs):
        o = np.asarray(o, np.float64)
        r = np.asarray(r, np.float64)
        if o.shape != r.shape:
            raise ValueError(f"output {o.shape} against reference {r.shape}")
        scale = max(float(np.abs(r).max()), 1e-30)
        err = float(np.abs(o - r).max()) if o.size else 0.0
        if not np.isfinite(err):
            err = float("inf")
        rel.append(err / scale)
        worst = max(worst, err)
    rel = np.asarray(rel)
    return {"rel_err_median": float(np.median(rel)),
            "diverged_share": float(np.mean(rel > diverged_rel)),
            "max_err": worst,
            "rel_err_q90": float(np.quantile(rel, 0.9)),
            "rel_err_q99": float(np.quantile(rel, 0.99)),
            "share_above": {f"{t:g}": float(np.mean(rel > t))
                            for t in LADDER},
            "units": int(len(rel))}
