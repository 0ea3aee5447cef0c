"""The one traffic generator: it reads a mix's parameters from
`traffic/<mix>.json` and makes the cell's inputs from the seed.

One kind of mix so far:

  sessions  open-loop streaming sessions. Arrivals are a Poisson process
            at `rate_per_s`; lengths are uniform over `length_steps`;
            each session streams `chunk_steps` at a time at real-time pace
            (`step_ms` a step) and then closes. Inputs replay rows of a
            seeded pool of spike rasters.

Every seed gets the same multiset of gaps, lengths and pool rows, in its
own order (stratified draws), so a seed changes the order of the work and
not its amount.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import numpy as np


# --- copied from the program's data/spikes.py (gen_shd_spikes) ----------------


def gen_shd_spikes(n: int, T: int = 100, seed=0, n_in: int = 700,
                   n_classes: int = 20, rate: float = 0.012
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """-> spikes (n, T, n_in) at ~`rate` mean density, labels (n,).

    Heidelberg SHD-style rasters: a class-dependent cochlear bump that
    drifts over the utterance. A copy of `repro.data.spikes.gen_shd_spikes`
    with the mean rate as a parameter, so the yardstick cannot move with
    the program."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, n_classes, n)
    spikes = np.zeros((n, T, n_in), np.float32)
    ch = np.arange(n_in)
    for i in range(n):
        c = labels[i]
        center = (c + 0.5) * n_in / n_classes
        width = n_in / n_classes * 1.5
        prof = np.exp(-0.5 * ((ch - center) / width) ** 2)
        tt = np.arange(T)[:, None]
        drift = center + (c % 5 - 2) * 1.2 * tt / T * width
        prof_t = np.exp(-0.5 * ((ch[None] - drift) / width) ** 2)
        p = rate * n_in / prof.sum() * prof_t
        spikes[i] = rng.random((T, n_in)) < p
    return spikes, labels


# --- sessions -----------------------------------------------------------------


def session_pool(mix: Dict[str, Any], n_in: int, rng) -> np.ndarray:
    """(pool, max length, n_in) float32 rasters of the mix's utterances."""
    spikes, _ = gen_shd_spikes(mix["pool"], T=mix["length_steps"][1],
                               seed=rng, n_in=n_in,
                               n_classes=mix["classes"],
                               rate=mix["input_rate"])
    return spikes


def session_schedule(mix: Dict[str, Any], span_s: float, rng,
                     rate_per_s: float = None) -> Dict[str, np.ndarray]:
    """Arrival time (s), length (steps) and pool row of every session that
    arrives within `span_s` of the start."""
    rate = float(rate_per_s if rate_per_s is not None else mix["rate_per_s"])
    n = max(1, int(math.ceil(rate * span_s)))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)          # Exp(rate) quantiles
    arrival = np.cumsum(gaps) - gaps[0]
    lo, hi = mix["length_steps"]
    lengths = rng.permutation(lo + np.floor(q * (hi - lo + 1)).astype(int))
    rows = np.concatenate([rng.permutation(mix["pool"])
                           for _ in range(-(-n // mix["pool"]))])[:n]
    keep = arrival < span_s
    return {"arrival": arrival[keep], "length": lengths[keep],
            "row": rows[keep]}


def session_events(sched: Dict[str, np.ndarray], mix: Dict[str, Any]
                   ) -> Dict[str, np.ndarray]:
    """The open-loop schedule as events sorted by due time: chunk j of
    session i is due once its last step has been spoken,
    arrival + min((j+1) * chunk, length) * step. Chunk -1 opens."""
    step_s = mix["step_ms"] / 1000.0
    ck = mix["chunk_steps"]
    due, sess, chunk = [], [], []
    for i, (a, L) in enumerate(zip(sched["arrival"], sched["length"])):
        n_ck = -(-int(L) // ck)
        due.append(a)
        sess.append(i)
        chunk.append(-1)
        for j in range(n_ck):
            due.append(a + min((j + 1) * ck, int(L)) * step_s)
            sess.append(i)
            chunk.append(j)
    due = np.asarray(due)
    order = np.lexsort((np.asarray(chunk), due))
    return {"due": due[order], "session": np.asarray(sess)[order],
            "chunk": np.asarray(chunk)[order]}
