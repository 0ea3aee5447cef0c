"""A free-running simulation streamed chunk by chunk through the
program's `plan.run_stream` (a `stream` mix).

One network, `batch` 1, no external input: the configuration's own
background drives it, at the mix's `bg_rate_hz` where the mix gives one
(else the configuration's). The harness draws the synapses, the background keys
and the initial potentials from the seed (the family's reference file),
the family's model file builds the program from them, and the stream runs
`chunk_steps` steps a call with every neuron's spikes read back each
chunk as a (step, neuron) list. The first `warmup_steps` (the initial
transient, and every compile) run in set-up.

End-to-end: `session_steps_per_s` counts the simulated steps whose spikes
reached the host inside the window, over the seconds from the window's
opening to the last of them (the one simulation is the one session). A
chunk is 0.8 % of a 51 s window: over the window's whole length, whether
the last chunk ended inside it would move the rate by that much. With `--trace 1` the window is the first
`trace_seconds` only, so the trace stays small.

`correct`: at the start of `sample_chunks` chunks spread over the window
the harness copies the program's state; after the window, with the
program gone, the plain reference runs each of those chunks from its copy
and is compared with what the program produced: every neuron's spike
steps and its V at the chunk's end.
"""

from __future__ import annotations

import gc
import itertools
import sys
import time
from typing import Any, Dict, List

import numpy as np

import harness

WEIGHTS, BACKGROUND, V0 = 0, 1, 2


def log(ctx, what: str) -> None:
    """A phase boundary on stderr, seconds since the process began."""
    print(f"simulate: {time.perf_counter() - ctx.t0:.1f} s {what}",
          file=sys.stderr, flush=True)


def _ref_state(state, pops) -> Dict[str, Any]:
    """A program state as the reference holds it (batch row 0)."""
    import jax.numpy as jnp
    return {"v": jnp.concatenate([state[p]["v"][0] for p in pops]),
            "i": jnp.concatenate([state[p]["i"][0] for p in pops]),
            "ref": jnp.concatenate([state[p]["ref"][0] for p in pops]),
            "ring": jnp.concatenate([state[p]["arr"][:, 0] for p in pops],
                                    axis=1),
            "t": int(state[pops[0]]["t"])}


def compare_chunks(outs: List[Dict[str, Any]], refs: List[Dict[str, Any]],
                   n: int, scale_mv: float, diverged_rel: float
                   ) -> Dict[str, Any]:
    """Each compared chunk's neurons: |dV| at its end over `scale_mv`, and
    diverged where the spike steps differ or |dV| / scale exceeds
    `diverged_rel`. The numbers over every neuron of every chunk."""
    rel, bad, flips = [], [], 0
    for o, r in zip(outs, refs):
        dv = np.abs(np.asarray(o["v"], np.float64)
                    - np.asarray(r["v"], np.float64)) / scale_mv
        dv = np.where(np.isfinite(dv), dv, np.inf)
        a = set(zip(o["steps"].tolist(), o["neurons"].tolist()))
        b = set(zip(r["steps"].tolist(), r["neurons"].tolist()))
        wrong = np.zeros(n, bool)
        for _, j in a ^ b:
            wrong[j] = True
        flips += int(wrong.sum())
        rel.append(dv)
        bad.append(wrong | (dv > diverged_rel))
    rel, bad = np.concatenate(rel), np.concatenate(bad)
    return {"rel_err_median": float(np.median(rel)),
            "diverged_share": float(np.mean(bad)),
            "max_dv_mv": float(rel.max() * scale_mv),
            "rel_err_q90": float(np.quantile(rel, 0.9)),
            "rel_err_q99": float(np.quantile(rel, 0.99)),
            "spike_mismatch_neurons": flips,
            "units": int(rel.size)}


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from repro.core import plan

    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    cfg, mix = dict(cell.cfg), cell.traffic
    cfg["bg_rate_hz"] = float(mix.get("bg_rate_hz", cfg["bg_rate_hz"]))
    model = cell.module("model")      # a program without the model fails here
    ref = cell.module("reference")
    pops = list(cfg["populations"])
    n = list(cfg["n"])
    N = int(sum(n))
    base = np.concatenate([[0], np.cumsum(n)])
    syn = ref.make_synapses(cfg, harness.np_rng(seed, WEIGHTS))
    keys = ref.background_keys(cfg, harness.jax_key(seed, BACKGROUND))
    v0 = ref.initial_v(cfg, harness.jax_key(seed, V0))
    n_syn = sum(int(jnp.sum(pj["rows"][3])) for pj in syn)
    log(ctx, f"{n_syn} synapses drawn")
    nodes, params, state = model.build(cfg, syn, keys, v0)
    log(ctx, "program built")
    T, B = int(mix["chunk_steps"]), int(mix["batch"])
    x = jnp.zeros((T, B, 0), jnp.float32)
    stream = plan.run_stream(nodes, params, itertools.repeat(x),
                             state=state, spikes="list")
    del state, v0
    dropped = 0
    for k in range(int(mix["warmup_steps"]) // T):
        state, sp = next(stream)
        dropped += sp.counts["spikes"] - len(sp.step)
        log(ctx, f"warm-up chunk {k}: {sp.counts['spikes']} spikes")
    jax.block_until_ready(state)
    log(ctx, "warm-up done")

    span_s = min(seconds, float(mix["trace_seconds"])) if ctx.trace \
        else float(seconds)
    n_sample = int(mix["sample_chunks"])
    # the sampled chunks start at 0, 20, 40 and 60 % of the window (for
    # four), so the last one fits even where chunks are long
    marks = [0.8 * k / n_sample * span_s for k in range(n_sample)]
    chunks, samples = [], []
    per_pop = np.zeros(len(n), np.int64)
    if ctx.trace:
        ctx.start_trace()
    ctx.mark_setup(0.0)
    clock = time.perf_counter
    t_open = clock()
    window = ctx.spans("window")
    window.__enter__()
    while clock() - t_open < span_s:
        take = (len(samples) < n_sample
                and clock() - t_open >= marks[len(samples)])
        if take:
            start = jax.tree_util.tree_map(jnp.copy, state)
        with ctx.spans("step"):
            ts = clock() - t_open
            state, sp = next(stream)
            te = clock() - t_open
        chunks.append((ts, te, len(sp.step), sp.counts))
        dropped += sp.counts["spikes"] - len(sp.step)
        if te <= span_s:
            per_pop += np.bincount(np.searchsorted(base, sp.neuron, "right")
                                   - 1, minlength=len(n))
        if take:
            samples.append({"start": start, "steps": sp.step,
                            "neurons": sp.neuron,
                            "v": jnp.concatenate([state[p]["v"][0]
                                                  for p in pops])})
    window.__exit__(None, None, None)
    if ctx.trace:
        ctx.stop_trace()
    mem = ctx.memory_peak()
    t_final = int(state[pops[0]]["t"])
    ring_entries = int(np.prod(state[pops[0]]["arr"].shape[:2])) * N
    stream.close()
    del state, stream, nodes, params
    gc.collect()
    log(ctx, f"window done: {len(chunks)} chunks")

    done = [c for c in chunks if c[1] <= span_s]
    steps = T * len(done)
    done_s = done[-1][1] if done else span_s
    rates = (per_pop / (np.asarray(n) * steps * cfg["dt_ms"] * 1e-3)
             if steps else np.zeros(len(n)))

    # the reference from each sampled chunk's start, the program gone
    scale = cfg["neuron"]["v_th_mV"] - cfg["neuron"]["e_l_mV"]
    dr = cfg["correct"]["diverged_rel"]
    outs, refs, ctls = [], [], []
    sound = ref.Reference(cfg, syn, keys)
    control = (ref.Reference(cfg, ref.lower_precision(syn), keys)
               if ctx.control else None)
    for s in samples:
        rs = _ref_state(s["start"], pops)
        s["start"] = None
        fin, st, nr = sound.run(rs, T)
        outs.append({"v": s["v"], "steps": s["steps"],
                     "neurons": s["neurons"]})
        refs.append({"v": fin["v"], "steps": st, "neurons": nr})
        if control is not None:
            fin, st, nr = control.run(rs, T)
            ctls.append({"v": fin["v"], "steps": st, "neurons": nr})
    log(ctx, "reference done")
    compared = (dict(compare_chunks(outs, refs, N, scale, dr),
                     spikes_dropped=int(dropped)) if outs else None)
    ctl = (dict(compare_chunks(ctls, refs, N, scale, dr), spikes_dropped=0)
           if ctls else None)
    in_win = [c for c in chunks if c[0] < span_s]
    counts = [c[3] for c in done]
    return {
        "e2e": {"session_steps_per_s": steps / done_s},
        "attempted": len(in_win),
        "failed": 0,
        "memory_peak_bytes": mem,
        "missing_in_sample": n_sample - len(samples),
        "compare": compared,
        "control": ctl,
        "records": {"chunks": chunks, "counts": counts, "window_s": span_s,
                    "T": T, "N": N, "ring_entries": ring_entries},
        "info": {
            "rates_hz": dict(zip(pops, [float(r) for r in rates])),
            "published_rates_hz": dict(zip(pops,
                                           cfg["published_rates_hz"])),
            "chunks_in_window": len(done), "steps_in_window": steps,
            "spikes_per_step": (sum(c["spikes"] for c in counts) / steps
                                if steps else 0.0),
            "events_per_step": (sum(c["events"] for c in counts) / steps
                                if steps else 0.0),
            "extra_pass_steps": sum(c["extra_pass_steps"] for c in counts),
            "chunk_ms_max": 1e3 * max((c[1] - c[0] for c in chunks),
                                      default=0.0),
            "synapses": n_syn,
            "final_step": t_final, "sample": len(samples)},
    }
