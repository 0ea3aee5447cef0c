"""Open-loop streaming sessions through the program's serve engine.

Sessions arrive on the schedule of a `sessions` mix whether or not the
engine keeps up. The harness makes the calls `StreamClient.stream` makes
(`open`, `submit`, `close`, `step`, `outputs`, `retire`) as the schedule
falls due, and steps the engine whenever a window is ready. It reads the
engine's cohorts by wrapping the scheduler's `next_cohort` of this one
engine, which changes nothing the engine does.

Times are on the host clock, from the start of the schedule:

  due      when a window's last input was due: the due time of the chunk
           that completes it (or of the final chunk, for the partial tail)
  ready    when that chunk was submitted
  start    when the `step()` that served the window began
  done     when that `step()` returned: its outputs are on the host

End-to-end: `window_p90_ms` is the 90th percentile of done - due over
every window due inside the measured window, delivered in it or after it
(an undelivered window counts with the time waited); `session_steps_per_s`
counts the input steps whose outputs were delivered inside the window.
The other percentiles go to the result's `info`.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

import compare
import counts
import generator
import harness

WEIGHTS, TRAFFIC, SCHEDULE = 0, 1, 3


class Engine:
    """The engine under test with the harness's view of its cohorts."""

    def __init__(self, cell, seed: int):
        from repro.serve import EngineConfig, make_engine

        cfg = cell.cfg
        self.ref = cell.module("reference")
        self.w = self.ref.make_weights(cfg, harness.jax_key(seed, WEIGHTS))
        nodes, params = cell.module("model").build(cfg, self.w)
        sv = cfg["serve"]
        self.eng = make_engine(nodes, params, EngineConfig(
            window=sv["window"], capacity=sv["capacity"],
            queue_limit=sv["queue_limit"]))
        self.cohort: List = []
        orig = self.eng.scheduler.next_cohort

        def next_cohort(capacity):
            c = orig(capacity)
            self.cohort = [(s.sid, valid) for s, _, valid in c]
            return c

        self.eng.scheduler.next_cohort = next_cohort

    def warm(self, n_in: int) -> None:
        """Compile and run the cohort step once: one session, one window."""
        eng = self.eng
        sid = eng.open("warm")
        eng.submit(sid, np.zeros((eng.cfg.window, n_in), np.float32))
        eng.close(sid)
        while not eng.finished(sid):
            eng.step()
        eng.retire(sid)


def drive(E: Engine, pool: np.ndarray, sched: Dict[str, np.ndarray],
          mix: Dict[str, Any], t_open: float, t_close: float, spans,
          keep=(), drain_s: float = 60.0) -> Dict[str, Any]:
    """Run the schedule open-loop; returns the raw records."""
    eng = E.eng
    W = eng.cfg.window
    ck = mix["chunk_steps"]
    L = sched["length"].astype(int)
    rows = sched["row"]
    n = len(L)
    n_win = -(-L // W)
    ev = generator.session_events(sched, mix)
    due, sess, chk = ev["due"], ev["session"], ev["chunk"]
    n_ev = len(due)
    submit_t = np.full((n, int(-(-L.max() // ck))), np.nan)
    submit_ev = np.full(n_ev, np.nan)
    served = np.zeros(n, int)
    keep = set(int(i) for i in keep)
    outputs: Dict[int, np.ndarray] = {}
    wins: List = []                  # (session, window, valid, start, done)
    steps: List = []                 # (start, done, sessions, ready before)
    busy = [0.0, 0.0]          # window's host seconds in submits, in steps
    window = None
    opened = closed = False
    clock = time.perf_counter
    t0 = clock()
    deadline = t_close + drain_s
    e = 0
    while True:
        now = clock() - t0
        if now > deadline:
            break
        if not opened and now >= t_open:
            window = spans("window")      # a span starts when it is made
            window.__enter__()
            opened = True
        if opened and not closed and now >= t_close:
            window.__exit__(None, None, None)
            closed = True
        submitted = e < n_ev and due[e] <= now
        if submitted:
            with spans("submit"):
                while e < n_ev and due[e] <= now:
                    i, j = int(sess[e]), int(chk[e])
                    if j < 0:
                        eng.open(str(i))
                    else:
                        r0 = j * ck
                        r1 = min(r0 + ck, L[i])
                        eng.submit(str(i), pool[rows[i], r0:r1])
                        if r1 == L[i]:
                            eng.close(str(i))
                        submit_t[i, j] = clock() - t0
                    submit_ev[e] = clock() - t0
                    e += 1
            if opened and not closed:
                busy[0] += clock() - t0 - now
        ready = eng.scheduler.ready_count
        if ready:
            ts = clock() - t0
            with spans("step"):
                eng.step()
            te = clock() - t0
            if opened and not closed:
                busy[1] += te - ts
            steps.append((ts, te, len(E.cohort), ready))
            for sid, valid in E.cohort:
                i = int(sid)
                wins.append((i, served[i], valid, ts, te))
                served[i] += 1
                if served[i] == n_win[i]:
                    if i in keep:
                        outputs[i] = eng.outputs(sid)
                    eng.retire(sid)
            E.cohort = []
            continue
        if submitted:
            continue
        if e >= n_ev:
            break
        with spans("wait"):
            time.sleep(max(0.0, min(due[e] - now, 0.002)))
    if opened and not closed:
        window.__exit__(None, None, None)
    t_end = clock() - t0
    return {"wins": np.asarray(wins, float).reshape(-1, 5),
            "steps": np.asarray(steps, float).reshape(-1, 4),
            "submit_t": submit_t, "submit_ev": submit_ev, "events": ev,
            "served": served, "n_win": n_win, "outputs": outputs,
            "t_end": t_end, "submit_s": busy[0], "step_total_s": busy[1],
            "t_open": t_open, "t_close": t_close,
            "W": W, "ck": ck, "step_s": mix["step_ms"] / 1000.0,
            "sched": sched}


def window_due(rec: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """Every window of every session: its session, index, due time and
    the chunk that completes it."""
    sched, W, ck, step_s = rec["sched"], rec["W"], rec["ck"], rec["step_s"]
    L = sched["length"].astype(int)
    sess = np.repeat(np.arange(len(L)), rec["n_win"])
    k = np.concatenate([np.arange(m) for m in rec["n_win"]])
    end = np.minimum(W * (k + 1), L[sess])          # steps through window k
    j = (end - 1) // ck                             # the chunk completing it
    due = sched["arrival"][sess] + np.minimum((j + 1) * ck, L[sess]) * step_s
    return {"session": sess, "k": k, "due": due, "chunk": j,
            "valid": end - W * k}


def summarize(rec: Dict[str, Any], seconds: float) -> Dict[str, Any]:
    """End-to-end numbers and the per-layer records of one drive."""
    lo, hi = rec["t_open"], rec["t_close"]
    wd = window_due(rec)
    idx = {(int(s), int(k)): n for n, (s, k) in
           enumerate(zip(wd["session"], wd["k"]))}
    done = np.full(len(wd["due"]), np.nan)
    start = np.full(len(wd["due"]), np.nan)
    for i, k, valid, ts, te in rec["wins"]:
        n = idx[(int(i), int(k))]
        done[n], start[n] = te, ts
    in_win = (wd["due"] >= lo) & (wd["due"] < hi)
    lat = np.where(np.isnan(done), rec["t_end"], done) - wd["due"]
    ready = rec["submit_t"][wd["session"], wd["chunk"]]
    delivered = ~np.isnan(done) & (done >= lo) & (done < hi)
    ev = rec["events"]
    chunk_ev = (ev["chunk"] >= 0) & (ev["due"] >= lo) & (ev["due"] < hi)
    steps = rec["steps"]
    st_in = (steps[:, 0] >= lo) & (steps[:, 0] < hi)
    last_due = np.zeros(len(rec["n_win"]))
    np.maximum.at(last_due, wd["session"], wd["due"])
    attempted = (last_due >= lo) & (last_due < hi)
    finished = rec["served"] == rec["n_win"]
    waited = in_win & ~np.isnan(start)
    q50, q90, q95, q99 = 1e3 * np.percentile(lat[in_win], [50, 90, 95, 99])
    return {
        "window_p90_ms": float(q90),
        "latency_ms": {"p50": float(q50), "p90": float(q90),
                       "p95": float(q95), "p99": float(q99),
                       "max": 1e3 * float(lat[in_win].max())},
        "session_steps_per_s": float(wd["valid"][delivered].sum()) / seconds,
        "windows_due": int(in_win.sum()),
        "windows_undelivered": int((in_win & np.isnan(done)).sum()),
        "gen_lag_s": rec["submit_ev"][chunk_ev] - ev["due"][chunk_ev],
        "queue_wait_s": start[waited] - ready[waited],
        "step_s": steps[st_in, 1] - steps[st_in, 0],
        "steps_in_window": st_in,
        "attempted": np.flatnonzero(attempted),
        "failed": int((attempted & ~finished).sum()),
        "ready_depth": steps[st_in, 3],
    }


def step_counts(rec: Dict[str, Any], pool: np.ndarray, cfg: Dict[str, Any],
                rows_per_call: int) -> List[Dict[str, float]]:
    """INTEG work of every cohort step in the window (both calls): the
    input layer's events from the cohort's input spikes; the readout's
    input spikes are not visible from outside the step, so it is counted
    with its output alone, a lower bound."""
    sched, W = rec["sched"], rec["W"]
    lo, hi = rec["t_open"], rec["t_close"]
    fan = cfg["n_branches"] * cfg["n_hidden"]
    by_step: Dict[float, List] = {}
    for i, k, valid, ts, te in rec["wins"]:
        if lo <= ts < hi:
            by_step.setdefault(ts, []).append((int(i), int(k), int(valid)))
    out = []
    for ts in sorted(by_step):
        spikes, active = 0, np.zeros(cfg["n_in"], bool)
        for i, k, valid in by_step[ts]:
            x = pool[sched["row"][i], W * k:W * k + valid]
            spikes += int(x.sum())
            active |= x.any(axis=0)
        c_in = counts.dense_integ(spikes, int(active.sum()), fan,
                                  rows_per_call, cfg["n_in"])
        c_ro = counts.integ(0, 0, rows_per_call, cfg["n_hidden"],
                            cfg["n_out"], 0)
        out.append({"start": ts, "input": c_in, "readout": c_ro})
    return out


def run(ctx) -> Dict[str, Any]:
    cell, seed, seconds = ctx.cell, ctx.seed, ctx.seconds
    cfg, mix = cell.cfg, cell.traffic
    E = Engine(cell, seed)
    pool = generator.session_pool(mix, cfg["n_in"],
                                  harness.np_rng(seed, TRAFFIC))
    t_open = float(mix["warmup_s"])
    t_close = t_open + seconds
    sched = generator.session_schedule(mix, t_close,
                                       harness.np_rng(seed, SCHEDULE))
    E.warm(cfg["n_in"])

    # every session whose last window falls due inside the window is
    # compared, the longest among them
    wd = window_due(rec_stub(sched, mix, E.eng.cfg.window))
    last_due = np.zeros(len(sched["length"]))
    np.maximum.at(last_due, wd["session"], wd["due"])
    sample = np.flatnonzero((last_due >= t_open)
                            & (last_due < t_close)).tolist()

    if ctx.trace:
        ctx.start_trace()
    ctx.mark_setup(t_open)
    rec = drive(E, pool, sched, mix, t_open, t_close, ctx.spans,
                keep=sample)
    if ctx.trace:
        ctx.stop_trace()
    mem = ctx.memory_peak()
    s = summarize(rec, seconds)

    # the program's state goes before the reference runs
    w = E.w
    del E
    gc.collect()
    outs, refs, ctl = reference_check(cfg, w, pool, sched, sample,
                                      rec["outputs"], ctx.control,
                                      ctx.cell.module("reference"))
    res = {
        "e2e": {"window_p90_ms": s["window_p90_ms"],
                "session_steps_per_s": s["session_steps_per_s"]},
        "attempted": int(len(s["attempted"])),
        "failed": s["failed"],
        "memory_peak_bytes": mem,
        "missing_in_sample": int(sum(i not in rec["outputs"]
                                     for i in sample)),
        "compare": (compare.readouts(outs, refs, cfg["correct"]["diverged_rel"])
                    if outs else None),
        "control": ctl,
        "records": {"summary": s, "rec": rec, "pool": pool, "cfg": cfg},
        "info": {"latency_ms": s["latency_ms"],
                 "step_ms_max": 1e3 * float(s["step_s"].max(initial=0.0)),
                 "windows_due": s["windows_due"],
                 "windows_undelivered": s["windows_undelivered"],
                 "sessions_scheduled": int(len(sched["length"])),
                 "steps_in_window": int(s["steps_in_window"].sum()),
                 "sample": len(sample)},
    }
    if ctx.trace:
        sv = cfg["serve"]
        res["records"]["counts"] = step_counts(
            rec, pool, cfg, sv["window"] * sv["capacity"])
    return res


def rec_stub(sched, mix, W: int) -> Dict[str, Any]:
    L = sched["length"].astype(int)
    return {"sched": sched, "W": W, "ck": mix["chunk_steps"],
            "step_s": mix["step_ms"] / 1000.0, "n_win": -(-L // W)}


def reference_check(cfg, w, pool, sched, sample, outputs, control, ref,
                    block: int = 512):
    """The plain reference over every compared session's whole input, in
    blocks of sessions of one shape (zero input past a session's end)."""
    if not sample:
        return [], [], None
    L = sched["length"].astype(int)
    T = pool.shape[1]
    w_ctl = ref.lower_precision(w) if control else None
    refs, ctls = [], []
    for b0 in range(0, len(sample), block):
        part = sample[b0:b0 + block]
        x = np.zeros((T, block, cfg["n_in"]), np.float32)
        for b, i in enumerate(part):
            x[:L[i], b] = pool[sched["row"][i], :L[i]]
        y = np.asarray(ref.forward(cfg, w, x))
        refs += [y[:L[i], b] for b, i in enumerate(part)]
        if control:
            yc = np.asarray(ref.forward(cfg, w_ctl, x))
            ctls += [yc[:L[i], b] for b, i in enumerate(part)]
    outs = [outputs.get(i, np.full_like(r, np.nan))
            for i, r in zip(sample, refs)]
    ctl = (compare.readouts(ctls, refs, cfg["correct"]["diverged_rel"])
           if control else None)
    return outs, refs, ctl
