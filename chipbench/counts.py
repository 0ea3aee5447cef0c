"""Operations and least bytes of the INTEG calls, from their shapes and
their input spikes, and the least time they could take on a chip.

The counts are of the work that any implementation of the call must do,
so that no kernel can read above its roofline by skipping work the count
assumes: 2 operations per synaptic event (an input spike times the
weights it fans out to), not the dense multiply-adds. The least bytes are
the weights those events touch, the input at the smaller of one bit per
entry and one 4-byte index per spike, and the output once (f32).
"""

from __future__ import annotations

from typing import Dict

F32 = 4


def integ(events: int, weights_touched: int, rows: int, n_pre: int,
          n_post: int, spikes: int) -> Dict[str, float]:
    """One INTEG call, (rows, n_pre) spikes -> (rows, n_post) currents.

    events:          sum over input spikes of each one's fan-out
    weights_touched: weights read by at least one event
    spikes:          input spikes in the call
    """
    return {"ops": 2.0 * events,
            "bytes": float(F32 * weights_touched
                           + min(rows * n_pre / 8.0, F32 * spikes)
                           + F32 * rows * n_post)}


def dense_integ(spikes: int, active_inputs: int, fan_out: int, rows: int,
                n_pre: int) -> Dict[str, float]:
    """A dense weight matrix (`spikemm`): every input feeds `fan_out`
    outputs; `active_inputs` input channels spiked at least once."""
    return integ(spikes * fan_out, active_inputs * fan_out, rows, n_pre,
                 fan_out, spikes)


def least_time(c: Dict[str, float], peaks: Dict[str, float]) -> float:
    """Seconds at the chip's peak: the larger of the compute and the
    memory bound."""
    return max(c["ops"] / peaks["bf16_flops_per_s"],
               c["bytes"] / peaks["hbm_bytes_per_s"])

