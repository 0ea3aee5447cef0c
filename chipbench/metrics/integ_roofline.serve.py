"""INTEG kernels of the resident step (`spikemm`, the branch-flattened
input layer and the readout) against their roofline: the least time the
chip needs for the calls of the window (`counts.py`) over the device
time of those kernels in the trace."""

import counts

# the custom call of the dense spikemm channel's Pallas kernel
# (`_spikemm_kernel`, called from `spikemm_pallas`), as the trace names the
# instruction
PATTERNS = [r"^%spikemm_pallas[.\d]* = "]


def read(view):
    c = view.records.get("counts")
    dev_s, n = view.trace.kernel_s(PATTERNS)
    if not c or n == 0 or dev_s <= 0:
        return None
    least = sum(counts.least_time(s["input"], view.peaks)
                + counts.least_time(s["readout"], view.peaks) for s in c)
    return 100.0 * least / dev_s
