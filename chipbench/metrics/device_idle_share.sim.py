"""Share of the chunks' host time in which no operation ran on the
device: 1 - device-busy union inside the `step` spans (one a chunk) /
their summed length, from the profiler trace; the serve cell's reader,
whose `step` spans are one an engine step."""

import os

import harness

read = harness.load_module(os.path.join(
    harness.BENCH_DIR, "metrics", "device_idle_share.serve.py")).read
