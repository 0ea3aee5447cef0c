"""serve — batched prompt loop + multi-tenant streaming session engine.

Two entry points live here:

  * `loop.py` — the request/response prompt path (`generate`,
    `generate_resilient`): pad a batch of prompts, run them to
    completion, return tokens.
  * the streaming stack — `sessions.py` / `scheduler.py` / `engine.py` /
    `metrics.py`: long-lived stateful sessions continuously batched into
    fixed-shape cohorts over one resident jitted `plan.run` window step,
    with an LRU byte-budgeted state cache (host spill + bit-identical
    restore) and operational metrics. See `engine.py` for the design;
    `client.py` adds the generator-based `StreamClient` facade for
    application code that wants chunks-in / windows-out.
"""

from repro.serve.loop import ServeConfig, ServeResult, Request, generate
from repro.serve.client import StreamClient
from repro.serve.engine import EngineConfig, BatchedEngine, make_engine
from repro.serve.metrics import Histogram, ServeMetrics
from repro.serve.scheduler import Scheduler
from repro.serve.sessions import Session, StateCache

__all__ = [
    "ServeConfig", "ServeResult", "Request", "generate",
    "EngineConfig", "BatchedEngine", "make_engine",
    "Histogram", "ServeMetrics", "Scheduler", "Session", "StateCache",
    "StreamClient",
]
