"""``repro.serving`` — the stateful streaming serving subsystem.

The paper's deployment story is a single real-time sensor stream (§6:
32 873 samples/s); this package is the production form of that story —
many named client streams multiplexed onto an ``Accelerator`` session
(or, behind a cluster, one per replica), each stream's recurrent carry
held across windows in a device-resident slot table, waves
double-buffered against device compute, tail latency bounded by a
deadline, and the paper's metrics (samples/s, GOP/s/W, latency
percentiles) measured where the server actually runs.

Public surface (docs/SERVING.md is the deployment guide):

  * :class:`StreamServer` — submit/poll/flush/close over named streams.
  * :class:`ServingConfig` — batch, deadline, backpressure, carry-table
    capacity, resilience and overload policies.
  * :class:`StreamResult` — (stream_id, seq, prediction) rows, plus the
    structured error/``state_reset`` reliability flags.
  * :class:`DeviceStateStore` / :class:`SlotAllocator` — the carry
    store (``repro.serving.device_state``): carries live in an
    on-accelerator slot table, the host keeps only the LRU
    ``stream_id -> slot`` map, and the hot path ships slot ids instead of
    (h, c) arrays.
  * :class:`ResiliencePolicy` / :class:`ExecutionGuard` — guarded wave
    execution: retry, timeout, backend degradation pallas -> xla -> ref
    with recovery probes (``repro.serving.resilience``).
  * :class:`OverloadPolicy` / :class:`ServerOverloaded` — admission
    control and deadline-aware load shedding.
  * :class:`FaultInjector` / :class:`FaultConfig` — the seeded chaos
    harness (``repro.serving.faults``); :class:`InjectedFault` is what it
    raises.
  * :func:`serve_windows` — ordered stateless mapping; the engine behind
    the ``Accelerator.serve`` / ``WaveBatcher.for_accelerator`` compat
    wrappers.
  * :class:`ClusterServer` / :class:`ClusterConfig` — N per-device
    replica servers behind a consistent-hash front door
    (``repro.serving.cluster``; docs/SERVING.md §Scaling out).
  * :class:`HashRing` — the routing primitive itself
    (``repro.serving.routing``), exposed for external load balancers
    that want to compute the same stream -> replica mapping.
"""

from repro.serving.cluster import ClusterConfig, ClusterServer   # noqa: F401
from repro.serving.device_state import (DeviceStateStore,        # noqa: F401
                                        SlotAllocator)
from repro.serving.faults import (FaultConfig, FaultInjector,    # noqa: F401
                                  InjectedFault)
from repro.serving.metrics import MetricsSink, WaveRecord        # noqa: F401
from repro.serving.resilience import (ExecutionGuard,            # noqa: F401
                                      GuardOutcome, ResiliencePolicy,
                                      WaveTimeout)
from repro.serving.routing import HashRing                       # noqa: F401
from repro.serving.scheduler import (OverloadPolicy,             # noqa: F401
                                     ServerOverloaded, Wave,
                                     WaveScheduler)
from repro.serving.server import (ServingConfig, StreamResult,   # noqa: F401
                                  StreamServer, serve_windows)

__all__ = [
    "ClusterConfig", "ClusterServer", "DeviceStateStore", "ExecutionGuard",
    "FaultConfig", "FaultInjector", "GuardOutcome", "HashRing",
    "InjectedFault", "SlotAllocator",
    "MetricsSink", "OverloadPolicy", "ResiliencePolicy", "ServerOverloaded",
    "ServingConfig", "StreamResult", "StreamServer",
    "Wave", "WaveRecord", "WaveScheduler", "WaveTimeout",
    "serve_windows",
]
