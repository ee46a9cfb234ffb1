"""Deterministic fault injection for the serving tier — the chaos harness.

The paper's deployment is always-on embedded inference; a serving stack
that only works when nothing ever fails is not that deployment.  This
module makes failure a *testable input*: a seedable :class:`FaultInjector`
wraps the two surfaces where production faults land —

  * the **backend execute path** (:meth:`FaultInjector.wrap_fn`): injected
    compute exceptions (a Pallas lowering hiccup, a device error) and
    latency spikes (a descheduled host thread, a contended device);
  * the **carry table** (:meth:`FaultInjector.wrap_device_state_store`):
    state *loss* (a carry silently dropped, as a crashed replica would)
    and state *corruption* (bit flips in the stored carry codes).

Everything is driven by one ``numpy`` PCG64 generator, so a given
``(seed, rates)`` pair injects the exact same schedule every run — chaos
tests assert exact counter values, not "some faults probably happened".
The injector records what it did (:meth:`stats`, :attr:`corrupted_streams`,
:attr:`lost_streams`), so a test can partition streams into *survivors*
(untouched by state faults — these must stay bit-exact with the
concatenated-sequence oracle) and *casualties* (these must be *flagged*,
via ``StreamResult.state_reset`` or an error, never silently wrong).

The injector is inert by default: every rate is 0.0, and a
``StreamServer`` built without one pays no wrapping cost at all.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, Dict, Hashable, Optional, Set

import numpy as np


class InjectedFault(RuntimeError):
    """The exception :class:`FaultInjector` raises on the execute path.

    A distinct type so the resilience layer (and tests) can tell an
    injected fault from a real defect — real defects must still surface."""


@dataclasses.dataclass(frozen=True)
class FaultConfig:
    """Per-surface fault rates, all probabilities per *event* in [0, 1].

    ``wave_fault_rate``: chance one execute *attempt* raises
    :class:`InjectedFault` (retries draw independently, so a retried wave
    usually lands).  ``latency_spike_rate`` / ``latency_spike_s``: chance
    an attempt sleeps ``latency_spike_s`` before computing (drives the
    guard's timeout path).  ``state_loss_rate``: chance a carry a wave
    stores is silently dropped — the stream's next window starts from the
    reset carry exactly like an LRU eviction.  ``state_corrupt_rate``:
    chance a stored carry's codes are bitwise-perturbed (the stream's id
    is recorded so tests can exclude it from bit-exactness)."""

    wave_fault_rate: float = 0.0
    latency_spike_rate: float = 0.0
    latency_spike_s: float = 0.05
    state_loss_rate: float = 0.0
    state_corrupt_rate: float = 0.0

    def __post_init__(self):
        """Validate every rate is a probability."""
        for f in ("wave_fault_rate", "latency_spike_rate",
                  "state_loss_rate", "state_corrupt_rate"):
            v = getattr(self, f)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{f} must be in [0, 1], got {v}")
        if self.latency_spike_s < 0:
            raise ValueError(
                f"latency_spike_s must be >= 0, got {self.latency_spike_s}")


class FaultInjector:
    """Seeded chaos source for one ``StreamServer`` run.

    One injector owns one PCG64 stream; draws are serialised under a lock
    (the execute path and the state store live on different threads), so
    the injected schedule is a pure function of ``(seed, config)`` and the
    order of events.  Construct with either a :class:`FaultConfig` or the
    equivalent keyword rates::

        inj = FaultInjector(seed=7, wave_fault_rate=0.2)
        server = StreamServer(sess, batch=8, fault_injector=inj)
    """

    def __init__(self, config: Optional[FaultConfig] = None, *,
                 seed: int = 0, **rates):
        """``config`` or keyword rates (``wave_fault_rate=...``, see
        :class:`FaultConfig`); ``seed`` fixes the injection schedule."""
        if config is not None and rates:
            raise ValueError("pass a FaultConfig or keyword rates, not both")
        self.config = config or FaultConfig(**rates)
        self.seed = seed
        self._rng = np.random.default_rng(seed)
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {
            "attempts": 0, "wave_faults": 0, "latency_spikes": 0,
            "state_losses": 0, "state_corruptions": 0}
        #: Stream ids whose stored carry was bitwise-perturbed — their
        #: outputs are expected to diverge from the oracle.
        self.corrupted_streams: Set[Hashable] = set()
        #: Stream ids that lost a carry — their next window restarts from
        #: the reset state (and must be flagged ``state_reset``).
        self.lost_streams: Set[Hashable] = set()

    def _draw(self, rate: float) -> bool:
        return rate > 0.0 and float(self._rng.random()) < rate

    # -- execute-path surface ------------------------------------------------

    def wrap_fn(self, fn: Callable, label: str = "") -> Callable:
        """Wrap a compiled datapath callable: each call first draws a
        latency spike (sleep), then a compute fault (:class:`InjectedFault`)
        — in that fixed order, so the schedule is deterministic — then
        delegates.  ``label`` names the wrapped engine in the raise."""
        cfg = self.config

        def chaotic(*args, **kwargs):
            with self._lock:
                self._counts["attempts"] += 1
                spike = self._draw(cfg.latency_spike_rate)
                fault = self._draw(cfg.wave_fault_rate)
                if spike:
                    self._counts["latency_spikes"] += 1
                if fault:
                    self._counts["wave_faults"] += 1
            if spike:
                time.sleep(cfg.latency_spike_s)
            if fault:
                raise InjectedFault(
                    f"injected compute fault"
                    f"{f' on {label}' if label else ''} "
                    f"(seed={self.seed}, attempt "
                    f"{self._counts['attempts']})")
            return fn(*args, **kwargs)

        return chaotic

    # -- carry-table surface -------------------------------------------------

    def wrap_device_state_store(self, store) -> "FaultyDeviceStateStore":
        """A delegating view of a ``DeviceStateStore`` whose per-wave
        ``commit`` draws a lose-then-corrupt fault per stored row, in
        batch-row order."""
        return FaultyDeviceStateStore(store, self)

    def draw_put_fault(self, stream_id: Hashable) -> str:
        """One store-side draw for ``stream_id``: ``"lose"`` (the carry is
        dropped — counted and recorded in :attr:`lost_streams`),
        ``"corrupt"`` (the stored codes must be bit-perturbed — counted
        and recorded in :attr:`corrupted_streams`), or ``"none"``."""
        with self._lock:
            lose = self._draw(self.config.state_loss_rate)
            corrupt = (not lose) and self._draw(self.config.state_corrupt_rate)
            if lose:
                self._counts["state_losses"] += 1
                self.lost_streams.add(stream_id)
                return "lose"
            if corrupt:
                self._counts["state_corruptions"] += 1
                self.corrupted_streams.add(stream_id)
                return "corrupt"
            return "none"

    # -- reporting -----------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        """Injection counters (attempts seen, faults/spikes/losses/
        corruptions injected) — the ``faults.injected`` block of
        ``metrics_summary()``."""
        with self._lock:
            return dict(self._counts)


class FaultyDeviceStateStore:
    """A ``DeviceStateStore`` view with injected commit-time faults; every
    other method delegates verbatim (kept API-compatible so the serving
    layer cannot tell the difference — which is the point).

    The engine has already scattered every row's carry into the table by
    the time the wave commits, so faults land AT COMMIT, once per
    really-stored row in batch-row order.  A ``lose`` releases the row's
    slot (the scattered carry becomes unreachable — the stream's next
    window restarts from the ZERO row, flagged ``state_reset``); a
    ``corrupt`` XORs the low bit of every code in the row's table slot
    (``DeviceStateStore.corrupt_slot``)."""

    def __init__(self, store, injector: FaultInjector):
        """Wrap ``store`` (a ``DeviceStateStore``) with ``injector``'s
        store-side schedule."""
        self._store = store
        self._injector = injector

    def commit(self, new_table, rows) -> None:
        """Adopt the wave's updated table, then apply one store-fault draw
        per stored row (``rows``: the wave's ``(batch_row, stream_id)``
        scatters, in batch-row order)."""
        self._store.commit(new_table, rows)
        for _, sid in rows:
            fault = self._injector.draw_put_fault(sid)
            if fault == "lose":
                self._store.pop(sid)
            elif fault == "corrupt":
                self._store.corrupt_slot(sid)

    def __getattr__(self, name):
        # lookup/assign/take/restore/pop/read_state/seed_state/
        # corrupt_slot/stats/table/capacity/zero_slot/trash_slot delegate
        # verbatim.
        return getattr(self._store, name)

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, stream_id: Hashable) -> bool:
        return stream_id in self._store
