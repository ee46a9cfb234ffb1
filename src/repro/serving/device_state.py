"""Device-resident per-stream carry — the slot allocator and state table.

Every stateful ``StreamServer`` keeps its streams' carries here, in one
persistent int32 table ON the accelerator: ``max_slots + 2`` slots of
the cell's ``plan()['state_shape']`` ``(L, S, H)`` carry — ``S == 2``
(h, c) for the LSTM, ``S == 1`` for GRU/rGLRU — laid out by
``kernels.qlstm_cell.state_table_shape``
(``Accelerator.init_state_table``).  The host keeps only a
:class:`SlotAllocator`, a bounded LRU map ``stream_id -> table row``
with hit/miss/eviction counters.  Per wave the scheduler ships two (B,)
int32 slot-id vectors; the engine gathers each row's carry at t == 0 and
scatters the final state at t == T-1 — inside the fused kernel
(``kernels/qlstm_cell.qlstm_seq_slot_pallas``) on ``pallas``, through
the XLA-level adapter on ``ref``/``xla`` — so no carry array crosses the
host/device boundary on the hot path: the paper's state-next-to-compute
residency argument, and ELSA's throughput lever, applied to serving.

Table slot conventions (shared with the kernel and the XLA-level adapter
``backends.common.run_slots_via_state``):

  * slots ``0 .. max_slots-1`` — live stream carries, owned by the
    allocator;
  * slot ``max_slots`` (:attr:`DeviceStateStore.zero_slot`) — the RESET
    slot: always all-zero, gathered by fresh/evicted/ended streams, never
    written;
  * slot ``max_slots + 1`` (:attr:`DeviceStateStore.trash_slot`) — the
    TRASH slot: the scatter target for padding rows, tombstoned windows,
    and same-wave eviction victims; never read.

Eviction/reset semantics: an evicted or brand-new stream gathers the
ZERO row, and a window of a stream with history computed from it comes
back flagged ``state_reset=True``.  The stale codes left in a freed slot are
unreachable — a returning stream misses in the allocator before it could
ever gather them, and the slot's next owner overwrites them at its first
scatter.

The only time a carry crosses back to the host is PLANNED stream
movement: :meth:`DeviceStateStore.read_state` /
:meth:`DeviceStateStore.seed_state`, used by
``ClusterServer.remove_replica`` to hand a draining replica's streams to
their new ring homes warm (docs/SERVING.md §Scaling out).

The table is updated IN PLACE: every program that writes it — the wave
programs and the planned writes here — donates it, so a write lands in
the table's own buffer and the array handed in is deleted.  A wave
therefore borrows the table (:meth:`DeviceStateStore.take`) and gives a
table back (:meth:`DeviceStateStore.commit` on success,
:meth:`DeviceStateStore.restore` on failure); meanwhile the planned
surfaces wait, so none of them reads a donated array.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.qlstm_cell import gather_carry, scatter_carry

# One stream's carry on the host: per layer, a tuple of the cell's
# ``state_arity`` (hidden_size,) int32 code vectors (2 for the LSTM's
# (h, c), 1 for GRU/rGLRU).
StreamState = List[Tuple[np.ndarray, ...]]


class SlotAllocator:
    """LRU map ``stream_id -> slot id`` over ``capacity`` device-table rows.

    The host half of the device-resident state store: it decides WHICH
    table row each stream's carry occupies — :meth:`lookup` (recency
    refresh, hit/miss counters), :meth:`assign` (insert or refresh, LRU
    eviction when full), :meth:`release`.  Slot ids are
    unique among live streams; released slots are reused (LIFO) before
    the high-water mark grows, so a bursty tenancy pattern touches the
    fewest distinct table rows.

    NOT thread-safe on its own — :class:`DeviceStateStore` serialises
    access under its lock."""

    def __init__(self, capacity: int = 1024):
        """``capacity``: number of live stream slots (>= 1)."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._slots: "OrderedDict[Hashable, int]" = OrderedDict()
        self._free: List[int] = []      # released slots, reused LIFO
        self._next = 0                  # high-water mark of slots ever used
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def lookup(self, stream_id: Hashable) -> Optional[int]:
        """The stream's slot (refreshing its recency), or ``None`` when
        the stream is new or was evicted — the caller gathers the ZERO
        row."""
        slot = self._slots.get(stream_id)
        if slot is None:
            self.misses += 1
            return None
        self._slots.move_to_end(stream_id)
        self.hits += 1
        return slot

    def assign(self, stream_id: Hashable) -> Tuple[int, List[Hashable]]:
        """The slot the stream's next scatter should target, allocating
        one if needed; returns ``(slot, evicted_ids)``: an existing
        stream keeps its slot (recency
        refreshed); a new stream takes a freed slot, a never-used slot,
        or — when all ``capacity`` slots are live — the LRU victim's
        (the victim is evicted and returned so the caller can release its
        bookkeeping and redirect any same-wave scatter to TRASH)."""
        if stream_id in self._slots:
            self._slots.move_to_end(stream_id)
            return self._slots[stream_id], []
        evicted: List[Hashable] = []
        if self._free:
            slot = self._free.pop()
        elif self._next < self.capacity:
            slot = self._next
            self._next += 1
        else:
            victim, slot = self._slots.popitem(last=False)
            self.evictions += 1
            evicted.append(victim)
        self._slots[stream_id] = slot
        return slot, evicted

    def release(self, stream_id: Hashable) -> Optional[int]:
        """Free a stream's slot (end-of-stream / state loss); returns the
        slot or ``None``."""
        slot = self._slots.pop(stream_id, None)
        if slot is not None:
            self._free.append(slot)
        return slot

    def slot_of(self, stream_id: Hashable) -> Optional[int]:
        """Peek at a stream's slot WITHOUT touching recency or counters
        (for fault injection and state read-back)."""
        return self._slots.get(stream_id)

    @property
    def high_water(self) -> int:
        """Distinct slots ever handed out — the reuse property tests pin
        that this never exceeds the peak number of live streams."""
        return self._next

    def live(self) -> Dict[Hashable, int]:
        """Snapshot of the live ``stream_id -> slot`` map, LRU-first."""
        return dict(self._slots)

    def __len__(self) -> int:
        return len(self._slots)

    def __contains__(self, stream_id: Hashable) -> bool:
        return stream_id in self._slots


class DeviceStateStore:
    """A stateful server's carry store: a :class:`SlotAllocator` plus the
    accelerator-resident state table.

    The bookkeeping surface (``pop`` / ``ids`` / ``stats`` / ``__len__``
    / ``__contains__`` / ``capacity``), the slot surface the hot path runs
    on (:meth:`lookup` / :meth:`assign` / :meth:`take` / :meth:`commit` /
    :meth:`restore`) and the planned-movement surface (:meth:`read_state`
    / :meth:`seed_state`).  All methods take the internal lock; multi-op
    wave transactions are additionally serialised by the server's own
    lock."""

    def __init__(self, session, capacity: int = 1024):
        """``session``: the (quantised) ``Accelerator`` whose device owns
        the table; ``capacity``: live stream slots (the ``max_streams``
        serving knob)."""
        self.capacity = capacity
        self._alloc = SlotAllocator(capacity)
        self._shape = session.plan["state_shape"]      # (L, S, H)
        self._fresh = lambda: session.init_state_table(capacity)
        #: The persistent int32 carry table (``capacity + 2`` slots).  The
        #: serving hot path replaces this reference wholesale after each
        #: wave (:meth:`commit`) — the array itself never visits the host.
        self.table = self._fresh()
        self._write = jax.jit(scatter_carry, donate_argnums=0)
        self._lock = threading.Lock()
        # Set while a wave holds the table (take .. commit / restore).
        self._returned = threading.Condition(self._lock)
        self._in_flight = False

    @property
    def zero_slot(self) -> int:
        """Table row fresh/reset streams gather from (always zero)."""
        return self.capacity

    @property
    def trash_slot(self) -> int:
        """Table row retired/padding rows scatter to (never read)."""
        return self.capacity + 1

    # -- wave surface (serialised by the server's lock) ----------------------

    def lookup(self, stream_id: Hashable) -> Optional[int]:
        """The stream's slot, refreshing its recency
        (:meth:`SlotAllocator.lookup`)."""
        with self._lock:
            return self._alloc.lookup(stream_id)

    def assign(self, stream_id: Hashable) -> Tuple[int, List[Hashable]]:
        """The slot the stream's scatter targets, and any evicted ids
        (:meth:`SlotAllocator.assign`)."""
        with self._lock:
            return self._alloc.assign(stream_id)

    def take(self):
        """Lend the table to a wave program, which donates it: until
        :meth:`commit` or :meth:`restore` gives a table back, the planned
        surfaces wait."""
        with self._lock:
            self._in_flight = True
            return self.table

    def commit(self, new_table, rows: List[Tuple[int, Hashable]]) -> None:
        """Adopt the kernel's updated table after a successful wave.
        ``rows`` lists the wave's real scatters as ``(batch_row,
        stream_id)`` — unused here, but the fault-injection wrapper draws
        its per-row schedule from them (``faults.FaultyDeviceStateStore``)."""
        with self._lock:
            self._give_back(new_table)

    def restore(self, table) -> bool:
        """Give back the table a failed wave borrowed.  A table that is
        still alive (the failure came before the program was dispatched)
        is kept as it was.  A donated one is gone with every carry in it:
        a zero table takes its place and every live slot is released, so
        each stream's next window starts from the zero carry, flagged
        ``state_reset``.  Returns True when the table was lost."""
        lost = table.is_deleted()
        if lost:
            table = self._fresh()
        with self._lock:
            if lost:
                for sid in list(self._alloc.live()):
                    self._alloc.release(sid)
            self._give_back(table)
        return lost

    def _give_back(self, table) -> None:
        """Install ``table`` and wake the planned surfaces.  Caller holds
        the lock."""
        self.table = table
        self._in_flight = False
        self._returned.notify_all()

    def _table_at_rest(self):
        """The table, once no wave holds it.  Caller holds the lock."""
        while self._in_flight:
            self._returned.wait()
        return self.table

    def pop(self, stream_id: Hashable) -> Optional[int]:
        """Release a stream's slot (end-of-stream, failed wave, shed,
        injected loss).  The freed row's stale codes are unreachable: the
        stream now misses in the allocator, and the slot's next owner
        overwrites them at its first scatter.  Returns the freed slot."""
        with self._lock:
            return self._alloc.release(stream_id)

    def ids(self) -> List[Hashable]:
        """Snapshot of the stream ids currently holding slots, LRU-first —
        the server's ``reset_streams()`` walks it to end every stream."""
        with self._lock:
            return list(self._alloc.live())

    # -- planned movement (cluster drain/rebalance) --------------------------

    def read_state(self, stream_id: Hashable) -> Optional[StreamState]:
        """Read a stream's carry BACK to the host — the one sanctioned
        host/device state transfer, used only on planned stream movement
        (``ClusterServer.remove_replica``).  Returns per layer a tuple of
        the cell's ``state_arity`` int32 rows (``[(h, c), ...]`` for the
        LSTM), or ``None`` for an unknown stream.  Waits while a wave
        holds the table."""
        with self._lock:
            slot = self._alloc.slot_of(stream_id)
            if slot is None:
                return None
            state = gather_carry(self._table_at_rest(),
                                 jnp.asarray([slot]), *self._shape)
            return [tuple(np.asarray(a)[0] for a in layer)
                    for layer in state]

    def seed_state(self, stream_id: Hashable,
                   state: StreamState) -> List[Hashable]:
        """Plant a host-side carry into the table (the destination half of
        a warm handoff): assigns a slot and writes the row in place.
        Returns any ids the assignment evicted.  Waits while a wave holds
        the table."""
        with self._lock:
            table = self._table_at_rest()
            slot, evicted = self._alloc.assign(stream_id)
            self.table = self._write(
                table, jnp.asarray([slot]),
                [tuple(np.asarray(a)[None] for a in layer)
                 for layer in state])
        return evicted

    # -- fault-injection surface ---------------------------------------------

    def corrupt_slot(self, stream_id: Hashable) -> bool:
        """XOR the low bit of every code in the stream's table row — a
        bitwise-plausible corruption that is guaranteed to change the
        carry (the fault injector's ``corrupt``).  Returns False for an
        unknown stream (nothing to corrupt).  Waits while a wave holds the
        table."""
        with self._lock:
            slot = self._alloc.slot_of(stream_id)
            if slot is None:
                return False
            table = self._table_at_rest()
            slots = jnp.asarray([slot])
            state = gather_carry(table, slots, *self._shape)
            self.table = self._write(
                table, slots,
                [tuple(jnp.bitwise_xor(a, 1) for a in layer)
                 for layer in state])
            return True

    # -- reporting ---------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        """Counters for the metrics report: live streams, capacity,
        hits/misses (carry found vs reset), evictions, and the slot
        high-water mark."""
        with self._lock:
            return {"live_streams": len(self._alloc),
                    "capacity": self.capacity,
                    "hits": self._alloc.hits,
                    "misses": self._alloc.misses,
                    "evictions": self._alloc.evictions,
                    "slot_high_water": self._alloc.high_water}

    def __len__(self) -> int:
        with self._lock:
            return len(self._alloc)

    def __contains__(self, stream_id: Hashable) -> bool:
        with self._lock:
            return stream_id in self._alloc
