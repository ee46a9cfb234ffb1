"""``StreamServer`` — many named client streams, one accelerator.

The paper's headline is *real-time* inference (§6: 32 873 samples/s on a
live sensor stream); the ROADMAP scenario is that stream multiplied by
"millions of users".  This module is the piece between the two: clients
``submit`` windows tagged with a stream id, the scheduler groups them into
fixed-size waves (one static shape for the jitted datapath), and — the part
the stateless ``Accelerator.serve`` path cannot do — each stream's
recurrent carry (whatever shape the model's cell spec declares)
survives across its windows, so window *k+1* continues the
recurrence window *k* left off, bit-exactly equal to running the stream's
concatenated sequence through the accelerator in one shot.

Deployment shape::

    server = StreamServer(session, batch=64, deadline_s=0.005)
    server.submit("sensor-17", window)        # (T, M) float, any thread
    for r in server.poll(timeout=0.1):        # StreamResult(stream_id, seq, y)
        route(r.stream_id, r.y)
    server.metrics_summary()                  # samples/s, p50/p95/p99, GOP/s/W
    server.close()

A stateful server keeps every stream's carry in its session's
device-resident slot table
(:class:`~repro.serving.device_state.DeviceStateStore`), whatever the
engine: the fused pallas kernel gathers and scatters the rows inside the
kernel, ``ref``/``xla`` through the XLA-level slot adapter.  The host
keeps only the LRU ``stream_id -> slot`` map, and per wave only two (B,)
slot-id vectors cross to the device.  An evicted or brand new stream
starts from the all-zero reset carry.

One server serves one session.  To spread streams across replicas use
``repro.serving.cluster.ClusterServer``, which pins every stream to
exactly one replica by consistent hash so its carry stays in that
replica's table (the routing invariant, pinned in
``tests/test_cluster.py``).
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import (Dict, Hashable, Iterable, Iterator, List, Optional,
                    Tuple, Union)

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.device_state import DeviceStateStore
from repro.serving.faults import FaultInjector
from repro.serving.metrics import MetricsSink, WaveRecord
from repro.serving.resilience import ExecutionGuard, ResiliencePolicy
from repro.serving.scheduler import (OverloadPolicy, Slot, Wave,
                                     WaveScheduler)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Knobs of the streaming subsystem (docs/SERVING.md has the tuning
    guide).

    ``batch``: static wave size the jitted datapath sees.  ``deadline_s``:
    the upper limit of a window's pending wait — a padded partial wave is
    cut at once when the compute thread waits for work with no wave queued
    for it, and otherwise once the oldest pending window has waited this
    long (None = wait for full waves; no early cut).  ``queue_depth``:
    assembled waves the compute thread may fall behind by (2 = double
    buffering).
    ``max_pending``: submitted-but-unassembled window bound — ``submit``
    blocks past it (None = 4 * batch); when pending saturates and no full
    wave can form (one window per stream), a partial wave is flushed
    rather than deadlocking the blocked submitters.  ``max_results``:
    computed-but-unpolled result bound — past it the compute thread blocks
    before emitting, which stalls the whole pipeline back to ``submit``
    (full backpressure to a stalled consumer).  The default ``None`` is
    unbounded: required for the submit-everything-then-``drain()`` pattern
    (``drain`` flushes before polling, so a bound smaller than the
    outstanding windows would deadlock it); production servers with a
    concurrent ``poll`` loop should set it.  ``max_streams``: live
    streams the carry table holds (LRU-evicted past it).  ``stateful``:
    carry (h, c) across a stream's windows (requires ``path="int"``);
    False gives the stateless ``Accelerator.serve`` semantics.
    ``backend``: engine override
    (``ref`` | ``pallas`` | ``xla`` — all three carry state; the default
    follows the plan's ``stateful_backend``, docs/API.md §Backends).

    ``resilience``: the guarded-execution policy (retry/backoff/timeout +
    backend degradation, docs/SERVING.md §Reliability); every wave runs
    under it.  ``overload``: admission-control / load-shedding policy
    (None = legacy block-on-backpressure, never shed)."""

    batch: int = 256
    path: str = "int"
    backend: Optional[str] = None
    stateful: bool = True
    deadline_s: Optional[float] = 0.010
    queue_depth: int = 2
    max_pending: Optional[int] = None
    max_results: Optional[int] = None
    max_streams: int = 1024
    resilience: ResiliencePolicy = ResiliencePolicy()
    overload: Optional[OverloadPolicy] = None

    def __post_init__(self):
        """Reject contradictory settings at construction time."""
        if self.stateful and self.path != "int":
            raise ValueError(
                f"stateful serving carries integer state codes, so it "
                f"requires path='int' (got path={self.path!r}); set "
                f"stateful=False for the float/qat paths")
        if self.max_results is not None and self.max_results < 1:
            raise ValueError(
                f"max_results must be >= 1, got {self.max_results}")
        if self.resilience is None:
            raise ValueError(
                "resilience cannot be None — pass ResiliencePolicy("
                "max_retries=0) to minimise guarding instead of disabling "
                "it")


@dataclasses.dataclass(frozen=True)
class StreamResult:
    """One prediction — or one structured per-stream failure.

    ``stream_id``/``seq`` identify the window (``seq`` is the value
    ``submit`` returned).  ``y`` is the (P,) float prediction, or ``None``
    when ``error`` is set: ``"shed"`` (deadline-aware load shedding
    dropped the window uncomputed) or a ``"compute_failed: ..."``
    description (every engine of the degradation ladder failed the wave).
    ``state_reset`` flags a window computed from the all-zero reset carry
    although the stream had history (LRU eviction, injected state loss, or
    a failed wave dropped it) — the prediction is a valid model output, it
    just lost the history; silent before, now reported.  ``backend`` names
    the engine that computed the window (None for error rows).

    ``routed_replica`` says WHERE the window ran: None on a
    ``StreamServer``; through ``ClusterServer`` it is the replica NAME,
    and the routing invariant guarantees one stream always reports one
    replica.

    ``wave`` is the id of the wave that computed the window (None for
    shed windows): the ``wave=`` tag of that wave's ``serve.*`` spans on
    a profiler trace and ``WaveRecord.wave`` in the metrics sink."""

    stream_id: Hashable
    seq: int
    y: Optional[np.ndarray]
    error: Optional[str] = None
    state_reset: bool = False
    backend: Optional[str] = None
    routed_replica: Optional[Hashable] = None
    wave: Optional[int] = None

    @property
    def ok(self) -> bool:
        """True for a real prediction, False for a shed/failed window."""
        return self.error is None


class _StageClock:
    """Times the execute stages of one wave (``metrics.STAGES``), one at a
    time (``with clock("call"): ...``): the wall seconds of each into
    ``wall`` and the thread's CPU seconds of each but ``ready`` (the wait
    for the device) into ``cpu``.  Each stage is also a ``serve.<stage>``
    span tagged with the wave's id."""

    def __init__(self, wave_id: int):
        self.wave = wave_id
        self.wall: Dict[str, float] = {}
        self.cpu: Dict[str, float] = {}

    def __call__(self, stage: str) -> "_StageClock":
        self._stage = stage
        self._span = TraceAnnotation("serve." + stage, wave=self.wave)
        return self

    def __enter__(self) -> None:
        self._c0 = time.thread_time() if self._stage != "ready" else 0.0
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._span.__exit__(*exc)
        if self._stage != "ready":
            self.cpu[self._stage] = time.thread_time() - self._c0
        self.wall[self._stage] = t1 - self._t0   # last: marks it complete


class StreamServer:
    """Stateful streaming front-end over one ``Accelerator`` session (see
    the module docstring for the deployment shape).

    Results are delivered through :meth:`poll` / :meth:`drain` as
    :class:`StreamResult` rows; padded slots of partial waves are computed
    and dropped — they are never emitted and their carries go to the
    table's TRASH row."""

    def __init__(self, session, config: Optional[ServingConfig] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 **overrides):
        """``session``: the quantised ``Accelerator`` to serve (replicas go
        behind a ``ClusterServer``).  ``config`` or keyword overrides
        (``batch=``, ``deadline_s=``, ...) set the :class:`ServingConfig`.
        ``fault_injector`` (tests/chaos drills only) wraps the execute path
        and the carry table with a seeded fault schedule — see
        ``repro.serving.faults``."""
        if isinstance(session, (list, tuple)):
            raise ValueError(
                "StreamServer serves one Accelerator session; put replicas "
                "behind a ClusterServer, which routes each stream to one "
                "replica and its carry table")
        cfg = config or ServingConfig()
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        self.config = cfg
        self._session = session
        self.fault_injector = fault_injector
        # Resolve the degradation ladder and validate NOW: a bad
        # path/backend or an unquantised session fails at construction,
        # not in the compute thread.  The preferred engine is compiled at
        # the first submit, once the window shape is known
        # (_compile_preferred); jit closures are lazy, so non-preferred
        # ladder levels cost nothing until a degradation runs them.
        from repro import backends as _backends
        if cfg.path == "int":
            ladder = _backends.degradation_ladder(
                session.model, session.accel, override=cfg.backend,
                stateful=cfg.stateful)
            fns = [(n, session.compiled_stateful_slots(n) if cfg.stateful
                    else session.compiled(cfg.path, n)) for n in ladder]
        else:
            # float/qat run one plan-resolved graph; the ladder is trivial
            # but the guard's retry/timeout protection still applies.
            ladder = (cfg.path,)
            fns = [(cfg.path, session.compiled(cfg.path, cfg.backend))]
        self._preferred = fns[0][1]
        if fault_injector is not None:
            fns = [(n, fault_injector.wrap_fn(fn, label=n)) for n, fn in fns]
        # The ladder the guard runs, held as a list of one ladder: the
        # benchmark's fault checks (benchmarks/chip/tests/test_faults.py)
        # wrap its engines in that shape.
        self._fns = [fns]
        self.guard = ExecutionGuard(ladder, cfg.resilience)
        self.states: Optional[DeviceStateStore] = None
        if cfg.stateful:
            self.states = DeviceStateStore(session, cfg.max_streams)
            if fault_injector is not None:
                self.states = fault_injector.wrap_device_state_store(
                    self.states)
        self.metrics = MetricsSink()
        self._results: "queue.Queue" = queue.Queue(
            maxsize=cfg.max_results or 0)
        self._seq: Dict[Hashable, int] = {}
        # stream_id -> submission watermark of an end_stream request:
        # carries of windows submitted before it are not re-stored.  Every
        # tombstone is pruned once the stream has no windows in flight
        # (tracked in _outstanding), so neither dict can grow beyond the
        # streams currently inside the pipeline.
        self._ended: Dict[Hashable, int] = {}
        self._outstanding: Dict[Hashable, int] = {}
        self._seq_lock = threading.Lock()
        self._window_shape = None
        self._sched = WaveScheduler(
            cfg.batch, self._execute, one_per_stream=cfg.stateful,
            deadline_s=cfg.deadline_s, queue_depth=cfg.queue_depth,
            max_pending=cfg.max_pending, overload=cfg.overload,
            on_shed=self._shed)

    # -- client surface -----------------------------------------------------

    def submit(self, stream_id: Hashable,
               window: Union[np.ndarray, "jnp.ndarray"]) -> int:
        """Enqueue one (T, M) float window for ``stream_id``; returns the
        window's per-stream sequence number.  Blocks under backpressure
        (``max_pending``); with a reject-mode ``OverloadPolicy`` it raises
        ``ServerOverloaded`` instead of blocking when the server is
        saturated.  All windows of a server must share one shape (the
        jitted datapath is compiled for it).

        Inputs are validated HERE, per call: a malformed window (wrong
        rank, wrong feature width, non-float-convertible dtype, NaN/Inf)
        raises ``ValueError`` to this caller only — it never reaches the
        compute thread, where it would poison a whole wave of other
        clients' windows."""
        try:
            w = np.asarray(window, dtype=np.float32)
        except (TypeError, ValueError) as e:
            raise ValueError(
                f"window is not convertible to a float32 array: {e}"
            ) from None
        if w.ndim != 2:
            raise ValueError(
                f"window must be a (T, M) array, got shape {w.shape}")
        m = self._session.model.input_size
        if w.shape[0] < 1 or w.shape[1] != m:
            raise ValueError(
                f"window shape {w.shape} does not match the model's "
                f"(T>=1, input_size={m})")
        if not np.isfinite(w).all():
            raise ValueError(
                "window contains NaN/Inf; the int datapath would quantise "
                "them to arbitrary codes and corrupt the stream's carry — "
                "rejected at submit")
        with self._seq_lock:
            if self._window_shape is None:
                self._compile_preferred(w.shape)
                self._window_shape = w.shape
            elif w.shape != self._window_shape:
                raise ValueError(f"window shape {w.shape} != first window's "
                                 f"{self._window_shape}; one server serves "
                                 f"one static shape")

        def alloc_seq() -> int:
            # Runs inside the scheduler's critical section, so the seq a
            # thread gets and its position in the FIFO cannot be reordered
            # against another thread submitting to the same stream.
            with self._seq_lock:
                seq = self._seq.get(stream_id, 0)
                self._seq[stream_id] = seq + 1
                if self.config.stateful:
                    self._outstanding[stream_id] = \
                        self._outstanding.get(stream_id, 0) + 1
                return seq

        self.metrics.note_submit(time.perf_counter())
        return self._sched.submit(stream_id, w, alloc_seq)

    def _compile_preferred(self, window_shape: Tuple[int, ...]) -> None:
        """Compile the preferred engine for waves of
        ``(batch, *window_shape)``, outside the ``ExecutionGuard``.  A
        kernel the compiler refuses raises here, to the first submitter:
        the guard degrades past runtime faults, and a program that cannot
        compile is not one.  Waves then hit jit's cache for the same
        program."""
        b = self.config.batch
        # Host arrays, as a wave passes them (_execute).
        x = np.zeros((b, *window_shape), np.float32)
        args = (x,)
        if self.states is not None:
            slots = np.zeros((b,), np.int32)
            args = (x, self.states.table, slots, slots)
        try:
            self._preferred.lower(*args).compile()
        except Exception as e:
            raise RuntimeError(
                f"the preferred engine {self.guard.ladder[0]!r} failed "
                f"to compile for waves of shape {x.shape}: "
                f"{type(e).__name__}: {e}") from e

    def poll(self, timeout: float = 0.0) -> List[StreamResult]:
        """Completed predictions, in wave order (per-stream order is always
        submission order).  Returns immediately with whatever is ready;
        with ``timeout`` > 0, waits up to that long for the first result.
        Re-raises a compute-thread failure."""
        out: List[StreamResult] = []
        end = time.perf_counter() + timeout
        while True:
            try:
                while True:
                    out.append(self._results.get_nowait())
            except queue.Empty:
                pass
            if out:
                return out
            err = self._sched.error
            if err is not None:
                raise err
            remaining = end - time.perf_counter()
            if remaining <= 0:
                return out
            try:
                out.append(self._results.get(timeout=min(remaining, 0.25)))
            except queue.Empty:
                pass

    def flush(self, timeout: Optional[float] = None) -> None:
        """Barrier: force partial waves and wait until every window
        submitted before the call has been computed."""
        self._sched.flush(timeout=timeout)

    def drain(self, timeout: Optional[float] = None) -> List[StreamResult]:
        """``flush`` then collect everything: all outstanding predictions."""
        self.flush(timeout=timeout)
        return self.poll()

    def end_stream(self, stream_id: Hashable) -> None:
        """Forget a stream (explicit end-of-stream): its carry on stateful
        servers, and its sequence numbering on every server — the next
        window under the same id starts a fresh stream, from the reset
        state and with its sequence numbering restarted at 0.  On
        stateless servers this is also the only way to prune a retired
        id's ``_seq`` entry, so long-lived deployments with rotating
        client ids should call it.

        Safe against in-flight windows: carries of windows submitted
        before this call are never re-stored (a tombstone watermark sends
        their scatter to the TRASH row, and a wave already running writes
        a released slot), so a window submitted AFTER the call is
        guaranteed the zero reset carry."""
        if self.states is None:
            with self._seq_lock:
                self._seq.pop(stream_id, None)
            return
        watermark = self._sched.submission_watermark()
        with self._seq_lock:
            self._seq.pop(stream_id, None)
            # A tombstone is only needed while windows are in flight; it is
            # pruned by _retire once the last of them clears the pipeline.
            if self._outstanding.get(stream_id, 0) > 0:
                self._ended[stream_id] = max(watermark,
                                             self._ended.get(stream_id, 0))
            # Inside the lock: _gather_slots holds it across its tombstone
            # checks AND its slot assignments, so the release here cannot
            # interleave with an assignment and free a reborn stream's slot
            # (or miss a stale one).
            self.states.pop(stream_id)

    def reset_streams(self) -> None:
        """Forget EVERY stream — carries and sequence numbering — without
        tearing down the server: threads, the compiled session and the
        resident slot table all survive, so the next
        window is served by a warm datapath from a zero carry.

        This is the scenario harness's short-run reset
        (``repro.explore.serving_objective``): warm up once, then
        ``reset_streams()`` + ``reset_metrics()`` give a fresh measurement
        interval on an already-compiled server, point after point.
        Flushes first; call it between submission rounds, not concurrently
        with ``submit``."""
        self.flush()
        with self._seq_lock:
            ids = set(self._seq)
        if self.states is not None:
            # Streams seeded via seed_stream_state but never submitted
            # hold a carry without a _seq entry — end those too.
            ids.update(self.states.ids())
        for sid in ids:
            self.end_stream(sid)

    def read_stream_state(self, stream_id: Hashable):
        """A host-side copy of a stream's carry (per layer, a tuple of the
        cell's ``state_arity`` int32 rows — ``[(h, c), ...]`` for the
        LSTM), or ``None`` when the server holds none.  This is the one
        sanctioned read-back from the carry table, meant for PLANNED
        stream movement (``ClusterServer`` drain) — not for the hot path.
        Call only with the stream quiescent (no windows in flight), e.g.
        after ``flush()``."""
        if self.states is None:
            return None
        return self.states.read_state(stream_id)

    def seed_stream_state(self, stream_id: Hashable, state) -> None:
        """Plant a carry for ``stream_id`` (same per-layer carry-tuple
        layout ``read_stream_state`` returns) as if the server had
        computed it — the destination
        half of a warm stream handoff.  The stream's next window continues
        the recurrence from ``state`` with no ``state_reset`` flag.  Any
        streams the insertion LRU-evicts are reconciled exactly like a
        wave's own evictions."""
        if self.states is None:
            raise ValueError("cannot seed state on a stateless server")
        with self._seq_lock:
            evicted = set(self.states.seed_state(stream_id, state))
        self._reconcile_evictions(evicted)

    def close(self, abandon: bool = False,
              timeout: float = 30.0) -> List[str]:
        """Stop the server.  Default: drain submitted windows first;
        ``abandon=True`` discards pending work immediately.  A drain that
        cannot complete (a ``max_results``-bounded queue wedged by a
        consumer that stopped polling) escalates to abandon after
        ``timeout`` instead of leaking the worker threads.  Returns the
        names of any threads that survived the escalated join (empty =
        clean shutdown; also visible in ``health()["leaked_threads"]``)."""
        leaked = self._sched.close(abandon=abandon, timeout=timeout)
        self.guard.close()
        return leaked

    def __enter__(self) -> "StreamServer":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close(abandon=exc_type is not None)

    # -- metrics ------------------------------------------------------------

    def reset_metrics(self) -> None:
        """Start a fresh metrics window (e.g. after a warm-up wave, so the
        compile time stays out of the measured interval)."""
        self.metrics = MetricsSink()

    def metrics_summary(self) -> Dict:
        """The serving report: achieved samples/s, per-wave latency
        p50/p95/p99, occupancy, deadline flushes, carry-table counters, and
        the energy model's GOP/s/W at the MEASURED operating point (mean
        wave compute latency, mean occupancy) — the paper's Table-4 metric
        evaluated where the server actually runs."""
        s = self.metrics.summary()
        s["stateful"] = self.config.stateful
        s["state"] = self.states.stats() if self.states is not None else None
        g = self.guard.stats()
        sched = self._sched.stats()
        counters = self.metrics.counters()
        s["faults"] = {
            "retries": g["retries"],
            "timeouts": g["timeouts"],
            "wave_failures": g["wave_failures"],
            "degradations": g["degradations"],
            "promotions": g["promotions"],
            "probes": g["probes"],
            "backend": g["backend"],
            "degraded": g["level"] > 0,
            "sheds": sched["sheds"],
            "rejections": sched["rejections"],
            "recoveries": sched["recoveries"],
            "deadline_miss_rate": sched["deadline_miss_rate"],
            "state_resets": counters.get("state_resets", 0),
            "stream_errors": counters.get("stream_errors", 0),
            "injected": (self.fault_injector.stats()
                         if self.fault_injector is not None else None),
        }
        # Per-wave state traffic: only slot ids travel.  Then the waves
        # whose new table is the old one's buffer, waves whose is not, and
        # failed waves that lost it.
        s["state_transfer"] = {
            "slot_id_bytes": counters.get("slot_id_bytes", 0),
            "table_in_place": counters.get("table_in_place", 0),
            "table_copied": counters.get("table_copied", 0),
            "table_losses": counters.get("table_losses", 0),
        }
        s["health"] = self.health()
        if s["waves"]:
            sess = self._session
            occupancy = max(1, round(s["mean_occupancy"]))
            rep = sess.report(latency_s=s["compute_ms_mean"] / 1e3,
                              batch=occupancy)
            s["ops_per_inference"] = rep["ops_per_inference"]
            s["energy"] = rep["energy"]
            s["gops_per_watt"] = rep["energy"]["gops_per_watt"]
        return s

    def health(self) -> Dict:
        """Live health snapshot — cheap enough for a readiness probe.

        ``status``: ``"failed"`` (an unrecovered compute-thread error is
        pending re-raise), ``"overloaded"`` (pending queue saturated),
        ``"degraded"`` (serving below the preferred engine), else
        ``"ok"``.  Plus the current engine and ladder, queue depths, the
        rolling deadline-miss rate, live stream count, and any leaked
        worker threads from the last ``close``.  Schema documented in
        docs/SERVING.md §Reliability."""
        g = self.guard.stats()
        sched = self._sched.stats()
        if sched["dead"]:
            status = "failed"
        elif sched["pending"] >= sched["max_pending"]:
            status = "overloaded"
        elif g["level"] > 0:
            status = "degraded"
        else:
            status = "ok"
        return {
            "status": status,
            "backend": g["backend"],
            "ladder": g["ladder"],
            "degraded": g["level"] > 0,
            "pending": sched["pending"],
            "max_pending": sched["max_pending"],
            "results_waiting": self._results.qsize(),
            "deadline_miss_rate": sched["deadline_miss_rate"],
            "live_streams": (len(self.states)
                             if self.states is not None else None),
            "leaked_threads": list(self._sched.leaked_threads),
        }

    # -- compute thread -----------------------------------------------------

    def _execute(self, wave: Wave) -> None:
        """Assign slots -> GUARDED device datapath -> commit the table ->
        emit.  Runs on the scheduler's compute thread, waves strictly in
        order — which is what makes the slot transactions of consecutive
        windows of one stream consistent.

        The guard absorbs engine failures (retry, backoff, degradation
        down the bit-identical ladder); only a wave that fails on EVERY
        engine is converted into per-stream error results — the compute
        thread survives either way.

        The work runs as the stages of ``metrics.STAGES``, timed into the
        wave's :class:`WaveRecord` (``slots`` and ``commit`` only on a
        stateful server); ``compute_s`` spans every stage before
        ``emit``."""
        fns = self._fns[0]
        clock = _StageClock(wave.id)
        t0 = time.perf_counter()
        stateful = self.config.stateful
        if stateful:
            with clock("slots"):
                # The carries never leave the table — only two (B,) int32
                # slot-id vectors cross to the device.  The allocator
                # transaction (lookup + assign + tombstone checks) happens
                # BEFORE compute, so faults can only strand slots, never
                # corrupt the allocator<->table correspondence.
                g, s, reset, rows, evicted = self._gather_slots(wave)
                self.metrics.count("slot_id_bytes",
                                   int(g.nbytes + s.nbytes))
        else:
            reset = [False] * len(wave.slots)
        with clock("call"):
            # The wave's host arrays go to the jitted program as they are:
            # its C++ argument path uploads them, to the committed table's
            # device on a stateful server.
            if stateful:
                # The wave program donates the table: the store lends it
                # here and gets a table back at commit, or at restore when
                # the wave fails.  No attempt runs on a donated table.
                table = self.states.take()
                before = table.unsafe_buffer_pointer()
                outcome = self.guard.run(
                    fns, wave.x, table, g, s,
                    alive=lambda: not table.is_deleted())
            else:
                outcome = self.guard.run(fns, wave.x)
        if not outcome.ok:
            if stateful:
                self._return_table(table)
            self._fail_wave(wave, outcome, t0)
            if stateful:
                # Slot assignment (and any LRU evictions) happened before
                # compute; the victims are still gone even though the
                # wave's table update was discarded.
                self._reconcile_evictions(evicted)
            return
        with clock("ready"):
            y, new_table = (outcome.value if stateful
                            else (outcome.value, None))
            try:
                y = np.asarray(y)
            except BaseException:
                # A device error surfaces here, after the dispatch: the
                # donated table went with the failed program.
                if stateful:
                    self._return_table(table)
                raise
        if stateful:
            with clock("commit"):
                self._commit_table(table, before, new_table, rows)
                self._retire(wave)
                self._reconcile_evictions(evicted)
                n_reset = sum(reset)
                if n_reset:
                    self.metrics.count("state_resets", n_reset)
        t1 = time.perf_counter()
        with clock("emit"):
            t_submit = np.fromiter((sl.t_submit for sl in wave.slots),
                                   np.float64, len(wave.slots))
            # The record shares the clock's dicts, so the emit stage's
            # times land in it when this block ends, after the results are
            # out, without taking the sink's lock again.
            self.metrics.record_wave(WaveRecord(
                t_done=t1, compute_s=t1 - t0, latency_s=t1 - wave.t_oldest,
                occupancy=wave.occupancy, batch=self.config.batch,
                deadline_flush=wave.deadline_flush,
                idle_cut=wave.idle_cut, wave=wave.id,
                t_built=wave.t_built, t_start=t0, stage_s=clock.wall,
                stage_cpu_s=clock.cpu,
                pending_s=(wave.t_built - t_submit).astype(np.float32)))
            for i, slot in enumerate(wave.slots):
                self._emit(StreamResult(slot.stream_id, slot.seq, y[i],
                                        state_reset=reset[i],
                                        backend=outcome.backend,
                                        wave=wave.id))

    def _commit_table(self, table, before: int, new_table,
                      rows: List[Tuple[int, Hashable]]) -> None:
        """Adopt a wave's new state table, counting whether it is the old
        table's buffer (``before`` is that buffer's address).  A program
        that handed back a deleted array, its own donated input, left no
        table: that is a loss, as in a failed wave."""
        if new_table.is_deleted():
            self._return_table(table)
            return
        self.metrics.count("table_in_place"
                           if new_table.unsafe_buffer_pointer() == before
                           else "table_copied")
        self.states.commit(new_table, rows)

    def _return_table(self, table) -> None:
        """Give the store back the table a wave borrowed and did not
        replace; count a loss when the wave had donated it."""
        if self.states.restore(table):
            self.metrics.count("table_losses")

    def _fail_wave(self, wave: Wave, outcome, t0: float) -> None:
        """Every ladder engine failed this wave: isolate the damage to the
        wave's own streams.  Their carries are dropped (a window was lost,
        so continuing from the pre-wave carry would be a silent gap — the
        next window restarts from the reset state and is FLAGGED
        ``state_reset``), each slot gets a structured error result, and
        the compute thread moves on."""
        err = f"compute_failed: {outcome.error}"
        if self.config.stateful:
            for slot in wave.slots:
                self.states.pop(slot.stream_id)
            self._retire(wave)
        self.metrics.count("stream_errors", wave.occupancy)
        t1 = time.perf_counter()
        self.metrics.record_wave(WaveRecord(
            t_done=t1, compute_s=t1 - t0, latency_s=t1 - wave.t_oldest,
            occupancy=wave.occupancy, batch=self.config.batch,
            deadline_flush=wave.deadline_flush,
            idle_cut=wave.idle_cut, wave=wave.id,
            t_built=wave.t_built, t_start=t0))
        for slot in wave.slots:
            self._emit(StreamResult(slot.stream_id, slot.seq, None,
                                    error=err, wave=wave.id))

    def _shed(self, slot: Slot) -> None:
        """Scheduler shed callback (assembler thread): the window was
        dropped uncomputed.  On a stateful server the stream's carry is
        dropped too — its recurrence now has a hole, and a silently wrong
        continuation is worse than a flagged reset — so the next window
        restarts from zero with ``state_reset=True``."""
        if self.config.stateful:
            with self._seq_lock:
                self.states.pop(slot.stream_id)
            self._retire_slot(slot.stream_id)
        self.metrics.count("sheds")
        self._emit(StreamResult(slot.stream_id, slot.seq, None,
                                error="shed"))

    def _emit(self, r: StreamResult) -> None:
        """Deliver one result.  With max_results set this blocks, stalling
        the compute thread and — through the wave queue and pending bounds
        — eventually submit(): full backpressure to a stalled consumer.
        Give up on abandon so close(abandon=True) cannot hang on a full
        results queue."""
        while True:
            try:
                self._results.put(r, timeout=0.1)
                return
            except queue.Full:
                if self._sched.stopped:
                    return

    def _gather_slots(self, wave: Wave):
        """The wave's carry bookkeeping, run BEFORE compute: one allocator
        transaction under ``_seq_lock`` producing the wave's slot-id
        vectors.  Returns ``(gather, scatter, reset, rows, evicted)``:

        * ``gather[i]``: table row whose carry seeds batch row ``i`` at
          t == 0 — the stream's slot, or ZERO for new/evicted streams and
          padding;
        * ``reset[i]``: True when a stream WITH HISTORY (seq > 0) found no
          slot — it was evicted, lost, or dropped by a failed wave, and
          its result must say so instead of silently continuing from
          zeros;
        * ``scatter[i]``: row for the final carry at t == T-1 — the
          stream's (possibly new) slot, or TRASH for padding, tombstoned
          windows, and same-wave eviction victims;
        * ``rows``: the real scatters as ``(batch_row, stream_id)``, the
          unit the fault injector draws per-put faults over;
        * ``evicted``: ids LRU-evicted by this wave's assignments.

        The two phases run every lookup, then every assignment in
        batch-row order, so the hit/miss/eviction counters and any
        injected fault schedule are a function of the waves alone."""
        store = self.states
        batch = self.config.batch
        g = np.full(batch, store.zero_slot, dtype=np.int32)
        s = np.full(batch, store.trash_slot, dtype=np.int32)
        reset = [False] * len(wave.slots)
        rows: List[Tuple[int, Hashable]] = []
        evicted_all: set = set()
        with self._seq_lock:
            for i, slot in enumerate(wave.slots):
                sl = store.lookup(slot.stream_id)
                if sl is not None:
                    g[i] = sl
                elif slot.seq > 0:
                    reset[i] = True
            row_of_slot: Dict[int, int] = {}
            for i, slot in enumerate(wave.slots):
                sid = slot.stream_id
                watermark = self._ended.get(sid)
                if watermark is not None:
                    if slot.sub_idx < watermark:
                        continue   # ended-generation carry: scatter=TRASH
                    del self._ended[sid]   # stream reborn after the end
                sl, evicted = store.assign(sid)
                evicted_all.update(evicted)
                j = row_of_slot.pop(sl, None)
                if j is not None:
                    # An earlier row of THIS wave was assigned this slot
                    # and its stream was just LRU-evicted (batch >
                    # capacity): its carry is dropped — redirect its dead
                    # scatter to TRASH.
                    s[j] = store.trash_slot
                row_of_slot[sl] = i
                s[i] = sl
                rows.append((i, sid))
        return g, s, reset, rows, evicted_all

    def _reconcile_evictions(self, evicted: set) -> None:
        """An evicted stream is forgotten ENTIRELY — carry and sequence
        numbering — so a returning client looks like a new stream (and a
        stateful server's _seq cannot grow without bound).  Runs after
        :meth:`_retire`, and only prunes a victim that is really gone:

        * a victim that was a LATER slot of the evicting wave was assigned
          a slot again and stored its (correctly continued) carry — never
          really evicted, keeps its numbering;
        * a victim with windows still in flight keeps its numbering too —
          its pending window will be assigned a slot again (from the
          flagged reset carry), so pruning here would hand out duplicate
          (stream_id, seq) keys."""
        with self._seq_lock:
            for vid in evicted:
                if vid not in self.states \
                        and self._outstanding.get(vid, 0) == 0:
                    self._seq.pop(vid, None)

    def _retire(self, wave: Wave) -> None:
        """Per-stream in-flight accounting: once a stream has no windows
        left in the pipeline, its end_stream tombstone (if any) can never
        match again and is pruned — this bounds ``_ended``/``_outstanding``
        by the streams currently inside the pipeline."""
        with self._seq_lock:
            for slot in wave.slots:
                self._retire_slot_locked(slot.stream_id)

    def _retire_slot(self, sid: Hashable) -> None:
        """One window left the pipeline outside a wave (it was shed)."""
        with self._seq_lock:
            self._retire_slot_locked(sid)

    def _retire_slot_locked(self, sid: Hashable) -> None:
        """Decrement a stream's in-flight count; prune its bookkeeping at
        zero.  Caller holds ``_seq_lock``."""
        left = self._outstanding.get(sid, 1) - 1
        if left > 0:
            self._outstanding[sid] = left
        else:
            self._outstanding.pop(sid, None)
            self._ended.pop(sid, None)


def serve_windows(session, stream: Iterable, batch: int = 256,
                  path: str = "int",
                  backend: Optional[str] = None) -> Iterator[np.ndarray]:
    """Ordered stateless mapping of a window iterator — the
    ``Accelerator.serve`` semantics, executed by the streaming subsystem.

    Windows of shape (T, M) are assembled into fixed-size waves of
    ``batch``; predictions of shape (P,) are yielded in submission order.
    The final partial wave is PADDED to the static shape by repeating the
    last window; padded outputs are computed and dropped — exactly
    ``len(list(stream))`` predictions are yielded, never more.  Unlike the
    legacy synchronous path, wave *N+1* is assembled while wave *N*
    computes (the scheduler's double buffering), and a slow consumer
    exerts backpressure instead of unbounded buffering."""
    config = ServingConfig(batch=batch, path=path, backend=backend,
                           stateful=False, deadline_s=None)
    # Validate NOW (cached on the session): a bad path/backend or an
    # unquantised session fails at the call site, not at first iteration.
    # The server itself — two live threads — is only constructed once the
    # generator is actually consumed, so an abandoned call leaks nothing.
    session.compiled(path, backend)

    def _gen():
        server = StreamServer(session, config)
        try:
            for w in stream:
                server.submit(None, w)
                for r in server.poll():
                    yield r.y
            for r in server.drain():
                yield r.y
        finally:
            server.close(abandon=True)

    return _gen()
