"""The unified accelerator session API — ``repro.build``.

One configuration, compiled once, deployed everywhere (the paper's thesis:
a single *parameterised* design covers many deployment situations):

    import repro
    from repro.core.qlstm import QLSTMConfig
    from repro.core.accelerator import AcceleratorConfig

    acc = repro.build(QLSTMConfig(), AcceleratorConfig())
    acc.train_qat(data, steps=400)          # QAT (§6.1)
    acc.quantize()                          # float master -> integer codes
    y = acc.infer(x, path="int")            # bit-exact accelerator datapath
    for pred in acc.serve(stream, batch=256):
        ...                                 # batched real-time serving (§6)
    acc.report()                            # Table-2 plan + Table-4 energy

The session owns the float master params, the quantised params, and the
resolved ``plan()``; ``infer``/``serve`` dispatch through the backend
registry (`repro/backends/`: ``ref`` oracle | fused ``pallas`` kernel |
``xla`` scan) selected by the plan, with explicit override.  Jitted
entry points are cached per (path, backend) so repeated calls — the
serving hot path — never retrace.

``serve`` is the stateless compat wrapper; the production streaming layer
(named client streams, cross-window (h, c) carry, deadline-bounded waves,
serving metrics) is ``repro.serving.StreamServer``, built on
``compiled_stateful``/``init_state`` below (docs/SERVING.md).

See docs/API.md for the full lifecycle and the Table-2 parameter mapping.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Iterator, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import backends, cells
from repro.core import fixed_point as fxp
from repro.core.accelerator import (AcceleratorConfig, plan as resolve_plan,
                                    resolve_model, sync_accelerator)
from repro.core.energy import check_modelled_device, power_report
from repro.core.qlstm import QLSTMConfig

Array = jax.Array
Params = Dict[str, Any]

PATHS = ("float", "qat", "int")

# The paper's measured operating point (§6: 28.07 us/inference on the
# XC7S15) — the default latency anchor for report().
PAPER_LATENCY_S = 28.07e-6


def build(model: Optional[QLSTMConfig] = None,
          accel: Optional[AcceleratorConfig] = None, *,
          params: Optional[Params] = None, seed: int = 0) -> "Accelerator":
    """Compile a (model, accelerator) configuration into a session.

    This is the single entry point of the pipeline: Table-2 meta-parameters
    in, a deployable object out.  ``params`` seeds the session with
    existing float master weights; otherwise they are initialised from
    ``seed``."""
    return Accelerator(model or QLSTMConfig(), accel or AcceleratorConfig(),
                       params=params, seed=seed)


def build_cluster(session, n: int, *, devices=None, names=None, config=None,
                  **overrides):
    """A ready multi-replica serving cluster from one quantised session:
    ``session.replicate(n)`` (per-device pinned copies) behind a
    ``repro.serving.ClusterServer`` consistent-hash front door.

    ``devices`` pins explicit placement (``launch.mesh.serving_devices``
    semantics); ``names`` labels the replicas on the ring; ``config`` /
    keyword overrides set ``ClusterConfig`` and fall through to the
    per-replica ``ServingConfig`` (``batch=``, ``deadline_s=``, ...).
    docs/SERVING.md §Scaling out is the deployment guide."""
    # Lazy: the serving package (threaded scheduler) only loads when a
    # cluster is actually built, same posture as the other serving entry
    # points.
    from repro.serving.cluster import ClusterServer

    replicas = session.replicate(n, devices=devices)
    return ClusterServer(replicas, config=config, names=names, **overrides)


class Accelerator:
    """A built accelerator: params + resolved plan + dispatchable datapaths.

    Lifecycle: ``build`` -> ``train_qat`` -> ``quantize`` -> ``infer`` /
    ``serve`` / ``report``.  Stage methods return ``self`` for chaining."""

    def __init__(self, model: QLSTMConfig, accel: AcceleratorConfig, *,
                 params: Optional[Params] = None, seed: int = 0):
        # Canonicalise both directions once: AcceleratorConfig is the source
        # of truth; legacy model-side knobs are honoured with a warning.
        self.model = resolve_model(model, accel)
        self.accel = sync_accelerator(self.model, accel)
        # The cell spec owns every datapath and the param/state trees;
        # KeyError here (unknown cell id) fails the build immediately.
        self.cell = cells.get(self.model.cell)
        self.plan = resolve_plan(self.model, self.accel)
        if self.accel.backend != "auto":
            # Fail at build, not first infer: an explicit engine that cannot
            # run this configuration would otherwise be reported by plan()/
            # report() as if it could.
            backends.select(self.model, self.accel)
        self.params: Params = (params if params is not None
                               else self.cell.init_params(
                                   self.model, jax.random.key(seed)))
        self.qparams: Optional[Params] = None
        self.train_summary: Optional[Dict[str, Any]] = None
        self._jitted: Dict[Tuple[str, str], Any] = {}
        # Set by replicate(): the jax.Device this session's params are
        # committed to (None = uncommitted, jax's default placement).
        self.device = None

    # -- training -----------------------------------------------------------

    def train_qat(self, data, steps: int = 200, *, batch: int = 64,
                  lr: float = 3e-3, seed: int = 0,
                  ckpt_dir: Optional[str] = None, log_every: int = 50,
                  log=print) -> "Accelerator":
        """Quantisation-aware training (§6.1): MSE regression with STE
        fake-quant at every hardware rounding point.

        ``data``: either the dict from ``data.timeseries.pems_like_dataset``
        (its ``"train"`` split is used) or an ``(x, y)`` tuple with
        x (N, T, M) float and y (N, P).  Fault tolerance comes from the
        shared ``Trainer`` (checkpoint/resume in ``ckpt_dir``,
        SIGTERM/SIGINT checkpoint-and-exit)."""
        from repro.training.optimizer import (OptConfig, apply_updates,
                                              init_opt_state)
        from repro.training.train_loop import LoopConfig, Trainer

        xtr, ytr = data["train"] if isinstance(data, dict) else data
        cfg = self.model
        opt_cfg = OptConfig(name="adamw", lr=lr, weight_decay=0.0,
                            warmup_steps=min(20, max(1, steps // 10)),
                            total_steps=steps)
        state = {"params": self.params,
                 "opt": init_opt_state(self.params, opt_cfg),
                 "step": jnp.zeros((), jnp.int32)}

        forward_qat = self.cell.forward_qat

        @jax.jit
        def step_fn(state, batch_d):
            def loss(p):
                y = forward_qat(p, batch_d["x"], cfg)
                mse = jnp.mean(jnp.square(y - batch_d["y"]))
                return mse, {"mse": mse}

            (l, m), g = jax.value_and_grad(loss, has_aux=True)(state["params"])
            p, o, om = apply_updates(state["params"], g, state["opt"], opt_cfg)
            return ({"params": p, "opt": o, "step": state["step"] + 1},
                    {"loss": l, **m, **om})

        def batch_fn(step):
            rng = np.random.default_rng((seed, step))
            idx = rng.integers(0, len(xtr), batch)
            return {"x": jnp.asarray(xtr[idx]), "y": jnp.asarray(ytr[idx])}

        trainer = Trainer(step_fn, state, batch_fn,
                          LoopConfig(total_steps=steps, ckpt_dir=ckpt_dir,
                                     ckpt_every=100, log_every=log_every),
                          log=log)
        trainer.maybe_resume()
        self.train_summary = trainer.run()
        self.params = trainer.state["params"]
        # Params changed: stale quantisation and jit closures must go.
        self.qparams = None
        self._jitted.clear()
        return self

    # -- quantisation -------------------------------------------------------

    def quantize(self) -> "Accelerator":
        """Float master weights -> integer codes for the hardware datapath
        (weights in (a,b); biases at the wide accumulator precision)."""
        self.qparams = self.cell.quantize_params(self.params, self.model)
        # Cached int-path closures (stateless AND stateful) captured the
        # previous codes; drop them.
        self._jitted = {k: fn for k, fn in self._jitted.items()
                        if not k[0].startswith("int")}
        return self

    # -- inference ----------------------------------------------------------

    def infer(self, x: Union[Array, np.ndarray], path: str = "float",
              backend: Optional[str] = None) -> Array:
        """x: (B, T, M) float -> (B, P) float.

        ``path``: ``float`` (training semantics), ``qat`` (fake-quant
        graph), ``int`` (bit-exact integer datapath — dequantised at the
        boundary).  ``backend`` overrides the plan's engine for the int
        path (``ref`` | ``pallas`` | ``xla``)."""
        return self._fn(path, backend)(jnp.asarray(x))

    def infer_int(self, x_int: Union[Array, np.ndarray],
                  backend: Optional[str] = None) -> Array:
        """Integer codes in, integer codes out — the raw accelerator
        boundary, for bit-exactness checks and benchmarks."""
        self._require_quantized()
        bk = backends.select(self.model, self.accel, override=backend)
        return bk.run(self.qparams, jnp.asarray(x_int), self.model, self.accel)

    def compiled(self, path: str = "int", backend: Optional[str] = None):
        """The cached jitted entry point for (path, backend): a callable
        ``(B, T, M) float -> (B, P) float``.  Useful for benchmarking the
        datapath without per-call dispatch overhead."""
        return self._fn(path, backend)

    def init_state(self, batch: int):
        """The reset cross-window carry for ``compiled_stateful``: per
        layer, the cell spec's ``state_arity`` zero int32 code arrays of
        shape (batch, hidden) — what the accelerator's state registers
        hold before a stream's first window (for ``cell='lstm'`` this is
        the classic per-layer (h, c) pair)."""
        return cells.init_state(self.model, batch)

    def compiled_stateful(self, backend: Optional[str] = None):
        """The cached jitted STATEFUL int-path entry point: a callable
        ``((B, T, M) float, state) -> ((B, P) float, new_state)`` where
        ``state`` is the per-layer (h, c) carry (``init_state`` for a fresh
        stream).  This is the datapath behind ``repro.serving`` — feeding a
        stream window-by-window with the carried state is bit-identical to
        one call on the concatenated sequence.  Every engine is
        stateful-capable (``ref`` | ``pallas`` | ``xla``): the fused
        pallas kernel seeds its (h, c) VMEM scratch from the carry, so
        ``auto`` (the plan's ``stateful_backend``) resolves exactly like
        the stateless path — docs/API.md §Backends has the selection
        order."""
        self._require_quantized()
        bk = backends.select_stateful(self.model, self.accel,
                                      override=backend)
        key = ("int_stateful", bk.name)
        if key in self._jitted:
            return self._jitted[key]
        qparams, model, accel = self.qparams, self.model, self.accel

        def stateful_path(x, state):
            x_int = fxp.quantize(x, model.fxp)
            y_int, new_state = bk.run_stateful(qparams, x_int, model, accel,
                                               state)
            return fxp.dequantize(y_int, model.fxp), new_state

        fn = jax.jit(stateful_path)
        self._jitted[key] = fn
        return fn

    def init_state_table(self, max_slots: int) -> Array:
        """The reset DEVICE-RESIDENT state table for
        ``compiled_stateful_slots``: a zero int32 array of
        ``kernels.qlstm_cell.state_table_shape(max_slots + 2, L, S, H)``,
        where ``(L, S, H)`` is the cell's ``plan()['state_shape']`` (S is
        the carry arity — (h, c) for the LSTM, a single h for GRU/rGLRU),
        committed to this session's device when the session is pinned
        (``replicate``).  Slots ``max_slots`` and ``max_slots + 1`` are
        the conventions of the slot kernel: the always-zero RESET slot
        fresh/evicted streams gather from, and the write-only TRASH slot
        retired/padding rows scatter to
        (``kernels/qlstm_cell.qlstm_seq_slot_pallas``)."""
        from repro.kernels.qlstm_cell import state_table_shape
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        tbl = jnp.zeros(state_table_shape(max_slots + 2,
                                          *self.plan["state_shape"]),
                        jnp.int32)
        return jax.device_put(tbl, self.device) if self.device is not None \
            else tbl

    def compiled_stateful_slots(self, backend: Optional[str] = None):
        """The cached jitted DEVICE-RESIDENT-state entry point: a callable
        ``((B, T, M) float, table, gather_slots, scatter_slots) ->
        ((B, P) float, new_table)`` where ``table`` is the persistent
        per-stream carry table (``init_state_table``) and the slot vectors
        are (B,) int32 table-row ids.  Per wave the host ships only the
        float window batch and the two slot vectors — no (h, c) arrays
        cross the host/device boundary, which is what
        ``plan()['state_residency'] == 'device'`` buys the serving tier.
        The fused pallas engine gathers/scatters inside the kernel;
        ``ref``/``xla`` run the XLA-level adapter, so every rung of the
        degradation ladder accepts the same arguments.  Bit-identical to
        ``compiled_stateful`` fed the host-gathered carries."""
        self._require_quantized()
        bk = backends.select_stateful(self.model, self.accel,
                                      override=backend)
        key = ("int_stateful_slots", bk.name)
        if key in self._jitted:
            return self._jitted[key]
        impl = bk.run_stateful_slots
        if impl is None:
            from repro.backends.common import run_slots_via_state
            impl = lambda *a: run_slots_via_state(bk.run_stateful, *a)
        qparams, model, accel = self.qparams, self.model, self.accel

        def slot_path(x, table, gather_slots, scatter_slots):
            x_int = fxp.quantize(x, model.fxp)
            y_int, new_table = impl(qparams, x_int, model, accel, table,
                                    gather_slots, scatter_slots)
            return fxp.dequantize(y_int, model.fxp), new_table

        fn = jax.jit(slot_path)
        self._jitted[key] = fn
        return fn

    def degradation_ladder(self, backend: Optional[str] = None,
                           stateful: bool = True) -> Tuple[str, ...]:
        """Ordered engine names the serving tier falls back through on
        repeated backend failure (fastest first; all bit-identical on the
        int path, so degrading changes latency, never results).  ``backend``
        pins the preferred head of the ladder; ``stateful`` restricts it to
        engines able to carry (h, c) across windows — see
        ``backends.degradation_ladder`` and docs/SERVING.md §Reliability."""
        return backends.degradation_ladder(self.model, self.accel,
                                           override=backend,
                                           stateful=stateful)

    def replicate(self, n: int, devices=None) -> "list[Accelerator]":
        """``n`` device-pinned replica sessions of this (quantised)
        accelerator — the per-replica substrate of the serving cluster
        (docs/SERVING.md §Scaling out).

        Each replica shares this session's configuration and weights, with
        its params AND integer codes committed to its own device
        (``sharding.partition.pin_to_device``), so jit executes each
        replica's datapath on that device and a stream's (h, c) carry
        stays replica-local under ``ClusterServer`` routing.  Devices come
        from ``launch.mesh.serving_devices``: round-robin over
        ``jax.devices()`` by default (oversubscribing when there are fewer
        than ``n`` — the CPU-test posture), or an explicit ``devices``
        list for controlled placement.  The codes are pinned, NOT
        re-quantised, so every replica is bit-identical to this session."""
        from repro.launch.mesh import serving_devices
        from repro.sharding.partition import pin_to_device

        self._require_quantized()
        out = []
        for d in serving_devices(n, devices):
            rep = Accelerator(self.model, self.accel,
                              params=pin_to_device(self.params, d))
            rep.qparams = pin_to_device(self.qparams, d)
            rep.device = d
            out.append(rep)
        return out

    def _require_quantized(self):
        if self.qparams is None:
            raise RuntimeError(
                "the session is not quantised: call .quantize() before the "
                "int path (build -> train_qat -> quantize -> infer/serve)")

    def _fn(self, path: str, backend: Optional[str]):
        """Cached jitted entry point for (path, backend)."""
        if path not in PATHS:
            raise ValueError(f"path must be one of {PATHS}, got {path!r}")
        if backend is not None and path != "int":
            raise ValueError(
                f"backend={backend!r} only applies to path='int'; the "
                f"{path!r} path runs the float graph")
        model = self.model
        if path == "int":
            self._require_quantized()
            # Key on the RESOLVED engine: plan-auto and an explicit request
            # for the same engine share one compiled closure.
            bk = backends.select(model, self.accel, override=backend)
            key = (path, bk.name)
        else:
            key = (path, "plan")
        if key in self._jitted:
            return self._jitted[key]

        if path == "float":
            params, fwd = self.params, self.cell.forward_float
            fn = jax.jit(lambda x: fwd(params, x, model))
        elif path == "qat":
            params, fwd = self.params, self.cell.forward_qat
            fn = jax.jit(lambda x: fwd(params, x, model))
        else:
            qparams, accel = self.qparams, self.accel

            def int_path(x):
                x_int = fxp.quantize(x, model.fxp)
                y_int = bk.run(qparams, x_int, model, accel)
                return fxp.dequantize(y_int, model.fxp)

            fn = jax.jit(int_path)
        self._jitted[key] = fn
        return fn

    # -- serving ------------------------------------------------------------

    def serve(self, stream: Iterable[Union[Array, np.ndarray]],
              batch: int = 256, path: str = "int",
              backend: Optional[str] = None) -> Iterator[np.ndarray]:
        """Batched streaming inference — the paper's deployment scenario
        (§6: real-time samples/s).  Thin compat wrapper over
        ``repro.serving.serve_windows`` (stateless; for cross-window state
        carry and multi-client multiplexing use
        ``repro.serving.StreamServer``).

        ``stream`` yields windows of shape (T, M); predictions of shape
        (P,) are yielded in submission order.  Windows are assembled into
        fixed-size waves of ``batch`` so the jitted datapath sees one
        static shape.  **Final-partial-wave padding semantics**: when the
        stream ends mid-wave, the wave is padded to ``batch`` by repeating
        the last real window; the padded rows are computed and DROPPED —
        exactly one prediction per input window is yielded, never the
        padding's (pinned by ``tests/test_serving.py``)."""
        # Validate NOW, not at first iteration: serve() itself is a plain
        # function so a bad path/backend or an unquantised session fails at
        # the call site, not deep inside whatever consumes the generator.
        from repro.serving import serve_windows
        return serve_windows(self, stream, batch=batch, path=path,
                             backend=backend)

    def measure_scenario(self, scenario, *, batch: Optional[int] = None,
                         replicas: int = 1,
                         state_residency: str = "auto") -> Dict[str, Any]:
        """Measure THIS session at a serving operating point.

        ``scenario`` is a ``repro.explore.ServingScenario``; a short real
        ``StreamServer`` (or ``ClusterServer`` when ``replicas > 1``) run
        is stood up and the ``metrics_summary()``-derived objectives
        returned (samples/s, p50/p95/p99 ms, deadline-miss rate,
        GOP/s/W).  This is the re-measurement hook for an autotuned
        operating point: after ``explore.autotune(..., scenario=...)``,
        ``session.measure_scenario(scenario)`` verifies the deployed
        session still meets the SLO it was selected under."""
        return scenario.run(self, batch=batch, replicas=replicas,
                            state_residency=state_residency)

    # -- reporting ----------------------------------------------------------

    def report(self, latency_s: float = PAPER_LATENCY_S,
               batch: int = 1) -> Dict[str, Any]:
        """Resolved plan + op/footprint accounting + the Table-4-style
        energy report at the given operating point.  Raises on a TPU the
        energy model has no constants for (``core.energy``)."""
        check_modelled_device(self.device or jax.devices()[0])
        ops = self.cell.ops_per_inference(self.model)
        energy = power_report(
            flops=ops * batch, hbm_bytes=self.plan["weight_bytes"],
            ici_bytes=0, latency_s=latency_s,
            unit=self.plan["compute_unit"],
            dtype="int8" if self.accel.fxp.total_bits <= 8 else "bf16")
        return {
            "model": dataclasses.asdict(self.model),
            # JSON-friendly: the plan's FixedPointConfig becomes a dict too.
            "plan": {**self.plan,
                     "fxp": dataclasses.asdict(self.plan["fxp"])},
            "backend": self.plan["backend"],
            "backends_supported": backends.supported_backends(self.model,
                                                              self.accel),
            # Engines able to carry (h, c) across windows — the
            # repro.serving capability surface for this configuration.
            "stateful_backends": backends.stateful_backends(self.model,
                                                            self.accel),
            "ops_per_inference": ops,
            "weight_bytes": self.plan["weight_bytes"],
            "quantized": self.qparams is not None,
            "energy": energy,
        }

    def __repr__(self) -> str:
        return (f"Accelerator(fxp={self.model.fxp}, "
                f"unit={self.plan['compute_unit']}, "
                f"wmem={self.plan['weight_memory']}, "
                f"alu={self.plan['alu_mode']}, "
                f"hs={self.plan['hs_method']}, "
                f"backend={self.plan['backend']}, "
                f"quantized={self.qparams is not None})")
