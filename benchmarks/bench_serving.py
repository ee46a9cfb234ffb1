"""Streaming-serving benchmark — achieved samples/s vs the paper's §6
headline (32 873 samples/s at 11.89 GOP/s/W on the XC7S15).

  PYTHONPATH=src python -m benchmarks.bench_serving [--smoke]
      [--stateful-backend ref,xla,pallas]
      [--fault-rate F] [--chaos] [--replicas 1,2,4] [out.json]

Three scenario families through `repro.serving`:

  * ``stateless`` — the ``Accelerator.serve`` wave path (the paper's
    single-stream real-time deployment, batched).
  * ``stateful[<backend>]`` — many named client streams multiplexed
    through ``StreamServer`` with cross-window (h, c) carry (the
    ROADMAP's many-user scenario; one window per stream per wave), once
    per requested stateful engine, so the artifact records per-backend
    samples/s and GOP/s/W.  ``--stateful-backend`` takes a comma list of
    ``ref`` | ``xla`` | ``pallas``; the default is the plan's
    ``stateful_backend`` (the fused pallas kernel — off-TPU it runs
    interpret mode, so CI's ``--smoke`` measures the pallas-interpret
    point and the numbers track the trajectory, not the FPGA's).

  * ``cluster[rN]`` (``--replicas`` comma list) — the same multiplexed
    load through ``repro.build_cluster``: N device-pinned replica servers
    behind the consistent-hash front door, schedulers running in
    parallel.  The artifact records the cluster-AGGREGATE samples/s (over
    the common wall), the per-replica breakdown (each replica's own
    p50/p95/p99), and the ring block.  On CPU, scaling needs
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before jax
    initialises (how CI runs the ``--replicas 1,2`` smoke).

Every stateful scenario keeps its carries in the accelerator-resident
slot table and ships only (B,) slot-id vectors per wave (docs/SERVING.md
§The carry table); its summary's ``state_transfer`` block counts them.

Chaos axes (the PR-6 reliability layer, ``repro.serving.faults``):
``--fault-rate F`` runs the stateful scenarios under a seeded
:class:`FaultInjector` raising ``InjectedFault`` from a fraction ``F`` of
wave executions — the benchmark then measures the RESILIENT throughput
(retries/backoff absorb the faults) and each scenario's summary carries
the ``faults`` block (retries, sheds, degradations, injected counts).
``--chaos`` additionally injects latency spikes and state loss/corruption
at fixed small rates, the full drill described in docs/SERVING.md
§Reliability.

Writes ``BENCH_serving.json``: per-scenario achieved samples/s, per-wave
latency p50/p95/p99, GOP/s/W at the measured operating point, the
``faults``/``health`` reliability blocks, and the paper reference
numbers.  Render with
``python -m repro.analysis.report --serving BENCH_serving.json``.
CI runs ``--smoke --fault-rate 0.1`` (small waves, CPU interpret mode,
seeded chaos) and uploads the artifact.
"""

import json
import sys

PAPER_SAMPLES_PER_S = 32873.0     # §6, XC7S15 @ 204 MHz
PAPER_GOPS_PER_WATT = 11.89       # Table 4

# 2: stateful scenarios keyed "stateful[<backend>]" with a "backend" field
# (was one "stateful" key with the implicit plan engine).
# 3: scenario summaries carry the "faults"/"health" reliability blocks and
# the payload records the chaos axes under "chaos".
# 4: --replicas adds "cluster[rN]" scenarios (ClusterServer over N
# device-pinned replicas): aggregate samples/s over the common wall plus
# "samples_per_s_sum", the per-replica metrics breakdown under "replicas"
# (each with its own p99), and the "ring" routing block.
# 5: --state-residency adds per-placement stateful points keyed
# "stateful[<backend>@<residency>]" (the bare "stateful[<backend>]" key is
# kept for the default auto run); stateful summaries carry the resolved
# "state_residency" and the "state_transfer" per-wave byte counters
# (to_device/from_device pinned at 0 on the device point).
# 6: --cell (comma list from repro.cells.available()) adds one stateful
# point per NON-lstm cell at its plan defaults, keyed
# "stateful[<cell>@<backend>@<residency>]" with both parts resolved (gru/
# rglru resolve to xla@host — no fused kernel); "lstm" in the list is a
# no-op because the default scenarios ARE the lstm points, so every
# pre-v6 key (and its numbers) is byte-identical for a given config.
# 7: one carry placement (the device slot table): --state-residency and
# the "stateful[<backend>@<residency>]" keys are gone, the per-cell keys
# are "stateful[<cell>@<backend>]", and "state_transfer" no longer carries
# the to_device/from_device byte counters (they could only read 0).
SCHEMA_VERSION = 7

STATEFUL_BACKENDS = ("ref", "xla", "pallas")


def _scenario_stateless(sess, n_windows, batch):
    """Ordered stateless serving (the Accelerator.serve path)."""
    import numpy as np
    rng = np.random.default_rng(0)
    model = sess.model
    x = rng.uniform(0, 1, (n_windows, model.seq_len,
                           model.input_size)).astype(np.float32)
    from repro.serving import ServingConfig, StreamServer
    cfg = ServingConfig(batch=batch, stateful=False, deadline_s=None)
    with StreamServer(sess, cfg) as srv:
        # Warm-up wave compiles the datapath outside the measured interval.
        for w in x[:batch]:
            srv.submit(None, w)
        srv.drain()
    with StreamServer(sess, cfg) as srv:
        for w in x:
            srv.submit(None, w)
        srv.drain()
        return srv.metrics_summary()


def _injector(fault_rate, chaos, seed=42):
    """The seeded chaos harness for the requested axes (None when both are
    off — the plain, uninjected benchmark)."""
    if not fault_rate and not chaos:
        return None
    from repro.serving import FaultConfig, FaultInjector
    cfg = FaultConfig(
        wave_fault_rate=float(fault_rate or 0.0),
        latency_spike_rate=0.05 if chaos else 0.0,
        latency_spike_s=0.002,
        state_loss_rate=0.02 if chaos else 0.0,
        state_corrupt_rate=0.0,    # corruption breaks bit-exactness on
    )                              # purpose; keep it to the chaos TESTS
    return FaultInjector(cfg, seed=seed)


def _scenario_stateful(sess, n_streams, windows_per_stream, batch,
                       backend=None, fault_rate=0.0, chaos=False):
    """Multiplexed named streams with cross-window carry on ``backend``
    (None = the plan's ``stateful_backend``); ``fault_rate``/``chaos``
    run the scenario under the seeded FaultInjector."""
    import numpy as np
    rng = np.random.default_rng(1)
    model = sess.model
    xs = rng.uniform(0, 1, (n_streams, windows_per_stream, model.seq_len,
                            model.input_size)).astype(np.float32)
    from repro.serving import ResiliencePolicy, ServingConfig, StreamServer
    cfg = ServingConfig(batch=batch, deadline_s=0.05, backend=backend,
                        max_streams=max(16, n_streams),
                        resilience=ResiliencePolicy(
                            max_retries=3, backoff_base_s=0.0005))
    with StreamServer(sess, cfg,
                      fault_injector=_injector(fault_rate, chaos)) as srv:
        srv.submit("warmup", xs[0, 0])      # compile outside the clock
        srv.drain()
        srv.end_stream("warmup")
        srv.reset_metrics()                 # compile stays outside the clock
        for w in range(windows_per_stream):
            for s in range(n_streams):
                srv.submit(f"s{s}", xs[s, w])
        srv.drain()
        summary = srv.metrics_summary()
    summary["backend"] = backend or sess.plan["stateful_backend"]
    return summary


def _scenario_cluster(sess, n_replicas, n_streams, windows_per_stream,
                      batch):
    """N device-pinned replica servers behind the consistent-hash front
    door (``repro.build_cluster``): each stream sticks to one replica, the
    replicas' schedulers run in parallel, and the summary reports the
    cluster-aggregate samples/s with the per-replica breakdown.  On CI the
    CPU "devices" come from XLA_FLAGS=--xla_force_host_platform_device_
    count, so the scaling trend is the artifact, not absolute numbers."""
    import numpy as np
    import repro
    rng = np.random.default_rng(2)
    model = sess.model
    xs = rng.uniform(0, 1, (n_streams, windows_per_stream, model.seq_len,
                            model.input_size)).astype(np.float32)
    with repro.build_cluster(sess, n_replicas, batch=batch, deadline_s=0.05,
                             max_streams=max(16, n_streams)) as cluster:
        cluster.warmup(xs[0, 0])            # compile every replica's
        for w in range(windows_per_stream):  # datapath outside the clock
            for s in range(n_streams):
                cluster.submit(f"s{s}", xs[s, w])
        cluster.drain()
        summary = cluster.metrics_summary()
    summary["backend"] = f"cluster[{n_replicas}x" \
                         f"{sess.plan['stateful_backend']}]"
    return summary


def _row(name, summary):
    return (f"serving_{name}", summary["latency_ms"]["p50"] * 1e3,
            round(summary["samples_per_s"], 1))


def run(smoke: bool = False, out_path: str = "BENCH_serving.json",
        stateful_backends=None, fault_rate: float = 0.0,
        chaos: bool = False, replicas=None, cell_axis=None):
    """Measure the stateless scenario plus one stateful scenario per
    requested engine (under the seeded chaos axes when
    requested), one cluster scenario per requested replica count, and one
    plan-default stateful point per requested non-lstm cell; write the
    JSON payload and return the CSV-ish rows the benchmark harness
    prints."""
    import dataclasses

    import repro
    from repro import cells as cell_registry
    for c in (cell_axis or ()):
        if c not in cell_registry.available():
            raise SystemExit(f"unknown cell {c!r}; "
                             f"choose from {cell_registry.available()}")
    sess = repro.build().quantize()     # the paper's default configuration
    backends = tuple(stateful_backends) if stateful_backends \
        else (sess.plan["stateful_backend"],)
    for b in backends:
        if b not in STATEFUL_BACKENDS:
            raise SystemExit(f"unknown stateful backend {b!r}; "
                             f"choose from {STATEFUL_BACKENDS}")
    scenarios = {}
    if smoke:
        scenarios["stateless"] = _scenario_stateless(sess, n_windows=64,
                                                     batch=16)
        for b in backends:
            scenarios[f"stateful[{b}]"] = _scenario_stateful(
                sess, n_streams=8, windows_per_stream=4, batch=8,
                backend=b, fault_rate=fault_rate, chaos=chaos)
        for n in (replicas or ()):
            # Enough streams that every replica still fills waves at the
            # largest requested fan-out — the scaling trend needs the
            # per-replica occupancy to survive the split (and enough
            # compute per wave that the parallel schedulers have work to
            # overlap on a multi-core runner).
            scenarios[f"cluster[r{n}]"] = _scenario_cluster(
                sess, n_replicas=n, n_streams=48, windows_per_stream=8,
                batch=12)
    else:
        scenarios["stateless"] = _scenario_stateless(sess, n_windows=4096,
                                                     batch=256)
        for b in backends:
            scenarios[f"stateful[{b}]"] = _scenario_stateful(
                sess, n_streams=128, windows_per_stream=16, batch=64,
                backend=b, fault_rate=fault_rate, chaos=chaos)
        for n in (replicas or ()):
            scenarios[f"cluster[r{n}]"] = _scenario_cluster(
                sess, n_replicas=n, n_streams=128, windows_per_stream=16,
                batch=32)

    # The cell axis: one stateful point per non-lstm cell at its OWN plan
    # defaults (backend resolved by the registry — gru/rglru have no fused
    # kernel, so they land on xla).  "lstm" is skipped:
    # the scenarios above already measure it under the pre-v6 keys, which
    # must stay byte-identical.
    for c in (cell_axis or ()):
        if c == "lstm":
            continue
        sess_c = repro.build(
            dataclasses.replace(sess.model, cell=c)).quantize()
        key = f"stateful[{c}@{sess_c.plan['stateful_backend']}]"
        if smoke:
            scenarios[key] = _scenario_stateful(
                sess_c, n_streams=8, windows_per_stream=4, batch=8,
                fault_rate=fault_rate, chaos=chaos)
        else:
            scenarios[key] = _scenario_stateful(
                sess_c, n_streams=64, windows_per_stream=8, batch=32,
                fault_rate=fault_rate, chaos=chaos)
        scenarios[key]["cell"] = c

    payload = {
        "suite": "serving",
        "schema_version": SCHEMA_VERSION,
        "smoke": smoke,
        "cells": list(cell_axis or ()),
        "chaos": {"fault_rate": float(fault_rate), "chaos": bool(chaos)},
        "paper": {"samples_per_s": PAPER_SAMPLES_PER_S,
                  "gops_per_watt": PAPER_GOPS_PER_WATT},
        "scenarios": scenarios,
    }
    for s in payload["scenarios"].values():
        s["vs_paper_samples_per_s"] = s["samples_per_s"] / PAPER_SAMPLES_PER_S
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[serving] wrote {out_path}", file=sys.stderr)
    return [_row(k, v) for k, v in payload["scenarios"].items()]


def main(argv):
    """CLI: ``[--smoke] [--stateful-backend ref,xla,pallas]
    [--cell lstm,gru,rglru]
    [--fault-rate F] [--chaos] [--replicas 1,2,4] [out.json]``."""
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    smoke = "--smoke" in argv
    chaos = "--chaos" in argv
    stateful_backends = None
    cell_axis = None
    fault_rate = 0.0
    replicas = None
    paths = []
    it = iter(a for a in argv if a not in ("--smoke", "--chaos"))
    for a in it:
        if a == "--stateful-backend" or a.startswith("--stateful-backend="):
            val = a.split("=", 1)[1] if "=" in a else next(it, "")
            stateful_backends = [b for b in val.split(",") if b]
            if not stateful_backends:
                raise SystemExit(
                    "--stateful-backend needs a comma list of "
                    f"{','.join(STATEFUL_BACKENDS)}")
        elif a == "--cell" or a.startswith("--cell="):
            val = a.split("=", 1)[1] if "=" in a else next(it, "")
            cell_axis = [c for c in val.split(",") if c]
            if not cell_axis:
                raise SystemExit("--cell needs a comma list of registered "
                                 "cells (see repro.cells.available())")
        elif a == "--fault-rate" or a.startswith("--fault-rate="):
            val = a.split("=", 1)[1] if "=" in a else next(it, "")
            try:
                fault_rate = float(val)
            except ValueError:
                raise SystemExit(f"--fault-rate needs a float, got {val!r}")
            if not 0.0 <= fault_rate < 1.0:
                raise SystemExit(
                    f"--fault-rate must be in [0, 1), got {fault_rate}")
        elif a == "--replicas" or a.startswith("--replicas="):
            val = a.split("=", 1)[1] if "=" in a else next(it, "")
            try:
                replicas = [int(n) for n in val.split(",") if n]
            except ValueError:
                raise SystemExit(
                    f"--replicas needs a comma list of ints, got {val!r}")
            if not replicas or any(n < 1 for n in replicas):
                raise SystemExit(
                    f"--replicas needs positive counts, got {val!r}")
        elif a.startswith("--"):
            raise SystemExit(f"unknown flag {a!r}")
        else:
            paths.append(a)
    rows = run(smoke=smoke, out_path=paths[0] if paths
               else "BENCH_serving.json", stateful_backends=stateful_backends,
               fault_rate=fault_rate, chaos=chaos, replicas=replicas,
               cell_axis=cell_axis)
    print("name,us_per_call,derived")
    for n, us, d in rows:
        print(f"{n},{us:.2f},{d}")


if __name__ == "__main__":
    main(sys.argv[1:])
