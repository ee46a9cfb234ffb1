"""Smoke run of the serving path on a TPU: build -> train_qat -> quantize
-> StreamServer on the fused pallas engine, with device-resident stream
state, at the paper's published width (configs/lstm_pems.py: 1 LSTM
layer, H=20, T=6, (4,8) codes).

    python chip_smoke.py             # one chip: engines + StreamServer
    python chip_smoke.py --chips 4   # four one-chip replicas behind HashRing

Checks results, not speed: every row must be bit-exact with the ``ref``
engine run over the stream's concatenated windows, on the preferred
engine, with no retry, wave failure or degradation.  Exits non-zero when
no TPU is found.  The last line of a passing run is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

N_STREAMS = 1024        # = ServingConfig().max_streams
N_WINDOWS = 4
TRAIN_STEPS = 40
SEED = 0


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def versions() -> str:
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    return f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, libtpu {libtpu}"


def build_session():
    """The paper's model and accelerator, briefly QAT-trained from a seed,
    then quantised; plus the dataset it was trained on."""
    import repro
    from repro.configs.lstm_pems import CONFIG
    from repro.core.accelerator import PAPER_DEFAULT
    from repro.data.timeseries import pems_like_dataset

    data = pems_like_dataset(seq_len=CONFIG.seq_len, seed=SEED)
    acc = repro.build(CONFIG, PAPER_DEFAULT, seed=SEED)
    acc.train_qat(data, steps=TRAIN_STEPS, seed=SEED, log_every=TRAIN_STEPS,
                  log=lambda *a: None)
    acc.quantize()
    hist = acc.train_summary["history"]
    loss = float(hist[-1]["loss"]) if hist else float("nan")
    print(f"[build] lstm_pems H={CONFIG.hidden_size} "
          f"L={CONFIG.num_layers} T={CONFIG.seq_len}, {TRAIN_STEPS} QAT "
          f"steps (last logged loss {loss}), "
          f"quantised; plan backend={acc.plan['backend']}, "
          f"state_residency={acc.plan['state_residency']}", flush=True)
    return acc, data


def stream_windows(data, t_len: int):
    """``(N_STREAMS, N_WINDOWS, T, M)`` float32: each stream is a
    contiguous stretch of the traffic series cut into windows, so its
    concatenated windows are a real sensor sequence."""
    import numpy as np

    xs = np.concatenate([data["train"][0], data["test"][0]])   # x[i] = s[i:i+T]
    span = N_WINDOWS * t_len
    starts = (np.arange(N_STREAMS) * 7) % (len(xs) - span)
    return np.stack([np.stack([xs[s + w * t_len] for w in range(N_WINDOWS)])
                     for s in starts]).astype(np.float32)


def ref_oracle(acc, wins):
    """``(N_STREAMS, N_WINDOWS, P)``: window k of each stream = the ``ref``
    engine over the stream's windows 0..k concatenated, from the reset
    carry — no serving code involved."""
    import numpy as np

    n, k, t, m = wins.shape
    seq = wins.reshape(n, k * t, m)
    return np.stack([np.asarray(acc.infer(seq[:, :(w + 1) * t], path="int",
                                          backend="ref"))
                     for w in range(k)], axis=1)


def check_engines(acc, data) -> None:
    """``infer(path='int')`` bit-exact across the three engines."""
    import numpy as np

    x = data["test"][0][:512]
    outs = {b: np.asarray(acc.infer(x, path="int", backend=b))
            for b in ("ref", "xla", "pallas")}
    for b in ("xla", "pallas"):
        if not np.array_equal(outs[b], outs["ref"]):
            fail(f"infer(path='int', backend={b!r}) differs from ref on "
                 f"{int((outs[b] != outs['ref']).sum())} of "
                 f"{outs['ref'].size} outputs")
    print(f"[engines] infer(path='int') on {x.shape[0]} windows: ref, xla "
          f"and pallas bit-exact", flush=True)


def run_load(server, wins):
    """Submit every stream's windows in order; return the rows by
    (stream index, window)."""
    for w in range(wins.shape[1]):
        for s in range(wins.shape[0]):
            server.submit(f"sensor-{s}", wins[s, w])
    rows = server.drain(timeout=600)
    return {(int(r.stream_id.split("-")[1]), r.seq): r for r in rows}


def check_rows(rows, oracle, what: str) -> None:
    import numpy as np

    n, k = oracle.shape[:2]
    if len(rows) != n * k:
        fail(f"{what}: {len(rows)} rows for {n * k} windows")
    for (s, w), r in rows.items():
        if not r.ok:
            fail(f"{what}: sensor-{s} window {w} failed: {r.error}")
        if r.backend != "pallas":
            fail(f"{what}: sensor-{s} window {w} ran on {r.backend!r}, "
                 f"not pallas")
        if r.state_reset:
            fail(f"{what}: sensor-{s} window {w} lost its carry")
        if not np.array_equal(np.asarray(r.y), oracle[s, w]):
            fail(f"{what}: sensor-{s} window {w} differs from the ref "
                 f"oracle: {r.y} != {oracle[s, w]}")
    print(f"[{what}] {len(rows)} rows ({n} streams x {k} windows) "
          f"bit-exact with the ref oracle, all on pallas", flush=True)


def check_faults(faults, what: str) -> None:
    bad = {k: faults[k] for k in ("retries", "wave_failures", "degradations")
           if faults[k]}
    if bad:
        fail(f"{what}: guarded execution was not clean: {bad}")
    print(f"[{what}] retries=0 wave_failures=0 degradations=0", flush=True)


def one_chip(acc, data) -> None:
    from repro.serving import StreamServer

    check_engines(acc, data)
    wins = stream_windows(data, acc.model.seq_len)
    oracle = ref_oracle(acc, wins)
    with StreamServer(acc) as server:            # batch=256, max_streams=1024
        cfg = server.config
        if server.state_residency != "device":
            fail(f"state_residency resolved to {server.state_residency!r}, "
                 f"not 'device'")
        print(f"[server] batch={cfg.batch} max_streams={cfg.max_streams} "
              f"state_residency={server.state_residency} "
              f"ladder={server.guard.ladder}", flush=True)
        rows = run_load(server, wins)
        summary = server.metrics_summary()
    check_rows(rows, oracle, "server")
    check_faults(summary["faults"], "server")


def four_chips(acc, data) -> None:
    import jax

    import repro

    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--chips 4 needs four chips, found {len(devices)}")
    wins = stream_windows(data, acc.model.seq_len)
    oracle = ref_oracle(acc, wins)
    with repro.build_cluster(acc, 4, devices=devices[:4]) as cluster:
        placed = {}
        for name, server in cluster._servers.items():
            ids = {d.id for leaf in jax.tree.leaves(server._sessions[0].qparams)
                   for d in leaf.devices()}
            ids |= {d.id for d in server.states.table.devices()}
            if len(ids) != 1 or server.state_residency != "device":
                fail(f"replica {name}: qparams and state table span devices "
                     f"{sorted(ids)}, residency {server.state_residency!r}")
            placed[name] = ids.pop()
        if len(set(placed.values())) != 4:
            fail(f"replicas share devices: {placed}")
        print(f"[cluster] replicas on distinct devices {placed}", flush=True)
        rows = run_load(cluster, wins)
        summary = cluster.metrics_summary()
    check_rows(rows, oracle, "cluster")
    check_faults(summary["faults"], "cluster")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX's default device is {dev.platform} "
             f"({dev.device_kind}); this smoke run has no CPU fallback")
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        fail(f"cannot import repro from {here}/src: {e}")
    cache = use_compile_cache()
    print(f"platform={dev.platform}", flush=True)
    print(f"device_kind={dev.device_kind}", flush=True)
    print(f"device_count={len(jax.devices())}", flush=True)
    print(f"versions: {versions()}", flush=True)
    print(f"compile_cache={cache}", flush=True)

    acc, data = build_session()
    (four_chips if args.chips == 4 else one_chip)(acc, data)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
