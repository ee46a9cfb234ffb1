"""Real-time LSTM inference — the paper's deployment scenario (§6: 32873
samples/s on the XC7S15 at 204 MHz) — in both serving forms.

Part 1 streams windows through ``Accelerator.serve`` (stateless fixed-size
waves — the jitted engine sees one static shape) and reports samples/s
plus the projected TPU-side GOP/s and GOP/s/W from the energy model.
Part 2 is the production form (docs/SERVING.md): many named sensor
streams multiplexed through ``repro.serving.StreamServer``, each stream's
LSTM (h, c) carried across windows — predictions see the stream's whole
history, not just the current window.

Run:  PYTHONPATH=src python examples/serve_lstm_realtime.py
"""
import time

import jax
import numpy as np

import repro
from repro.compile_cache import use_compile_cache
from repro.core.accelerator import PAPER_DEFAULT, PAPER_NO_MXU
from repro.core.qlstm import QLSTMConfig
from repro.data.timeseries import pems_like_dataset
from repro.serving import StreamServer

use_compile_cache()

cfg = QLSTMConfig()
data = pems_like_dataset(seq_len=cfg.seq_len)
x, y = data["test"]

acc = repro.build(cfg, PAPER_DEFAULT, seed=0)
acc.train_qat(data, steps=200, log_every=100).quantize()

BATCH = 256
# Whole waves only, within the test set: no final-wave padding in the clock.
N = (min(BATCH * 20, len(x)) // BATCH) * BATCH
# Warm-up wave compiles the serving datapath once.
next(acc.serve(iter(x[:BATCH]), batch=BATCH))

t0 = time.perf_counter()
preds = list(acc.serve(iter(x[:N]), batch=BATCH))
dt = time.perf_counter() - t0
sps = len(preds) / dt
ops = acc.report()["ops_per_inference"]
dev = jax.devices()[0]
print(f"[serve] {len(preds)} samples in {dt:.2f}s = {sps:,.0f} samples/s "
      f"on {dev.platform} ({dev.device_kind}; paper: 32,873 samples/s on "
      f"FPGA)")
print(f"[serve] equivalent GOP/s at this rate: {sps*ops/1e9:.3f}")
print(f"[serve] stream MSE vs targets: "
      f"{float(np.mean((np.stack(preds) - y[:N]) ** 2)):.5f}")

for name, accel in [("mxu (DSP)", PAPER_DEFAULT), ("vpu (no-DSP)", PAPER_NO_MXU)]:
    # project: TPU latency bound by weight streaming + compute at unit peak
    rep = repro.build(cfg, accel).report(latency_s=BATCH / 32873.0,
                                         batch=BATCH)["energy"]
    print(f"[energy/{name:12s}] GOP/s/W={rep['gops_per_watt']:.2f} "
          f"total_W={rep['total_w']:.1f} (paper: 11.89 GOP/s/W)")

# --- Part 2: multiplexed STATEFUL streams (repro.serving) -------------------
# 16 sensors, 8 windows each; every sensor's (h, c) carries across its
# windows, so window k sees the sensor's whole history — bit-identical to
# running each sensor's concatenated sequence in one shot.
N_STREAMS, N_WINDOWS = 16, 8
with StreamServer(acc, batch=N_STREAMS, deadline_s=0.02,
                  max_streams=N_STREAMS) as server:
    server.submit("warmup", x[0])          # compile outside the clock
    server.drain()
    server.end_stream("warmup")
    server.reset_metrics()
    for w in range(N_WINDOWS):
        for s in range(N_STREAMS):
            server.submit(f"sensor-{s}", x[(s * N_WINDOWS + w) % len(x)])
    server.drain()
    m = server.metrics_summary()
print(f"[stream] {m['samples']} windows over {N_STREAMS} stateful streams: "
      f"{m['samples_per_s']:,.0f} samples/s, "
      f"p50/p95/p99 = {m['latency_ms']['p50']:.1f}/"
      f"{m['latency_ms']['p95']:.1f}/{m['latency_ms']['p99']:.1f} ms")
print(f"[stream] occupancy {m['mean_occupancy']:.1f}/{m['batch']}, "
      f"deadline flushes {m['deadline_flushes']}, "
      f"evictions {m['state']['evictions']}, "
      f"GOP/s/W at measured point {m['gops_per_watt']:.2e}")
