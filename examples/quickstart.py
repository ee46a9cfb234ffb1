"""Quickstart: the paper's pipeline end to end in ~a minute on CPU —
through the unified session API (docs/API.md).

1. ``repro.build`` the paper's accelerator (hidden 20, (4,8) fixed point,
   HardSigmoid* 'step' + HardTanh, pipelined ALU on the MXU).
2. ``train_qat`` briefly on synthetic PeMS-like traffic data.
3. ``quantize`` and run the deployment path — the plan selects the fused
   Pallas kernel (interpret mode on CPU) — and check it matches QAT and is
   bit-identical across every backend engine.
4. ``report()`` the Table-2 accelerator plan and Table-4-style energy.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax.numpy as jnp

import repro
from repro.compile_cache import use_compile_cache
from repro.core.accelerator import PAPER_DEFAULT
from repro.core.qlstm import QLSTMConfig
from repro.data.timeseries import pems_like_dataset

use_compile_cache()

cfg = QLSTMConfig()  # the paper's model
data = pems_like_dataset(seq_len=cfg.seq_len)
xte, yte = data["test"]

acc = repro.build(cfg, PAPER_DEFAULT, seed=0)
acc.train_qat(data, steps=150, batch=64, lr=3e-3).quantize()

x = jnp.asarray(xte[:512])
y = jnp.asarray(yte[:512])
mse_qat = float(jnp.mean((acc.infer(x, path="qat") - y) ** 2))
pred_hw = acc.infer(x, path="int")            # plan-selected Pallas kernel
mse_hw = float(jnp.mean((pred_hw - y) ** 2))
print(f"\ntest MSE: QAT={mse_qat:.5f}  int8-accelerator={mse_hw:.5f} "
      f"(paper reports 0.040 on real PeMS-4W)")

# Every execution engine produces the SAME integer codes (the paper's
# point: one parameterised design, many implementations).
for backend in ("ref", "pallas", "xla"):
    same = bool(jnp.all(acc.infer(x, path="int", backend=backend) == pred_hw))
    print(f"  backend {backend:6s}: bit-identical = {same}")

rep = acc.report()
print("\nAccelerator plan (Table 2 -> TPU):", rep["plan"])
print("Energy report (Table-4 analogue):", rep["energy"])
