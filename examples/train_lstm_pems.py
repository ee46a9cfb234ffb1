"""The paper's §6.1 experiment, end to end (e2e training driver).

Drives the session API (``repro.build`` -> ``train_qat`` -> ``quantize``
-> ``infer``; docs/API.md) via ``launch/train.py``: QAT on PeMS-like
traffic data, then MSE for float / QAT / the bit-exact int8 accelerator
datapath.  Checkpoints land in /tmp/repro_lstm_ckpt — rerun to resume;
Ctrl-C checkpoints-and-exits (the fault-tolerance contract).

Run:  PYTHONPATH=src python examples/train_lstm_pems.py [--steps 400]
"""
import sys
sys.argv = [sys.argv[0], "--arch", "lstm-pems",
            "--ckpt-dir", "/tmp/repro_lstm_ckpt"] + sys.argv[1:]
from repro.compile_cache import use_compile_cache
from repro.launch.train import main
use_compile_cache()
main()
