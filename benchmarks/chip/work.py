"""Operations and bytes a window and a wave need, from shapes alone.

They count what the algorithm needs, whatever implements it, so a change
that drops a copy, fuses the head or replaces the kernel is read against
the same work:

* operations: the gate multiply-accumulates, 2 per MAC, of every
  timestep and layer, ``2 * (in + H) * 4H`` per step, plus the dense
  head's ``2 * H * P``;
* bytes: the float32 input window as the wave receives it, the weights
  and biases once per wave at their code storage width, the carries
  (h and c of every layer, H codes each) read and written at that width,
  and the float32 outputs.
"""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def storage_bytes(bits: int) -> int:
    """Bytes of the narrowest native integer that holds a ``bits`` code."""
    return 1 if bits <= 8 else 2 if bits <= 16 else 4


def ops_per_window(cfg: Dict) -> int:
    m = cfg["model"]
    h, n_in = m["hidden_size"], m["input_size"]
    per_step = sum(2 * ((n_in if li == 0 else h) + h) * 4 * h
                   for li in range(m["num_layers"]))
    return per_step * m["seq_len"] + 2 * h * m["out_features"]


def weight_bytes(cfg: Dict) -> int:
    m = cfg["model"]
    h, n_in, p = m["hidden_size"], m["input_size"], m["out_features"]
    w = storage_bytes(cfg["number_format"]["total_bits"])
    b = storage_bytes(2 * cfg["number_format"]["total_bits"])
    total = 0
    for li in range(m["num_layers"]):
        total += ((n_in if li == 0 else h) + h) * 4 * h * w + 4 * h * b
    return total + h * p * w + p * b


def bytes_per_wave(cfg: Dict, batch: int) -> int:
    m = cfg["model"]
    w = storage_bytes(cfg["number_format"]["total_bits"])
    x = batch * m["seq_len"] * m["input_size"] * 4
    y = batch * m["out_features"] * 4
    carry = 2 * batch * m["num_layers"] * 2 * m["hidden_size"] * w
    return x + weight_bytes(cfg) + carry + y


def peaks(device_kind: str) -> Dict:
    """The peak table's row for ``device_kind``; a device not in the table
    is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
