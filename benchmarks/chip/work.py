"""Bytes a wave needs, from shapes alone, and the peak table.

They count what the algorithm needs, whatever implements it, so a change
that drops a copy, fuses the head or replaces the kernel is read against
the same work.  The architecture's own counts come from its reference
(``references/__init__.py``): the operations of a window, the bytes of the
weights and the codes of one stream's carry.  A wave moves the float32
input windows as it receives them, the weights and biases once, the
carries of its rows read and written at the code storage width (none on a
stateless server), and the float32 outputs.
"""

from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def storage_bytes(bits: int) -> int:
    """Bytes of the narrowest native integer that holds a ``bits`` code."""
    return 1 if bits <= 8 else 2 if bits <= 16 else 4


def bytes_per_wave(ref, cfg: Dict, batch: int) -> int:
    """Bytes a wave of ``batch`` rows of ``cfg`` moves; ``ref`` is the
    configuration's reference module."""
    m = cfg["model"]
    w = storage_bytes(cfg["number_format"]["total_bits"])
    x = batch * m["seq_len"] * m["input_size"] * 4
    y = batch * m["out_features"] * 4
    carry = (2 * batch * ref.carry_codes(cfg) * w
             if cfg["serving"]["stateful"] else 0)
    return x + ref.weight_bytes(cfg) + carry + y


def peaks(device_kind: str) -> Dict:
    """The peak table's row for ``device_kind``; a device not in the table
    is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
