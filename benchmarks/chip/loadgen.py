"""The one load generator: open-loop periodic sensor streams.

A traffic mix is a data file under ``traffic/`` (see ``PERF.md``):

``kind``           ``periodic``, the one kind this generator makes;
``rate_per_s``     offered windows per second over all streams;
``streams``        sensors or devices, each sending one window per period,
                   so the period of one stream is ``streams / rate_per_s``;
``period_s``       instead of ``streams``: the period of one stream, so
                   there are ``round(period_s * rate_per_s)`` streams;
``warmup_s``       the same traffic runs this long before the measured
                   window opens;
``pool_windows``   input windows made from the seed; window k of stream s
                   is pool row ``(s * 7919 + k * 104729 + offset) % pool``;
``grace_s``        how long after the window closes the harness waits for
                   the answers still due;
``serving``        serving settings that follow from the stream count
                   (``max_streams``);
``rehearsal``      smaller values of the keys above for a CPU rehearsal.

Every stream is periodic; stream ``order[r]`` has phase ``r / streams`` of
a period, with ``order`` a permutation drawn from the seed.  So window
``i`` of the whole schedule is due ``i / rate_per_s`` seconds after the
start, for every seed: seeds change which stream sends when and what it
sends, never how many windows arrive or when.  The input signal of a
window is the configuration's (``input`` in the configuration file): per
channel a sine of random frequency, phase and amplitude plus Gaussian
noise, clipped to ``range``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

STREAM_MUL = 7919
SEQ_MUL = 104729


@dataclasses.dataclass
class Traffic:
    """A traffic mix made concrete for one seed and one configuration."""

    streams: int
    rate: float
    warmup_s: float
    grace_s: float
    pool: np.ndarray            # (pool_windows, T, M) float32
    order: np.ndarray           # schedule position -> stream id
    rank: np.ndarray            # stream id -> schedule position
    offset: int
    serving: Dict

    @property
    def period_s(self) -> float:
        return self.streams / self.rate

    def due(self, i):
        """Due time of schedule index ``i``, seconds after the start."""
        return np.asarray(i) / self.rate

    def rows(self, streams, seq):
        """Pool rows of window(s) ``seq`` of ``streams`` (broadcasting)."""
        s = np.asarray(streams, np.int64)
        k = np.asarray(seq, np.int64)
        return (s * STREAM_MUL + k * SEQ_MUL + self.offset) % len(self.pool)

    def row(self, stream: int, seq: int) -> int:
        """Pool row of one window, in Python integers (the hot loop)."""
        return (stream * STREAM_MUL + seq * SEQ_MUL + self.offset) \
            % len(self.pool)


def make_pool(rng: np.random.Generator, n: int, t_len: int, m: int,
              signal: Dict) -> np.ndarray:
    """``n`` input windows (n, T, M) float32 drawn with ``rng``."""
    lo, hi = signal["range"]
    c_lo, c_hi = signal["cycles_per_window"]
    t = np.arange(t_len, dtype=np.float64)[None, :, None]
    freq = rng.uniform(c_lo, c_hi, (n, 1, m)) / t_len
    phase = rng.uniform(0.0, 2 * np.pi, (n, 1, m))
    amp = rng.uniform(0.5, 1.0, (n, 1, m)) * signal["amplitude"]
    x = amp * np.sin(2 * np.pi * freq * t + phase)
    x += rng.normal(0.0, signal["noise"], (n, t_len, m))
    return np.clip(x, lo, hi).astype(np.float32)


def make_traffic(mix: Dict, cfg: Dict, seed: int,
                 rehearse: bool = False) -> Traffic:
    """The traffic of ``mix`` (a traffic file's dict) for configuration
    ``cfg`` and ``seed``; ``rehearse`` replaces keys by the mix's
    ``rehearsal`` values."""
    mix = {**mix, **(mix.get("rehearsal", {}) if rehearse else {})}
    if mix["kind"] != "periodic":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}; this "
                         f"generator makes periodic streams")
    rng = np.random.default_rng(np.random.SeedSequence(seed % 2 ** 64))
    m = cfg["model"]
    pool = make_pool(rng, int(mix["pool_windows"]), m["seq_len"],
                     m["input_size"], cfg["input"])
    streams = (int(mix["streams"]) if "streams" in mix
               else int(round(float(mix["period_s"]) * mix["rate_per_s"])))
    order = rng.permutation(streams)
    rank = np.empty(streams, np.int64)
    rank[order] = np.arange(streams)
    return Traffic(streams=streams, rate=float(mix["rate_per_s"]),
                   warmup_s=float(mix["warmup_s"]),
                   grace_s=float(mix["grace_s"]), pool=pool, order=order,
                   rank=rank, offset=int(rng.integers(0, len(pool))),
                   serving=dict(mix.get("serving", {})))
