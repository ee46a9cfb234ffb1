"""Plain reference of the quantised LSTM stack with a dense head.

It follows the configuration file's own description (``number_format``,
``activations``, ``model``) and imports nothing of the system under test.
Everything is integer arithmetic on int32 codes, so the reference and a
correct datapath agree exactly:

* a float becomes a code by ``floor(x * 2**a + 0.5)``, saturated to the
  ``b``-bit range ((a, b) = ``number_format``);
* weights are codes in (a, b); biases are codes at the product format
  (2a fractional bits, 2b bits) and add into the accumulator;
* every matrix product accumulates at full width and is rounded once,
  half up, by an arithmetic right shift of ``a`` bits, then saturated;
* the gates i, f, o go through HardSigmoid* (a truncating right shift by
  ``hs_slope_shift`` plus one half, clamped to [0, 1], 0 below
  ``-hs_bound`` and 1 from ``hs_bound`` up); g and tanh(c) go through
  HardTanh (a clip to ``[ht_min, ht_max]``);
* ``c' = round(f*c + i*g)`` and ``h' = round(o * HardTanh(c'))``;
* the head is ``round(h_last @ w + b)`` over the last layer's final h.

The weights come from :func:`make_params`, one jitted call from the
benchmark's seed.  The same float weights are handed to the system under
test, which quantises them itself; the reference quantises them here.

:func:`control_codes` is the control of the comparison: the same weights
rounded to 4-bit codes (two fewer fractional bits, eight levels each way),
the int4 step down from the configured int8 codes.

The module keeps the contract of ``references/__init__.py``: a stream's
carry is ``(h, c)`` of every layer, ``2 * num_layers * hidden_size`` codes.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from work import storage_bytes


def _fmt(cfg) -> Tuple[int, int]:
    nf = cfg["number_format"]
    return int(nf["frac_bits"]), int(nf["total_bits"])


def _range(bits: int) -> Tuple[int, int]:
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1


def quantize(x, frac: int, bits: int):
    """Float -> int32 code: round half up, saturate."""
    lo, hi = _range(bits)
    v = jnp.floor(jnp.asarray(x, jnp.float32) * float(1 << frac) + 0.5)
    return jnp.clip(v, lo, hi).astype(jnp.int32)


def make_params(cfg, key):
    """Float32 master weights on the device, in one jitted call.

    Per layer ``w_x`` (in, 4H) and ``w_h`` (H, 4H) uniform in
    +-``w_range``, ``b`` (4H,) zero but for the forget gate's
    ``forget_bias``; the head ``w`` (H, P) uniform in +-``w_range``, ``b``
    zero (both from the configuration's ``weights``).  Gate order i, f, g,
    o.  Returned as ``{"layers": [{"w_x", "w_h", "b"}, ...], "dense":
    {"w", "b"}}``."""
    m = cfg["model"]
    n_in, hid, n_layers, n_out = (m["input_size"], m["hidden_size"],
                                  m["num_layers"], m["out_features"])
    forget_bias = float(cfg["weights"]["forget_bias"])
    s = float(cfg["weights"]["w_range"])

    @jax.jit
    def init(key):
        keys = jax.random.split(key, 2 * n_layers + 1)
        layers = []
        for li in range(n_layers):
            d_in = n_in if li == 0 else hid
            b = jnp.zeros((4 * hid,), jnp.float32).at[hid:2 * hid].set(
                forget_bias)
            layers.append({
                "w_x": jax.random.uniform(keys[2 * li], (d_in, 4 * hid),
                                          jnp.float32, -s, s),
                "w_h": jax.random.uniform(keys[2 * li + 1], (hid, 4 * hid),
                                          jnp.float32, -s, s),
                "b": b})
        dense = {"w": jax.random.uniform(keys[-1], (hid, n_out), jnp.float32,
                                         -s, s),
                 "b": jnp.zeros((n_out,), jnp.float32)}
        return {"layers": layers, "dense": dense}

    return init(key)


def weight_codes(cfg, params) -> Dict:
    """The configured codes of ``params``: weights in (a, b), biases at
    the product format (2a, 2b)."""
    frac, bits = _fmt(cfg)
    wq = lambda w: quantize(w, frac, bits)
    bq = lambda b: quantize(b, 2 * frac, 2 * bits)
    return {"layers": [{"w_x": wq(p["w_x"]), "w_h": wq(p["w_h"]),
                        "b": bq(p["b"])} for p in params["layers"]],
            "dense": {"w": wq(params["dense"]["w"]),
                      "b": bq(params["dense"]["b"])}}


def control_codes(cfg, params) -> Dict:
    """The control: every weight rounded to a 4-bit code with two fewer
    fractional bits, then placed back on the (a, b) grid so the same
    datapath runs it.  Biases keep their codes."""
    frac, _ = _fmt(cfg)
    drop = 2

    def w4(w):
        return quantize(w, frac - drop, 4) * (1 << drop)

    codes = weight_codes(cfg, params)
    return {"layers": [{"w_x": w4(p["w_x"]), "w_h": w4(p["w_h"]),
                        "b": c["b"]}
                       for p, c in zip(params["layers"], codes["layers"])],
            "dense": {"w": w4(params["dense"]["w"]),
                      "b": codes["dense"]["b"]}}


def _ops(cfg):
    frac, bits = _fmt(cfg)
    lo, hi = _range(bits)
    half = 1 << (frac - 1)
    a = cfg["activations"]
    one = 1 << frac
    bound = int(round(float(a["hs_bound"]) * one))
    shift = int(a["hs_slope_shift"])
    qf = lambda v: int(np.clip(np.floor(v * one + 0.5), lo, hi))
    ht_lo, ht_hi = qf(float(a["ht_min"])), qf(float(a["ht_max"]))

    def rnd(v):
        return jnp.clip((v + half) >> frac, lo, hi)

    def hsig(v):
        lin = jnp.clip((v >> shift) + half, 0, one)
        return jnp.where(v < -bound, 0, jnp.where(v >= bound, one, lin))

    def htanh(v):
        return jnp.clip(v, ht_lo, ht_hi)

    return rnd, hsig, htanh


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def zero_carry(cfg, batch: int):
    m = cfg["model"]
    z = jnp.zeros((batch, m["hidden_size"]), jnp.int32)
    return tuple((z, z) for _ in range(m["num_layers"]))


def _make_window_fn(cfg):
    frac, bits = _fmt(cfg)
    hid = cfg["model"]["hidden_size"]
    rnd, hsig, htanh = _ops(cfg)

    def window(codes, x, carry):
        """x: (B, T, M) float32; carry per layer (h, c) -> (y codes (B, P),
        new carry)."""
        seq = jnp.swapaxes(quantize(x, frac, bits), 0, 1)     # (T, B, M)
        new_carry = []
        for p, (h0, c0) in zip(codes["layers"], carry):
            def step(hc, x_t, p=p):
                h, c = hc
                pre = rnd(_dot(x_t, p["w_x"]) + _dot(h, p["w_h"]) + p["b"])
                i = hsig(pre[:, :hid])
                f = hsig(pre[:, hid:2 * hid])
                g = htanh(pre[:, 2 * hid:3 * hid])
                o = hsig(pre[:, 3 * hid:])
                c = rnd(f * c + i * g)
                h = rnd(o * htanh(c))
                return (h, c), h
            (h, c), seq = jax.lax.scan(step, (h0, c0), seq)
            new_carry.append((h, c))
        y = rnd(_dot(new_carry[-1][0], codes["dense"]["w"])
                + codes["dense"]["b"])
        return y, tuple(new_carry)

    return jax.jit(window)


_WINDOW_FNS: Dict[str, object] = {}


def window_fn(cfg):
    """The jitted one-window reference for ``cfg``: ``(codes, x, carry) ->
    (y_codes, new_carry)``, ``x`` float32 (B, T, M)."""
    if cfg["name"] not in _WINDOW_FNS:
        _WINDOW_FNS[cfg["name"]] = _make_window_fn(cfg)
    return _WINDOW_FNS[cfg["name"]]


def output_codes(cfg, y: np.ndarray) -> np.ndarray:
    """A served float output -> its integer code (exact for a correct
    datapath, which returns code * 2**-a)."""
    frac, _ = _fmt(cfg)
    return np.rint(np.asarray(y, np.float64) * (1 << frac)).astype(np.int64)


def ops_per_window(cfg) -> int:
    """The gate multiply-accumulates, 2 per MAC, of every timestep and
    layer, ``2 * (in + H) * 4H`` per step, plus the dense head's
    ``2 * H * P``."""
    m = cfg["model"]
    h, n_in = m["hidden_size"], m["input_size"]
    per_step = sum(2 * ((n_in if li == 0 else h) + h) * 4 * h
                   for li in range(m["num_layers"]))
    return per_step * m["seq_len"] + 2 * h * m["out_features"]


def weight_bytes(cfg) -> int:
    """Gate weights (in + H, 4H) and the head's (H, P) at the code width,
    biases (4H,) and (P,) at the product format's."""
    m = cfg["model"]
    h, n_in, p = m["hidden_size"], m["input_size"], m["out_features"]
    w = storage_bytes(cfg["number_format"]["total_bits"])
    b = storage_bytes(2 * cfg["number_format"]["total_bits"])
    total = 0
    for li in range(m["num_layers"]):
        total += ((n_in if li == 0 else h) + h) * 4 * h * w + 4 * h * b
    return total + h * p * w + p * b


def carry_codes(cfg) -> int:
    """h and c of every layer, H codes each."""
    m = cfg["model"]
    return m["num_layers"] * 2 * m["hidden_size"]


def carry_vector(cfg, state) -> np.ndarray:
    """``[(h, c), ...]`` per layer -> ``h0, c0, h1, c1, ...`` as one row."""
    return np.concatenate([np.asarray(a, np.int64).ravel()
                           for layer in state for a in layer])


def run_chains(cfg, codes, x_of, ids, n_windows, block: int, keep=()):
    """Reference outputs of stateful streams: stream ``ids[r]`` runs its
    windows 0..n_windows[r]-1 in order from the zero carry.
    ``x_of(streams, k)`` gives window k of each listed stream as float32
    (len, T, M).  Returns the codes (len(ids), max_windows, P), windows
    past a stream's count left at 0, and the carries after the last
    window of the streams at positions ``keep``, (len(keep),
    carry_codes) in :func:`carry_vector`'s order."""
    fn = window_fn(cfg)
    m = cfg["model"]
    ids, n_windows = np.asarray(ids), np.asarray(n_windows)
    keep = np.asarray(keep, np.int64)
    out = np.zeros((len(ids), int(n_windows.max(initial=0)),
                    m["out_features"]), np.int64)
    final = np.zeros((len(keep), carry_codes(cfg)), np.int64)
    for b0 in range(0, len(ids), block):
        rows = np.arange(b0, min(b0 + block, len(ids)))
        pad = block - len(rows)
        kept = np.flatnonzero((keep >= b0) & (keep < b0 + len(rows)))
        carry = zero_carry(cfg, block)
        for k in range(int(n_windows[rows].max(initial=0))):
            x = x_of(ids[rows], k)
            if pad:
                x = np.concatenate([x, np.zeros((pad,) + x.shape[1:],
                                                x.dtype)])
            y, carry = fn(codes, jnp.asarray(x), carry)
            out[rows, k] = np.asarray(y)[:len(rows)]
            last = kept[n_windows[keep[kept]] == k + 1]
            if len(last):
                local = keep[last] - b0
                final[last] = np.concatenate(
                    [np.asarray(a)[local] for layer in carry for a in layer],
                    1)
    return out, final
