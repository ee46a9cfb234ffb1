"""``rglru`` cell spec — Griffin's RG-LRU re-derived for the fixed-point
datapath.

The float seed (``models/rglru.py``, ``kernels/rglru_scan.py``) computes
``a = exp(-c * r * softplus(lam))`` and a ``sqrt(1 - a^2)`` input scale —
both hostile to an integer (a,b) pipeline (exp, sqrt, and a free-running
log-space parameter).  This module is the hardware-friendly redefinition
promoted through the :class:`repro.cells.CellSpec` contract:

  * gates are INPUT-ONLY (as in Griffin): ``r = gate(x W_a + b_a)``,
    ``i = gate(x W_i + b_i)`` — one MAC each, no recurrent matmul;
  * the decay is the bilinear ``a = 1 - r * lambda`` with
    ``lambda = gate(lam)`` baked to an (a,b) code at quantisation time —
    ``r -> 0`` gives ``a -> 1`` (remember), ``r -> 1`` gives
    ``a -> 1 - lambda`` (update), monotone like the exp form but a single
    multiply;
  * the input scale is the convex complement ``(1 - a)`` instead of
    ``sqrt(1 - a^2)``: ``h' = a*h + (1-a)*(i * (x W_x + b_x))`` — a
    stable convex mix whose coefficients sum to the exact 1.0 code.

Every product sits at the wide PRODUCT precision and rounds once (the S5
contract of ``core.qlstm``); MACs switch by ALU mode through
``qlstm.int_mac``.  ``kernels/ref.qrglru_seq_ref`` is the independently
written oracle the general datapath must match bit-for-bit.  No fused
Pallas kernel — ``supports_fused`` is ``None`` so ``plan()`` resolves the
xla engine, and serving drives the device carry table through the
XLA-level slot adapter.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.cells import CellSpec, paper_datapath_reason, register
from repro.core import fixed_point as fxp
from repro.core import qlstm
from repro.core.qlstm import Params, QLSTMConfig, check_int_state
from repro.kernels import ref as _ref

Array = jax.Array


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_params(cfg: QLSTMConfig, key: Array, dtype=jnp.float32) -> Params:
    """Float master params: per layer three input projections ``w_x/w_a/
    w_i (M, H)`` with biases, plus the raw decay parameter ``lam (H,)``
    (gated at quantisation time), plus the shared dense head."""
    layers = []
    for li in range(cfg.num_layers):
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        m, h = cfg.layer_in_dim(li), cfg.hidden_size
        s = 1.0 / jnp.sqrt(max(m, 1))
        layers.append({
            "w_x": jax.random.uniform(k1, (m, h), dtype, -s, s),
            "w_a": jax.random.uniform(k2, (m, h), dtype, -s, s),
            "w_i": jax.random.uniform(k3, (m, h), dtype, -s, s),
            "b_x": jnp.zeros((h,), dtype),
            "b_a": jnp.zeros((h,), dtype),
            "b_i": jnp.zeros((h,), dtype),
            # lam in ~[0.4, 2.6]: gate(lam) spans slow-to-fast decays.
            "lam": jax.random.uniform(k4, (h,), dtype, 0.4, 2.6),
        })
    key, kd = jax.random.split(key)
    s = 1.0 / jnp.sqrt(cfg.hidden_size)
    dense = {
        "w": jax.random.uniform(kd, (cfg.hidden_size, cfg.out_features),
                                dtype, -s, s),
        "b": jnp.zeros((cfg.out_features,), dtype),
    }
    return {"layers": layers, "dense": dense}


def quantize_params(params: Params, cfg: QLSTMConfig) -> Params:
    """Float masters -> integer codes.  Weights in (a,b), biases at the
    wide PRODUCT format, and the decay is BAKED: ``lam_q =
    quantize(gate(lam))`` — the gate nonlinearity on the static parameter
    runs once here, not per step on the accelerator."""
    c = cfg.fxp
    wide = fxp.product_config(c, c)
    gate = qlstm._float_gate_act(cfg.acts, c)

    def q_layer(p):
        return {
            "w_x": fxp.quantize(p["w_x"], c),
            "w_a": fxp.quantize(p["w_a"], c),
            "w_i": fxp.quantize(p["w_i"], c),
            "b_x": fxp.quantize(p["b_x"], wide),
            "b_a": fxp.quantize(p["b_a"], wide),
            "b_i": fxp.quantize(p["b_i"], wide),
            "lam_q": fxp.quantize(gate(p["lam"]), c),
        }

    return {
        "layers": [q_layer(p) for p in params["layers"]],
        "dense": {"w": fxp.quantize(params["dense"]["w"], c),
                  "b": fxp.quantize(params["dense"]["b"], wide)},
    }


# ---------------------------------------------------------------------------
# Float / QAT forward
# ---------------------------------------------------------------------------

def _step_float(p, x_t, h, cfg: QLSTMConfig, fq: bool):
    fp = cfg.fxp
    q = (lambda t: fxp.fake_quant(t, fp)) if fq else (lambda t: t)
    gate = qlstm._float_gate_act(cfg.acts, fp, fq=fq)
    lam = gate(p["lam"])
    if fq:
        lam = q(lam)
    xp = q(x_t @ q(p["w_x"]) + p["b_x"])
    r = gate(x_t @ q(p["w_a"]) + p["b_a"])
    i = gate(x_t @ q(p["w_i"]) + p["b_i"])
    if fq:
        r, i = q(r), q(i)
    a = 1.0 - q(r * lam)
    gx = q(i * xp)
    return q(a * h + (1.0 - a) * gx)


def _forward(params: Params, x: Array, cfg: QLSTMConfig, fq: bool) -> Array:
    b = x.shape[0]
    h_t = x
    h_last = None
    for p in params["layers"]:
        h0 = jnp.zeros((b, cfg.hidden_size), x.dtype)

        def step(h, x_t, p=p):
            h = _step_float(p, x_t, h, cfg, fq)
            return h, h

        h_last, hs = jax.lax.scan(step, h0, jnp.swapaxes(h_t, 0, 1))
        h_t = jnp.swapaxes(hs, 0, 1)
    q = (lambda t: fxp.fake_quant(t, cfg.fxp)) if fq else (lambda t: t)
    return q(h_last @ q(params["dense"]["w"]) + params["dense"]["b"])


def forward_float(params: Params, x: Array, cfg: QLSTMConfig) -> Array:
    """Float RG-LRU stack + dense head: (B, T, M) -> (B, P)."""
    return _forward(params, x, cfg, fq=False)


def forward_qat(params: Params, x: Array, cfg: QLSTMConfig) -> Array:
    """QAT graph: the float forward with STE fake-quant at every hardware
    rounding point (including the baked ``gate(lam)`` code)."""
    return _forward(params, x, cfg, fq=True)


# ---------------------------------------------------------------------------
# Integer forward — the general (xla-engine) datapath
# ---------------------------------------------------------------------------

def _step_int(p, x_t, h, cfg: QLSTMConfig):
    fp = cfg.fxp
    prod = fxp.product_config(fp, fp)
    one = 1 << fp.frac_bits            # the exact (a,b) code of 1.0
    xp = qlstm.int_mac(x_t, p["w_x"], p["b_x"], cfg)
    r = qlstm.int_gate_act(qlstm.int_mac(x_t, p["w_a"], p["b_a"], cfg), cfg)
    i = qlstm.int_gate_act(qlstm.int_mac(x_t, p["w_i"], p["b_i"], cfg), cfg)
    a = one - qlstm.elem_mul_round(r, p["lam_q"].astype(jnp.int32), cfg)
    gx = qlstm.elem_mul_round(i, xp, cfg)
    # Convex mix a*h + (1-a)*gx: both products wide, add, round ONCE (S5).
    wide = a.astype(jnp.int32) * h.astype(jnp.int32) \
        + (one - a.astype(jnp.int32)) * gx.astype(jnp.int32)
    return fxp.requantize(wide, prod, fp)


def run_int_stateful(qparams: Params, x_int: Array, cfg: QLSTMConfig,
                     state) -> Tuple[Array, tuple]:
    """Bit-exact integer RG-LRU stack with an explicit cross-window carry
    (per layer ``(h,)``) — windowed feeding is bit-identical to one call
    on the concatenated sequence."""
    check_int_state(state, qparams)
    h_t = x_int.astype(jnp.int32)
    new_state = []
    h_last = None
    for p, (h0,) in zip(qparams["layers"], state):

        def step(h, x_t, p=p):
            h = _step_int(p, x_t, h, cfg)
            return h, h

        h_last, hs = jax.lax.scan(step, h0.astype(jnp.int32),
                                  jnp.swapaxes(h_t, 0, 1))
        new_state.append((h_last,))
        h_t = jnp.swapaxes(hs, 0, 1)
    y = qlstm.int_mac(h_last, qparams["dense"]["w"], qparams["dense"]["b"],
                      cfg)
    return y, tuple(new_state)


def ref_layer(x_tm: Array, p, model: QLSTMConfig, carry):
    """One oracle RG-LRU layer, time-major — ``kernels/ref.qrglru_seq_ref``
    resumed from ``carry = (h0,)``."""
    acts = model.acts
    (h0,) = carry
    hs, h_last = _ref.qrglru_seq_ref(
        x_tm, p["w_x"], p["w_a"], p["w_i"],
        p["b_x"], p["b_a"], p["b_i"], p["lam_q"], model.fxp,
        hs_slope_shift=acts.hs_slope_shift, hs_bound=acts.hs_bound, h0=h0)
    return hs, (h_last,)


def supports_int(model: QLSTMConfig, accel) -> Optional[str]:
    """None when the general int datapath covers the configuration.  The
    RG-LRU uses no cell activation — only the gate nonlinearity must have
    an integer form."""
    if model.acts.gate not in ("hard_sigmoid_star", "lut_sigmoid", "sigmoid"):
        return f"gate activation {model.acts.gate!r} has no integer datapath"
    return None


def ops_per_inference(cfg: QLSTMConfig) -> int:
    """Operations per inference, 2 per multiply-accumulate, for the RG-LRU
    stack + dense head — the GOP/s accounting convention of
    ``core.qlstm``."""
    total = 0
    for li in range(cfg.num_layers):
        m, h = cfg.layer_in_dim(li), cfg.hidden_size
        total += cfg.seq_len * 2 * 3 * h * m     # three input projections
    return total + 2 * cfg.hidden_size * cfg.out_features


def weight_bytes(model: QLSTMConfig, acc) -> int:
    """Bytes of quantised RG-LRU weights+biases (including the baked
    ``lam_q`` codes) the accelerator must hold."""
    itemsize = (acc.fxp.total_bits + 7) // 8
    wide_itemsize = 2 * itemsize
    total = 0
    for li in range(model.num_layers):
        m, h = model.layer_in_dim(li), model.hidden_size
        total += 3 * m * h * itemsize + 3 * h * wide_itemsize
        total += h * itemsize           # lam_q
    total += model.hidden_size * model.out_features * itemsize
    total += model.out_features * wide_itemsize
    return total


SPEC = register(CellSpec(
    name="rglru",
    state_arity=1,
    state_names=("h",),
    init_params=init_params,
    quantize_params=quantize_params,
    forward_float=forward_float,
    forward_qat=forward_qat,
    run_int_stateful=run_int_stateful,
    ref_layer=ref_layer,
    supports_int=supports_int,
    supports_oracle=paper_datapath_reason,
    supports_fused=None,    # no fused Pallas kernel (yet): auto -> xla
    ops_per_inference=ops_per_inference,
    weight_bytes=weight_bytes,
))
