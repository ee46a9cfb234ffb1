"""``gru`` cell spec — quantised GRU through the paper's datapath.

Same fixed-point contract as the LSTM (``core.qlstm``): weights in (a,b),
biases at the wide PRODUCT precision (2a frac bits), MACs by ALU mode
(pipelined = accumulate wide + ONE late S5 rounding; per_step = Algorithm
1's per-product rounding with saturating adds), gates through the integer
HardSigmoid*/LUT activations, elementwise state updates at wide precision
with a single rounding.

Gate order is [r, z, n] over a fused ``(in, 3H)`` weight layout (the
LSTM's ``[i, f, g, o]`` convention, one gate shorter):

    r = gate(x W_xr + h W_hr + b_r)            (reset)
    z = gate(x W_xz + h W_hz + b_z)            (update)
    n = cellact( (x W_xn + b_n)*1 + r * (h W_hn) )   (candidate, v3 form:
                                                r gates the RECURRENT half
                                                before the activation)
    h' = (1 - z) * n + z * h

The candidate combine and the state mix are both S5-style: every product
at the wide precision, add, round once.  The recurrent half ``h W_hn`` is
rounded at its own accumulator exit (a second MAC port in hardware), then
the ``r``-gating product restores the wide format — so ``x W_xn + b_n``
is lifted to wide by the exact ``1.0`` code and the sum rounds once.

``kernels/ref.qgru_seq_ref`` is the independently written oracle this
module's general datapath must match bit-for-bit
(``tests/test_cells.py``).  No fused Pallas kernel yet — the spec's
``supports_fused`` is ``None``, so ``plan()`` resolves the xla engine and
serving drives the device carry table through the XLA-level slot
adapter.
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
    """Float master params: per layer ``w_x (M, 3H)``, ``w_h (H, 3H)``,
    ``b (3H,)`` in gate order [r, z, n], plus the shared dense head."""
    layers = []
    for li in range(cfg.num_layers):
        key, k1, k2 = jax.random.split(key, 3)
        m, h = cfg.layer_in_dim(li), cfg.hidden_size
        s = 1.0 / jnp.sqrt(h)
        layers.append({
            "w_x": jax.random.uniform(k1, (m, 3 * h), dtype, -s, s),
            "w_h": jax.random.uniform(k2, (h, 3 * h), dtype, -s, s),
            "b": jnp.zeros((3 * h,), dtype),
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
    """Float masters -> integer codes: weights in (a,b), biases at the
    wide PRODUCT format — the LSTM quantisation rule, 3 gates wide."""
    c = cfg.fxp
    wide = fxp.product_config(c, c)
    q_layer = lambda p: {"w_x": fxp.quantize(p["w_x"], c),
                         "w_h": fxp.quantize(p["w_h"], c),
                         "b": fxp.quantize(p["b"], wide)}
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
    cellact = qlstm._float_cell_act(cfg.acts)
    hdim = cfg.hidden_size
    w_x, w_h = q(p["w_x"]), q(p["w_h"])
    rz = q(x_t @ w_x[:, :2 * hdim] + h @ w_h[:, :2 * hdim]
           + p["b"][:2 * hdim])
    r, z = gate(rz[:, :hdim]), gate(rz[:, hdim:])
    if fq:
        r, z = q(r), q(z)
    nh = q(h @ w_h[:, 2 * hdim:])
    n = cellact(q(x_t @ w_x[:, 2 * hdim:] + p["b"][2 * hdim:] + r * nh))
    if fq:
        n = q(n)
    return q((1.0 - z) * n + z * h)


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
    """Float GRU stack + dense head: (B, T, M) -> (B, P)."""
    return _forward(params, x, cfg, fq=False)


def forward_qat(params: Params, x: Array, cfg: QLSTMConfig) -> Array:
    """QAT graph: the float forward with STE fake-quant at every hardware
    rounding point."""
    return _forward(params, x, cfg, fq=True)


# ---------------------------------------------------------------------------
# Integer forward — the general (xla-engine) datapath
# ---------------------------------------------------------------------------

def _step_int(p, x_t, h, cfg: QLSTMConfig):
    fp = cfg.fxp
    prod = fxp.product_config(fp, fp)
    hdim = cfg.hidden_size
    one = 1 << fp.frac_bits            # the exact (a,b) code of 1.0
    w_x, w_h = p["w_x"], p["w_h"]
    rz = qlstm.int_mac(jnp.concatenate([x_t, h], axis=-1),
                       jnp.concatenate([w_x[:, :2 * hdim],
                                        w_h[:, :2 * hdim]], axis=-2),
                       p["b"][:2 * hdim], cfg)
    r = qlstm.int_gate_act(rz[:, :hdim], cfg)
    z = qlstm.int_gate_act(rz[:, hdim:], cfg)
    # Candidate: both halves MAC'd by ALU mode to (a,b); the combine is
    # S5 — lift nx by the 1.0 code, gate nh by r (both wide), round once.
    nh = qlstm.int_mac(h, w_h[:, 2 * hdim:],
                       jnp.zeros((hdim,), jnp.int32), cfg)
    nx = qlstm.int_mac(x_t, w_x[:, 2 * hdim:], p["b"][2 * hdim:], cfg)
    n_pre = fxp.requantize(nx.astype(jnp.int32) * one
                           + r.astype(jnp.int32) * nh.astype(jnp.int32),
                           prod, fp)
    n = qlstm.int_cell_act(n_pre, cfg)
    # h' = (1-z)*n + z*h : both products wide, add, round ONCE (S5).
    wide = (one - z.astype(jnp.int32)) * n.astype(jnp.int32) \
        + z.astype(jnp.int32) * h.astype(jnp.int32)
    return fxp.requantize(wide, prod, fp)


def run_int_stateful(qparams: Params, x_int: Array, cfg: QLSTMConfig,
                     state) -> Tuple[Array, tuple]:
    """Bit-exact integer GRU stack with an explicit cross-window carry
    (per layer ``(h,)``).  Window-by-window feeding is bit-identical to
    one call on the concatenated sequence — the serving contract."""
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
    """One oracle GRU layer, time-major — ``kernels/ref.qgru_seq_ref``
    resumed from ``carry = (h0,)``."""
    acts = model.acts
    (h0,) = carry
    hs, h_last = _ref.qgru_seq_ref(
        x_tm, p["w_x"], p["w_h"], p["b"], model.fxp,
        hs_slope_shift=acts.hs_slope_shift, hs_bound=acts.hs_bound,
        ht_min=acts.ht_min, ht_max=acts.ht_max, h0=h0)
    return hs, (h_last,)


def supports_int(model: QLSTMConfig, accel) -> Optional[str]:
    """None when the general int datapath covers the configuration (both
    ALU modes, hard or LUT activations), else the reason."""
    if model.acts.gate not in ("hard_sigmoid_star", "lut_sigmoid", "sigmoid"):
        return f"gate activation {model.acts.gate!r} has no integer datapath"
    if model.acts.cell not in ("hard_tanh", "lut_tanh", "tanh"):
        return f"cell activation {model.acts.cell!r} has no integer datapath"
    return None


def ops_per_inference(cfg: QLSTMConfig) -> int:
    """Operations per inference, 2 per multiply-accumulate, for the GRU
    stack + dense head — the GOP/s accounting convention of
    ``core.qlstm``."""
    total = 0
    for li in range(cfg.num_layers):
        m, h = cfg.layer_in_dim(li), cfg.hidden_size
        total += cfg.seq_len * 2 * 3 * h * (m + h)   # gate/candidate MACs
    return total + 2 * cfg.hidden_size * cfg.out_features


def weight_bytes(model: QLSTMConfig, acc) -> int:
    """Bytes of quantised GRU weights+biases the accelerator must hold."""
    itemsize = (acc.fxp.total_bits + 7) // 8
    wide_itemsize = 2 * itemsize
    total = 0
    for li in range(model.num_layers):
        m, h = model.layer_in_dim(li), model.hidden_size
        total += (m + h) * 3 * h * itemsize + 3 * h * wide_itemsize
    total += model.hidden_size * model.out_features * itemsize
    total += model.out_features * wide_itemsize
    return total


SPEC = register(CellSpec(
    name="gru",
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
