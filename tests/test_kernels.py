"""Per-kernel validation: sweep shapes/dtypes, assert exact equality against
the pure-jnp oracles in kernels/ref.py (interpret=True executes the kernel
body on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.accelerator import AcceleratorConfig
from repro.core.fixed_point import FXP_4_8, FXP_8_16, FixedPointConfig
from repro.core.qlstm import ActivationConfig, QLSTMConfig
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand_lstm(T, B, M, H, cfg):
    lo, hi = cfg.int_min, cfg.int_max + 1
    x = RNG.integers(lo, hi, (T, B, M)).astype(np.int8 if cfg.total_bits <= 8
                                               else np.int16)
    wx = RNG.integers(lo // 4, hi // 4, (M, 4 * H)).astype(x.dtype)
    wh = RNG.integers(lo // 8, hi // 8, (H, 4 * H)).astype(x.dtype)
    b = RNG.integers(-200, 200, (4 * H,)).astype(np.int32)
    return map(jnp.asarray, (x, wx, wh, b))


@pytest.mark.parametrize("T,B,M,H", [(3, 2, 1, 4), (7, 13, 3, 20),
                                     (6, 128, 1, 20), (2, 5, 10, 60),
                                     (12, 1, 2, 8)])
def test_qlstm_kernel_shapes(T, B, M, H):
    cfg = FXP_4_8
    x, wx, wh, b = _rand_lstm(T, B, M, H, cfg)
    want = ref.qlstm_seq_ref(x, wx, wh, b, cfg)
    model = QLSTMConfig(input_size=M, hidden_size=H, seq_len=T)
    got = ops.qlstm_seq(x, wx, wh, b, model)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("unit", ["mxu", "vpu"])
@pytest.mark.parametrize("method", ["arithmetic", "step"])
def test_qlstm_kernel_units_and_methods(unit, method):
    cfg = FXP_4_8
    x, wx, wh, b = _rand_lstm(6, 9, 2, 16, cfg)
    want = ref.qlstm_seq_ref(x, wx, wh, b, cfg)
    model = QLSTMConfig(input_size=2, hidden_size=16, seq_len=6,
                        acts=ActivationConfig(hs_method=method))
    got = ops.qlstm_seq(x, wx, wh, b, model,
                        AcceleratorConfig(compute_unit=unit, hs_method=method))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("cfg", [FXP_4_8, FixedPointConfig(6, 8), FXP_8_16])
@pytest.mark.parametrize("method", ["arithmetic", "step"])
def test_qlstm_kernel_stateful_resume_bit_exact(cfg, method):
    """Windowed execution with the carried (h, c) fed back into the kernel
    equals the one-shot run — outputs AND final state, across fxp widths
    and HardSigmoid* methods."""
    from repro.kernels.qlstm_cell import qlstm_seq_pallas
    x, wx, wh, b = _rand_lstm(9, 5, 2, 12, cfg)
    want, (h_w, c_w) = ref.qlstm_seq_ref(x, wx, wh, b, cfg,
                                         return_state=True)
    outs, state = [], (None, None)
    for w in range(3):                       # three windows of T=3
        o, state = qlstm_seq_pallas(x[3 * w:3 * (w + 1)], wx, wh, b,
                                    cfg=cfg, hs_method=method,
                                    h0=state[0], c0=state[1],
                                    return_state=True)
        outs.append(np.asarray(o))
    np.testing.assert_array_equal(np.concatenate(outs), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(state[0]), np.asarray(h_w))
    np.testing.assert_array_equal(np.asarray(state[1]), np.asarray(c_w))


@pytest.mark.parametrize("unit", ["mxu", "vpu"])
@pytest.mark.parametrize("num_layers", [1, 2, 3])
def test_qlstm_multilayer_kernel_vs_layered_ref(num_layers, unit):
    """The fused multi-layer entry — all layers in ONE pallas_call, state
    resident in VMEM — is bit-exact with threading the oracle through the
    stack layer by layer, including the per-layer final state and a
    non-zero initial carry."""
    from repro.kernels.qlstm_cell import qlstm_seq_multilayer_pallas
    cfg = FXP_4_8
    T, B, M, H = 5, 5, 2, 12
    x, wx0, wh0, b0 = _rand_lstm(T, B, M, H, cfg)
    wxs, whs, bs = [wx0], [wh0], [b0]
    for _ in range(num_layers - 1):
        _, wxd, whd, bd = _rand_lstm(T, B, H, H, cfg)
        wxs.append(wxd), whs.append(whd), bs.append(bd)
    h0s = tuple(jnp.asarray(RNG.integers(-100, 100, (B, H)), jnp.int32)
                for _ in range(num_layers))
    c0s = tuple(jnp.asarray(RNG.integers(-100, 100, (B, H)), jnp.int32)
                for _ in range(num_layers))
    got, state = qlstm_seq_multilayer_pallas(
        x, tuple(wxs), tuple(whs), tuple(bs), h0s, c0s, cfg=cfg,
        compute_unit=unit, batch_block=2)        # batch 5 -> padded to 6
    h_t = x
    for li in range(num_layers):
        h_t, (h_l, c_l) = ref.qlstm_seq_ref(
            h_t.astype(x.dtype), wxs[li], whs[li], bs[li], cfg,
            h0=h0s[li], c0=c0s[li], return_state=True)
        np.testing.assert_array_equal(np.asarray(state[li][0]),
                                      np.asarray(h_l))
        np.testing.assert_array_equal(np.asarray(state[li][1]),
                                      np.asarray(c_l))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(h_t))


def test_qlstm_multilayer_kernel_rejects_mismatched_tuples():
    """Per-layer tuples that disagree on the layer count fail loudly."""
    from repro.kernels.qlstm_cell import qlstm_seq_multilayer_pallas
    cfg = FXP_4_8
    x, wx, wh, b = _rand_lstm(3, 2, 1, 4, cfg)
    z = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="layer count"):
        qlstm_seq_multilayer_pallas(x, (wx,), (wh, wh), (b,), (z,), (z,),
                                    cfg=cfg)


def _slot_battery_weights(num_layers, cfg, T, B, M, H):
    """Per-layer random weights for the slot-kernel battery."""
    x, wx0, wh0, b0 = _rand_lstm(T, B, M, H, cfg)
    wxs, whs, bs = [wx0], [wh0], [b0]
    for _ in range(num_layers - 1):
        _, wxd, whd, bd = _rand_lstm(T, B, H, H, cfg)
        wxs.append(wxd), whs.append(whd), bs.append(bd)
    return x, tuple(wxs), tuple(whs), tuple(bs)


def _check_slot_kernel(cfg, method, num_layers, H):
    """One slot-battery case: random slot permutations of a pre-filled
    table through the slot kernel vs the multilayer kernel handed the
    host-gathered carries, outputs AND updated table."""
    from repro.kernels.qlstm_cell import (TABLE_LANES, gather_carry,
                                          qlstm_seq_multilayer_pallas,
                                          qlstm_seq_slot_pallas,
                                          scatter_carry, state_table_shape,
                                          table_chunks)
    T, B, M = 4, 5, 2
    n_data = 6                      # slots 0..5; ZERO = 6, TRASH = 7
    zero_slot, trash_slot = n_data, n_data + 1
    x, wxs, whs, bs = _slot_battery_weights(num_layers, cfg, T, B, M, H)
    rng = np.random.default_rng(7 * num_layers + len(method))
    all_slots = jnp.arange(n_data + 2)
    shape = state_table_shape(n_data + 2, num_layers, 2, H)
    for trial in range(3):
        table = rng.integers(-100, 100,
                             (n_data + 2, num_layers, 2, H)).astype(np.int32)
        table[zero_slot] = 0
        packed = scatter_carry(
            jnp.zeros(shape, jnp.int32), all_slots,
            [(table[:, li, 0], table[:, li, 1]) for li in range(num_layers)])
        gather = rng.permutation(n_data)[:B].astype(np.int32)
        scatter = rng.permutation(n_data)[:B].astype(np.int32)
        gather[0] = zero_slot       # a fresh/evicted stream's row
        scatter[1] = trash_slot     # a padding/tombstoned row
        got, new_packed = qlstm_seq_slot_pallas(
            x, jnp.asarray(gather), jnp.asarray(scatter), packed,
            wxs, whs, bs, cfg=cfg, hs_method=method)
        # Pad lanes stay zero; unpack to the logical (slots, L, 2, H) view.
        lanes = np.asarray(new_packed).reshape(
            num_layers * 2, table_chunks(H), n_data + 2, TABLE_LANES
        ).transpose(0, 2, 1, 3).reshape(num_layers * 2, n_data + 2, -1)
        assert not lanes[..., H:].any()
        new_table = np.stack(
            [np.stack(layer, axis=1) for layer in gather_carry(
                new_packed, all_slots, num_layers, 2, H)], axis=1)
        # Oracle: gather the same carries host-side, run the plain
        # multilayer kernel, scatter host-side.
        h0s = tuple(jnp.asarray(table[gather, li, 0])
                    for li in range(num_layers))
        c0s = tuple(jnp.asarray(table[gather, li, 1])
                    for li in range(num_layers))
        want, state = qlstm_seq_multilayer_pallas(
            x, wxs, whs, bs, h0s, c0s, cfg=cfg, hs_method=method)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        expect = table.copy()
        for i in range(B):
            if scatter[i] == trash_slot:
                continue
            for li in range(num_layers):
                expect[scatter[i], li, 0] = np.asarray(state[li][0][i])
                expect[scatter[i], li, 1] = np.asarray(state[li][1][i])
        expect[trash_slot] = new_table[trash_slot]  # don't-care
        np.testing.assert_array_equal(new_table, expect)
        # The ZERO row survives every wave unwritten.
        assert not new_table[zero_slot].any()


@pytest.mark.parametrize("cfg", [FXP_4_8, FixedPointConfig(6, 8), FXP_8_16])
@pytest.mark.parametrize("method", ["arithmetic", "step"])
@pytest.mark.parametrize("num_layers", [1, 2, 3])
def test_qlstm_slot_kernel_matches_gathered_multilayer(cfg, method,
                                                       num_layers):
    """The slot-battery acceptance sweep: the in-kernel gather/scatter
    entry, over random slot PERMUTATIONS of a pre-filled state table, is
    bit-exact with the multilayer kernel handed the same carries as
    explicit (h, c) arrays — outputs AND the updated table — across fxp
    widths, HardSigmoid methods, and 1-3 layers.  Also pins the table
    conventions: ZERO-row gathers start the recurrence from the reset
    carry, TRASH-row scatters drop a row's final state, and table rows
    the wave never scattered to are byte-identical before/after."""
    _check_slot_kernel(cfg, method, num_layers, H=8)


def test_qlstm_slot_kernel_multi_chunk_rows():
    """A carry wider than one 128-lane table row (H=136 spans two row
    chunks per component) through the same battery."""
    _check_slot_kernel(FXP_4_8, "arithmetic", 2, H=136)


def test_qlstm_slot_kernel_validates_inputs():
    """Layer-count mismatches and undersized tables fail loudly."""
    from repro.kernels.qlstm_cell import (qlstm_seq_slot_pallas,
                                          state_table_shape)
    cfg = FXP_4_8
    x, wx, wh, b = _rand_lstm(3, 2, 1, 4, cfg)
    slots = jnp.zeros((2,), jnp.int32)
    table = jnp.zeros(state_table_shape(5, 1, 2, 4), jnp.int32)
    with pytest.raises(ValueError, match="layer count"):
        qlstm_seq_slot_pallas(x, slots, slots, table, (wx, wx), (wh,), (b,),
                              cfg=cfg)
    for bad in (state_table_shape(2, 1, 2, 4), (5, 1, 2, 4)):
        with pytest.raises(ValueError, match="table"):
            qlstm_seq_slot_pallas(x, slots, slots, jnp.zeros(bad, jnp.int32),
                                  (wx,), (wh,), (b,), cfg=cfg)


def test_qlstm_kernel_int16_datapath():
    """(8,16) — the baseline [15] width — through the same kernel."""
    cfg = FXP_8_16
    x, wx, wh, b = _rand_lstm(4, 3, 1, 8, cfg)
    want = ref.qlstm_seq_ref(x, wx, wh, b, cfg)
    model = QLSTMConfig(input_size=1, hidden_size=8, seq_len=4, fxp=cfg)
    got = ops.qlstm_seq(x, wx, wh, b, model)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 5),
       st.integers(0, 2))
@settings(max_examples=25, deadline=None)
def test_quant_matmul_property(mi, ki, ni, blocki):
    m, k, n = mi * 13, ki * 17, ni * 11
    block = [(16, 16, 16), (32, 16, 8), (128, 128, 128)][blocki]
    x = RNG.integers(-128, 128, (m, k)).astype(np.int8)
    w = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    got = ops.quant_matmul(jnp.asarray(x), jnp.asarray(w), block=block)
    np.testing.assert_array_equal(
        np.asarray(got), x.astype(np.int32) @ w.astype(np.int32))


def test_quant_matmul_requant_fused():
    cfg = FXP_4_8
    x = RNG.integers(-128, 128, (50, 70)).astype(np.int8)
    w = RNG.integers(-128, 128, (70, 90)).astype(np.int8)
    got = ops.quant_matmul_requant(jnp.asarray(x), jnp.asarray(w), cfg,
                                   block=(32, 32, 32))
    want = ref.quant_matmul_requant_ref(jnp.asarray(x), jnp.asarray(w), cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert got.dtype == jnp.int8


@pytest.mark.parametrize("cfg", [FXP_4_8, FixedPointConfig(6, 8),
                                 FixedPointConfig(8, 10), FXP_8_16])
@pytest.mark.parametrize("method", ["arithmetic", "1to1", "step"])
def test_hard_act_kernel_all_configs(cfg, method):
    xs = jnp.arange(cfg.int_min, cfg.int_max + 1).reshape(-1, 16) \
        .astype(cfg.storage_dtype)
    got = ops.hard_sigmoid_star_int(xs, cfg, method=method)
    want = ref.hard_act_ref(xs, cfg, method)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_hard_tanh_kernel():
    cfg = FXP_4_8
    xs = jnp.arange(-128, 128).reshape(16, 16).astype(jnp.int8)
    got = ops.hard_tanh_int(xs, cfg)
    want = ref.hard_tanh_ref(xs, cfg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# flash_attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,s,hd,causal,window", [
    (64, 64, 32, True, None),
    (64, 64, 32, False, None),
    (96, 96, 16, True, 24),       # SWA
    (40, 72, 32, False, None),    # padded, cross-attention shapes
    (128, 128, 64, True, None),
])
def test_flash_attention_vs_ref(t, s, hd, causal, window):
    if causal and t != s:
        pytest.skip("causal requires t == s here")
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(0, 1, (3, t, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (3, s, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (3, s, hd)).astype(np.float32))
    from repro.kernels.flash_attention import flash_attention_pallas
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=32, block_k=32)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_mha_flash_gqa_matches_model_attention():
    """The Pallas kernel agrees with the model's chunked-jnp attention
    (layers.flash_attention) — kernel and pure-JAX paths are interchangeable."""
    from repro.models.layers import flash_attention as jnp_attn
    rng = np.random.default_rng(8)
    b, t, h, kv, hd = 2, 64, 4, 2, 16
    q = jnp.asarray(rng.normal(0, 1, (b, t, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(0, 1, (b, t, kv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(0, 1, (b, t, kv, hd)).astype(np.float32))
    got = ops.mha_flash(q, k, v, causal=True, scale=hd ** -0.5,
                        block_q=16, block_k=16)
    want = jnp_attn(q, k, v, causal=True, scale=hd ** -0.5,
                    q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# rglru_scan kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,bsz,w", [(5, 3, 8), (16, 7, 32), (9, 128, 16)])
def test_rglru_kernel_vs_ref(t, bsz, w):
    from repro.kernels.rglru_scan import rglru_seq_pallas
    rng = np.random.default_rng(11)
    log_a = jnp.asarray(-np.abs(rng.normal(0, 1, (t, bsz, w))).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 1, (t, bsz, w)).astype(np.float32))
    got = rglru_seq_pallas(log_a, b, batch_block=4)
    want = ref.rglru_seq_ref(log_a, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_rglru_kernel_matches_model_recurrence():
    """The fused kernel computes exactly the model's RG-LRU recurrence given
    the model's own decays/inputs."""
    from repro.kernels.rglru_scan import rglru_seq_pallas
    from repro.models import rglru as RG
    from repro.configs import ARCH_CONFIGS, reduce_config
    from repro.models import transformer as TT
    cfg = reduce_config(ARCH_CONFIGS["recurrentgemma-2b"])
    p_full, _ = TT.init_model(cfg, jax.random.key(0))
    p = jax.tree.map(lambda x: x[0], p_full["groups"][0]["mixer"])
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(0, 1, (3, 7, cfg.recurrent.lru_width))
                    .astype(np.float32))
    want = RG.rglru_scan(p, x, cfg)
    a, mult, i = RG._decay(p, x, cfg)
    log_a = jnp.log(jnp.maximum(a, 1e-30))
    b = mult * (i * x)
    got = rglru_seq_pallas(jnp.swapaxes(log_a, 0, 1), jnp.swapaxes(b, 0, 1))
    np.testing.assert_allclose(np.asarray(jnp.swapaxes(got, 0, 1)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)
