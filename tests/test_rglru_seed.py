"""Seed coverage for the float RG-LRU stack — ``models/rglru.py`` and
``kernels/rglru_scan.py``.

These are the Griffin-faithful float-path modules (exp/softplus/sqrt
datapath) that the quantised ``cells/rglru.py`` deliberately REdefines
for hardware; they ship in the seed but had no dedicated tests.  This
file pins them: shape/finiteness on the block forward, decode==train
equivalence through the conv window and recurrent state, and fixed-seed
regression values so a silent numeric change (a dropped normaliser, a
sign flip in the decay) fails loudly rather than drifting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_CONFIGS, reduce_config
from repro.kernels import ref
from repro.kernels.rglru_scan import rglru_seq_pallas
from repro.models import rglru as RG
from repro.models.modules import unbox


@pytest.fixture(scope="module")
def cfg():
    return reduce_config(ARCH_CONFIGS["recurrentgemma-2b"])


@pytest.fixture(scope="module")
def params(cfg):
    # The pins below are the random bits jax.random.key(3) draws under
    # the partitionable threefry that JAX >= 0.5 uses by default.
    p, _ = unbox(RG.init_rglru_block(jax.random.key(3), cfg))
    return p


def test_init_rglru_block_tree(cfg, params):
    """The block's param tree: every Griffin surface, correct shapes."""
    d, w = cfg.d_model, cfg.recurrent.lru_width
    cw = cfg.recurrent.conv_width
    want = {"w_x": (d, w), "w_gate": (d, w), "w_out": (w, d),
            "conv_w": (cw, w), "conv_b": (w,), "w_a": (w, w), "b_a": (w,),
            "w_i": (w, w), "b_i": (w,), "lam": (w,)}
    assert set(params) == set(want)
    for name, shape in want.items():
        assert params[name].shape == shape, name
    # Biases start at zero, Lambda at one (Griffin's stable decay band).
    assert not np.any(np.asarray(params["b_a"]))
    assert not np.any(np.asarray(params["b_i"]))
    np.testing.assert_array_equal(np.asarray(params["lam"]),
                                  np.ones(w, np.float32))


def test_rglru_scan_shape_finite_and_pinned(cfg, params):
    """Fixed-seed regression: the scan's output is pinned, not just
    finite — decay normalisation bugs move these digits."""
    rng = np.random.default_rng(42)
    w = cfg.recurrent.lru_width
    x = jnp.asarray(rng.normal(0, 1, (2, 7, w)).astype(np.float32))
    h = RG.rglru_scan(params, x, cfg)
    assert h.shape == (2, 7, w)
    assert bool(jnp.all(jnp.isfinite(h)))
    np.testing.assert_allclose(float(jnp.sum(h)), -18.600780, atol=1e-3)
    np.testing.assert_allclose(
        np.asarray(h)[0, -1, :4],
        [-0.694003, -0.418674, 0.379087, 0.140698], atol=1e-4)


def test_rec_block_apply_train_shape_finite_and_pinned(cfg, params):
    rng = np.random.default_rng(42)
    w = cfg.recurrent.lru_width
    rng.normal(0, 1, (2, 7, w))           # keep the draw order of the pin
    x = jnp.asarray(rng.normal(0, 1, (2, 5, cfg.d_model)).astype(np.float32))
    y = RG.rec_block_apply(params, x, cfg)
    assert y.shape == (2, 5, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(
        np.asarray(y)[1, -1, :4],
        [-1.313005, 1.646791, 0.959831, 0.029209], atol=1e-4)


def test_rec_block_decode_equals_train(cfg, params):
    """O(1) decode through the conv window + recurrent state reproduces
    the full train/prefill forward step-for-step."""
    rng = np.random.default_rng(7)
    w, cw = cfg.recurrent.lru_width, cfg.recurrent.conv_width
    x = jnp.asarray(rng.normal(0, 1, (2, 6, cfg.d_model)).astype(np.float32))
    y_train = RG.rec_block_apply(params, x, cfg)
    state = {"h": jnp.zeros((2, w)), "conv": jnp.zeros((2, cw - 1, w))}
    outs = []
    for t in range(6):
        y_t, state = RG.rec_block_apply(params, x[:, t:t + 1], cfg,
                                        mode="decode", state=state)
        outs.append(y_t)
    y_dec = jnp.concatenate(outs, axis=1)
    assert state["h"].shape == (2, w)
    assert state["conv"].shape == (2, cw - 1, w)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_train),
                               rtol=1e-5, atol=1e-5)


def test_rglru_seq_pallas_matches_ref_and_pinned():
    """The fused sequential kernel against its oracle (exact) plus a
    fixed-seed pin, including a batch that needs padding (3 rows,
    batch_block=2)."""
    rng = np.random.default_rng(42)
    rng.normal(0, 1, (2, 7, 64))          # keep the draw order of the pin
    rng.normal(0, 1, (2, 5, 64))
    log_a = jnp.asarray(rng.uniform(-1.0, -0.01, (6, 3, 8)).astype(np.float32))
    b = jnp.asarray(rng.normal(0, 1, (6, 3, 8)).astype(np.float32))
    h = rglru_seq_pallas(log_a, b, batch_block=2)
    assert h.shape == (6, 3, 8)
    np.testing.assert_array_equal(np.asarray(h),
                                  np.asarray(ref.rglru_seq_ref(log_a, b)))
    np.testing.assert_allclose(
        np.asarray(h)[-1, 0, :4],
        [-1.130105, -1.414136, 0.452369, -0.542766], atol=1e-4)


def test_rglru_seq_pallas_zero_decay_is_cumulative_sum():
    """log_a == 0 (a == 1) degenerates to a running sum — an analytic
    anchor independent of the oracle."""
    rng = np.random.default_rng(5)
    b = jnp.asarray(rng.normal(0, 1, (5, 2, 8)).astype(np.float32))
    h = rglru_seq_pallas(jnp.zeros_like(b), b, batch_block=2)
    np.testing.assert_allclose(np.asarray(h),
                               np.cumsum(np.asarray(b), axis=0),
                               rtol=1e-5, atol=1e-5)
