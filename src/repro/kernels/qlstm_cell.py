"""Fused quantised-LSTM sequence kernel — the paper's pipelined ALU (C3)
re-thought for the TPU memory hierarchy.

FPGA design (paper §5.2)                 This kernel
----------------------------------       ------------------------------------
5-stage pipeline: load W[i],x[i] ∥       Pallas grid pipeline: HBM→VMEM DMA of
  multiply ∥ accumulate                    x_{t+1} overlapped with step-t MXU/
                                           VPU compute (double buffering).
Weights in BRAM, no off-chip access      Weights fetched once into VMEM and
                                           resident across all T grid steps
                                           (constant index_map ⇒ no re-fetch).
16-bit accumulator, round ONCE (S5)      int32 accumulator in VMEM scratch,
                                           single round-half-up shift per MAC.
ALU_resource_type = DSP | LUT            compute_unit = mxu (int8 systolic
                                           matmul) | vpu (vector mul-reduce).
HardSigmoid* methods                      arithmetic (shift+add+selects) and
                                           step (unrolled comparator cascade);
                                           both bit-identical to the oracle.
State registers (h, c) in SRAM           per-layer (h, c) VMEM scratch, seeded
                                           from the carried state at t == 0 and
                                           emitted as extra outputs at the last
                                           step — the stream-resume contract of
                                           ``repro.serving``.

Grid = (batch_blocks, T); T is the minor axis, so the (h, c) VMEM scratch
carries state across timesteps of one batch block.  At t == 0 the scratch
is seeded from the ``(h0, c0)`` inputs (all-zero for a fresh stream), and
at t == T-1 it is written to the final-state outputs, so a window-by-window
resumed run is bit-identical to one concatenated run.

Three public entry points share one cell-step implementation:

  * :func:`qlstm_seq_pallas` — one layer, optionally resumed from a carried
    ``(h0, c0)`` and optionally returning the final state.
  * :func:`qlstm_seq_multilayer_pallas` — the whole LSTM stack fused into
    ONE ``pallas_call``: every layer's (h, c) stays resident in VMEM and
    layer *l*'s hidden state at step *t* feeds layer *l+1* at the same step
    without ever round-tripping through HBM (the Python-level per-layer
    re-launch of ``backends.common.run_layered`` is exactly what this
    removes from the serving hot path).
  * :func:`qlstm_seq_slot_pallas` — the multi-layer kernel with
    DEVICE-RESIDENT stream state: instead of shipping ``(h0, c0)`` batch
    arrays from the host, the call carries a persistent HBM state TABLE
    (:func:`state_table_shape`: ``n_slots + 2`` slots, each stream's
    per-layer (h, c) in 128-lane rows) plus two per-row int32 slot-id
    vectors.  At t == 0 each batch row DMAs its carry from slot
    ``gather_slots[i]``; at t == T-1 each row DMAs its final (h, c) into
    slot ``scatter_slots[i]`` — all inside the kernel, so the host ships
    only integer inputs and slot ids per wave.  Slot ``n_slots`` is the
    ZERO slot (always the reset carry, gathered by fresh/reset streams,
    never written); slot ``n_slots + 1`` is the TRASH slot (the scatter
    target for padding/retired rows, never read).
    Because every gather happens at t == 0 and every scatter at t == T-1,
    a slot freed and reassigned within one wave is still read before it
    is overwritten.

Oracle: ``kernels/ref.py::qlstm_seq_ref`` (bit-exact, including the carry).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import hard_act
from repro.core.fixed_point import FixedPointConfig, product_config

Array = jax.Array


def _cell_math(cfg: FixedPointConfig, hs_method: str, hs_slope_shift: int,
               hs_bound: float, ht_min: float, ht_max: float):
    """The shared integer arithmetic of every kernel variant: the S5
    late-rounding requant plus the hard activations.  Built from the exact
    oracle helpers (core/hard_act.py) so the kernels cannot drift from
    ``kernels/ref.py``.  The 'step' method is the gather-free unrolled
    cascade; HardTanh is the same pair of comparators the oracle clips
    with."""
    prod = product_config(cfg, cfg)
    shift = prod.frac_bits - cfg.frac_bits          # 2a -> a
    half = 1 << (shift - 1)
    spec = hard_act.HardSigmoidStarSpec(cfg, hs_slope_shift, hs_bound)
    lo = cfg.int_min
    hi = cfg.int_max
    hs_fn = (hard_act.hs_star_int_step_unrolled if hs_method == "step"
             else hard_act.hs_star_int_arithmetic)
    hs = lambda v: hs_fn(v, spec)
    ht = functools.partial(hard_act.hard_tanh_int, cfg=cfg,
                           min_val=ht_min, max_val=ht_max)

    def requant(v):  # round-half-up shift + saturate: the single S5 rounding
        return jnp.clip((v + half) >> shift, lo, hi)

    return requant, hs, ht


def _stack_step(x_t, wx, wh, b, h_s, c_s, *, hdim, compute_unit,
                requant, hs, ht):
    """One timestep through the whole fused layer stack: reads and updates
    the per-layer (h, c) VMEM scratch refs in place and returns the final
    layer's new hidden state.  Layer li's step-t output feeds layer li+1
    at the same step, staying in VMEM/registers — no HBM round-trip
    between layers."""
    carrier = x_t.dtype
    inp = x_t
    for li in range(len(wh)):
        h8 = h_s[li][...].astype(carrier)  # stored codes fit the carrier
        if compute_unit == "mxu":
            # int8 x int8 -> int32 systolic matmul (the DSP analogue)
            acc = jax.lax.dot_general(
                inp, wx[li][...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            acc += jax.lax.dot_general(
                h8, wh[li][...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
        else:
            # VPU: broadcast multiply + reduce (the LUT-fabric analogue)
            acc = jnp.sum(inp.astype(jnp.int32)[:, :, None]
                          * wx[li][...].astype(jnp.int32)[None, :, :],
                          axis=1)
            acc += jnp.sum(h8.astype(jnp.int32)[:, :, None]
                           * wh[li][...].astype(jnp.int32)[None, :, :],
                           axis=1)
        acc += b[li][...]                # bias at accumulator precision
        pre = requant(acc)               # late rounding (S5)

        i = hs(pre[:, :hdim])
        f = hs(pre[:, hdim:2 * hdim])
        g = ht(pre[:, 2 * hdim:3 * hdim])
        o = hs(pre[:, 3 * hdim:])

        c = c_s[li][...]
        wide = f * c + i * g             # both products wide, add, ...
        c_new = requant(wide)            # ... round once
        tanh_c = ht(c_new)
        h_new = requant(o * tanh_c)

        h_s[li][...] = h_new
        c_s[li][...] = c_new
        inp = h_new.astype(carrier)
    return inp


def _make_kernel(cfg: FixedPointConfig, hdim: int, hs_method: str,
                 hs_slope_shift: int, hs_bound: float,
                 ht_min: float, ht_max: float, compute_unit: str,
                 t_len: int, num_layers: int):
    requant, hs, ht = _cell_math(cfg, hs_method, hs_slope_shift, hs_bound,
                                 ht_min, ht_max)

    def kernel(*refs):
        # Ref layout (L = num_layers): x, L*w_x, L*w_h, L*b, L*h0, L*c0 |
        # out, L*h_fin, L*c_fin | L*h_scratch, L*c_scratch.
        n = num_layers
        x_ref = refs[0]
        wx = refs[1:1 + n]
        wh = refs[1 + n:1 + 2 * n]
        b = refs[1 + 2 * n:1 + 3 * n]
        h0 = refs[1 + 3 * n:1 + 4 * n]
        c0 = refs[1 + 4 * n:1 + 5 * n]
        out_ref = refs[1 + 5 * n]
        h_fin = refs[2 + 5 * n:2 + 6 * n]
        c_fin = refs[2 + 6 * n:2 + 7 * n]
        h_s = refs[2 + 7 * n:2 + 8 * n]
        c_s = refs[2 + 8 * n:2 + 9 * n]
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _():
            # Seed the state scratch from the carried (h0, c0) — the zero
            # reset state for a fresh stream, window k's final state when
            # resuming window k+1.
            for li in range(n):
                h_s[li][...] = h0[li][...]
                c_s[li][...] = c0[li][...]

        out_ref[0] = _stack_step(
            x_ref[0], wx, wh, b, h_s, c_s, hdim=hdim,
            compute_unit=compute_unit, requant=requant, hs=hs,
            ht=ht).astype(out_ref.dtype)         # final layer's h_t

        @pl.when(t == t_len - 1)
        def _():
            for li in range(n):
                h_fin[li][...] = h_s[li][...]
                c_fin[li][...] = c_s[li][...]

    return kernel


#: Lane width of the state table's minor axis.  Mosaic moves one table
#: row per DMA only when the row is exactly one 128-lane tile wide, so
#: wider carries are split into 128-lane chunks along the leading axis.
TABLE_LANES = 128


def table_chunks(hidden: int) -> int:
    """128-lane chunks one (hidden,) carry component occupies."""
    return -(-hidden // TABLE_LANES)


def state_table_shape(n_rows: int, num_layers: int, arity: int,
                      hidden: int) -> Tuple[int, int, int]:
    """Shape of the device state table: ``(K, n_rows, 128)`` int32 with
    ``K = num_layers * arity * table_chunks(hidden)``.

    Component-major: chunk ``j`` of carry component ``s`` of layer ``li``
    is the ``(n_rows, 128)`` slab ``k = (li * arity + s) * chunks + j``,
    one stream per sublane row, its codes in the low lanes and zeros in
    the pad lanes.  One stream's whole carry is the strided column
    ``table[:, slot, :]`` — a single DMA — and the slabs tile HBM with no
    sublane padding."""
    return (num_layers * arity * table_chunks(hidden), n_rows, TABLE_LANES)


def _components(rows: Array, hidden: int):
    """``(K, B, 128)`` staged rows -> the K/chunks ``(B, hidden)`` carry
    components in table order."""
    nch = table_chunks(hidden)
    return [jnp.concatenate([rows[k + j] for j in range(nch)],
                            axis=1)[:, :hidden] if nch > 1
            else rows[k][:, :hidden]
            for k in range(0, rows.shape[0], nch)]


def gather_carry(table: Array, slots: Array, num_layers: int, arity: int,
                 hidden: int):
    """The per-layer carry of table rows ``slots``: ``num_layers`` tuples
    of ``arity`` (B, hidden) int32 arrays — the inverse of
    :func:`scatter_carry`, in jnp for the XLA engines and the host."""
    comps = _components(jnp.take(table, slots, axis=1), hidden)
    return tuple(tuple(comps[li * arity:(li + 1) * arity])
                 for li in range(num_layers))


def scatter_carry(table: Array, slots: Array, state) -> Array:
    """``table`` with rows ``slots`` set to the per-layer carry ``state``
    (tuples of (B, hidden) arrays); pad lanes are written as zeros."""
    comps = [jnp.asarray(a, jnp.int32) for layer in state for a in layer]
    bsz, hidden = comps[0].shape
    nch = table_chunks(hidden)
    pad = nch * TABLE_LANES - hidden
    rows = jnp.stack([jnp.pad(a, ((0, 0), (0, pad)))
                      .reshape(bsz, nch, TABLE_LANES).transpose(1, 0, 2)
                      for a in comps]).reshape(-1, bsz, TABLE_LANES)
    return table.at[:, slots, :].set(rows)


def _make_slot_kernel(cfg: FixedPointConfig, hdim: int, hs_method: str,
                      hs_slope_shift: int, hs_bound: float,
                      ht_min: float, ht_max: float, compute_unit: str,
                      t_len: int, num_layers: int, bsz: int):
    requant, hs, ht = _cell_math(cfg, hs_method, hs_slope_shift, hs_bound,
                                 ht_min, ht_max)
    nch = table_chunks(hdim)

    def kernel(*refs):
        # Ref layout (L = num_layers): gather_slots, scatter_slots (SMEM,
        # scalar-prefetched) | x, table (HBM) | L*w_x, L*w_h, L*b | out,
        # table_out (HBM, aliases table) | rows, L*h_s, L*c_s, dma_sem.
        n = num_layers
        g_ref, s_ref, x_ref, tbl_in = refs[:4]
        wx = refs[4:4 + n]
        wh = refs[4 + n:4 + 2 * n]
        b = refs[4 + 2 * n:4 + 3 * n]
        out_ref = refs[4 + 3 * n]
        tbl_out = refs[5 + 3 * n]
        rows = refs[6 + 3 * n]
        h_s = refs[7 + 3 * n:7 + 4 * n]
        c_s = refs[7 + 4 * n:7 + 5 * n]
        sem = refs[7 + 5 * n]
        t = pl.program_id(0)
        carries = [ref for li in range(n) for ref in (h_s[li], c_s[li])]

        def copy_rows(src, src_row, dst, dst_row):
            # One strided DMA per batch row moves that stream's whole
            # carry (every layer, h and c); start all B, then wait all B.
            def row(ref, r):
                return ref.at[:, pl.ds(r, 1), :]

            def start(i, c):
                pltpu.make_async_copy(row(src, src_row(i)),
                                      row(dst, dst_row(i)), sem).start()
                return c

            def wait(i, c):
                pltpu.make_async_copy(row(src, 0), row(dst, 0), sem).wait()
                return c

            jax.lax.fori_loop(0, bsz, start, 0)
            jax.lax.fori_loop(0, bsz, wait, 0)

        @pl.when(t == 0)
        def _():
            # GATHER: row i's carry comes from table row gather_slots[i] —
            # the ZERO row for fresh/reset streams.  Every gather completes
            # before any scatter starts.
            copy_rows(tbl_in, lambda i: g_ref[i], rows, lambda i: i)
            for ref, comp in zip(carries, _components(rows, hdim)):
                ref[...] = comp

        out_ref[0] = _stack_step(
            x_ref[0], wx, wh, b, h_s, c_s, hdim=hdim,
            compute_unit=compute_unit, requant=requant, hs=hs,
            ht=ht).astype(out_ref.dtype)

        @pl.when(t == t_len - 1)
        def _():
            # SCATTER: row i's final (h, c) lands in table row
            # scatter_slots[i] — the TRASH row for retired/padding rows.
            # Duplicate targets only ever occur at TRASH (the allocator
            # hands out unique live slots), whose content is never read.
            # Pad lanes keep the gathered row's zeros.
            for k, ref in enumerate(carries):
                for j in range(nch):
                    w = min(TABLE_LANES, hdim - j * TABLE_LANES)
                    rows[k * nch + j, :, :w] = \
                        ref[:, j * TABLE_LANES:j * TABLE_LANES + w]
            copy_rows(rows, lambda i: i, tbl_out, lambda i: s_ref[i])

    return kernel


def _qlstm_pallas(x_int, w_xs, w_hs, b_wides, h0s, c0s, *,
                  cfg: FixedPointConfig, hs_method: str, hs_slope_shift: int,
                  hs_bound: float, ht_min: float, ht_max: float,
                  compute_unit: str, batch_block: Optional[int],
                  interpret: bool):
    """Shared driver behind both public entries: one ``pallas_call`` over
    ``len(w_hs)`` fused layers, returning ``(out_seq, h_fin, c_fin)`` with
    the per-layer final state as tuples."""
    t_len, bsz, m = x_int.shape
    n = len(w_hs)
    hdim = w_hs[0].shape[0]
    bb = batch_block or min(bsz, 128)
    pad = (-bsz) % bb
    if pad:
        x_int = jnp.pad(x_int, ((0, 0), (0, pad), (0, 0)))
        # Padding rows start from (and produce) garbage-free zero state;
        # they are sliced away before return either way.
        h0s = tuple(jnp.pad(h, ((0, pad), (0, 0))) for h in h0s)
        c0s = tuple(jnp.pad(c, ((0, pad), (0, 0))) for c in c0s)
    bsz_p = bsz + pad
    nb = bsz_p // bb

    kernel = _make_kernel(cfg, hdim, hs_method, hs_slope_shift, hs_bound,
                          ht_min, ht_max, compute_unit, t_len, n)
    resident = lambda bi, t: (0, 0)                    # fetched once, stays
    per_block = lambda bi, t: (bi, 0)                  # constant across t
    in_specs = [pl.BlockSpec((1, bb, m), lambda bi, t: (t, bi, 0))]
    in_specs += [pl.BlockSpec(w.shape, resident) for w in w_xs]
    in_specs += [pl.BlockSpec(w.shape, resident) for w in w_hs]
    in_specs += [pl.BlockSpec((1, 4 * hdim), resident)] * n
    in_specs += [pl.BlockSpec((bb, hdim), per_block)] * (2 * n)
    out_specs = [pl.BlockSpec((1, bb, hdim), lambda bi, t: (t, bi, 0))]
    out_specs += [pl.BlockSpec((bb, hdim), per_block)] * (2 * n)
    out_shape = [jax.ShapeDtypeStruct((t_len, bsz_p, hdim), x_int.dtype)]
    out_shape += [jax.ShapeDtypeStruct((bsz_p, hdim), jnp.int32)] * (2 * n)
    outs = pl.pallas_call(
        kernel,
        grid=(nb, t_len),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bb, hdim), jnp.int32)] * (2 * n),
        interpret=interpret,
    )(x_int, *w_xs, *w_hs,
      *(b.reshape(1, -1).astype(jnp.int32) for b in b_wides),
      *(h.astype(jnp.int32) for h in h0s),
      *(c.astype(jnp.int32) for c in c0s))
    out = outs[0][:, :bsz]
    h_fin = tuple(o[:bsz] for o in outs[1:1 + n])
    c_fin = tuple(o[:bsz] for o in outs[1 + n:])
    return out, h_fin, c_fin


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "hs_method", "hs_slope_shift", "hs_bound",
                     "ht_min", "ht_max", "compute_unit", "batch_block",
                     "interpret", "return_state"))
def qlstm_seq_pallas(x_int: Array, w_x: Array, w_h: Array, b_wide: Array,
                     *, cfg: FixedPointConfig,
                     hs_method: str = "arithmetic",
                     hs_slope_shift: int = 3, hs_bound: float = 3.0,
                     ht_min: float = -1.0, ht_max: float = 1.0,
                     compute_unit: str = "mxu",
                     batch_block: Optional[int] = None,
                     interpret: bool = True,
                     h0: Optional[Array] = None, c0: Optional[Array] = None,
                     return_state: bool = False):
    """Run the fused kernel for one layer.

    x_int: (T, B, M) integer codes (storage dtype of cfg);
    w_x: (M, 4H); w_h: (H, 4H); b_wide: (4H,) int32.
    h0/c0: optional (B, H) int32 initial carry (zeros when omitted — the
    accelerator's reset state), seeded into the VMEM state scratch at
    t == 0; bit-exact with ``kernels/ref.qlstm_seq_ref(h0, c0)``.
    Returns (T, B, H) codes in the storage dtype; with
    ``return_state=True``, ``(out, (h_last, c_last))`` so the caller can
    resume the next window where this one left off.
    """
    _, bsz, _ = x_int.shape
    hdim = w_h.shape[0]
    if h0 is None:
        h0 = jnp.zeros((bsz, hdim), jnp.int32)
    if c0 is None:
        c0 = jnp.zeros((bsz, hdim), jnp.int32)
    out, (h_f,), (c_f,) = _qlstm_pallas(
        x_int, (w_x,), (w_h,), (b_wide,), (h0,), (c0,),
        cfg=cfg, hs_method=hs_method, hs_slope_shift=hs_slope_shift,
        hs_bound=hs_bound, ht_min=ht_min, ht_max=ht_max,
        compute_unit=compute_unit, batch_block=batch_block,
        interpret=interpret)
    if return_state:
        return out, (h_f, c_f)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "hs_method", "hs_slope_shift", "hs_bound",
                     "ht_min", "ht_max", "compute_unit", "batch_block",
                     "interpret"))
def qlstm_seq_multilayer_pallas(x_int: Array, w_xs: Tuple[Array, ...],
                                w_hs: Tuple[Array, ...],
                                b_wides: Tuple[Array, ...],
                                h0s: Tuple[Array, ...],
                                c0s: Tuple[Array, ...], *,
                                cfg: FixedPointConfig,
                                hs_method: str = "arithmetic",
                                hs_slope_shift: int = 3,
                                hs_bound: float = 3.0,
                                ht_min: float = -1.0, ht_max: float = 1.0,
                                compute_unit: str = "mxu",
                                batch_block: Optional[int] = None,
                                interpret: bool = True):
    """The whole LSTM stack, fused and stateful, in ONE ``pallas_call``.

    x_int: (T, B, M) integer codes; ``w_xs``/``w_hs``/``b_wides`` are
    per-layer tuples (layer 0's w_x is (M, 4H), deeper layers' (H, 4H);
    every w_h is (H, 4H), every b_wide (4H,) int32); ``h0s``/``c0s`` are
    the per-layer (B, H) int32 carry (``core.qlstm.init_int_state`` split
    into its h and c halves for a fresh stream).

    Every layer's (h, c) lives in VMEM scratch for the whole call and
    layer *l*'s step-t output feeds layer *l+1* at the same step without
    leaving the chip — unlike the layered Python loop, which launches one
    kernel per layer and round-trips the full (T, B, H) sequence through
    HBM between layers.

    Returns ``(out, state)``: out is the final layer's (T, B, H) hidden
    codes in the storage dtype; ``state`` is the per-layer
    ``((h_last, c_last), ...)`` int32 carry after the last step —
    bit-exact with threading ``kernels/ref.qlstm_seq_ref(h0, c0,
    return_state=True)`` through the stack layer by layer.
    """
    n = len(w_hs)
    if not (len(w_xs) == len(b_wides) == len(h0s) == len(c0s) == n):
        raise ValueError(
            f"per-layer tuples disagree on the layer count: "
            f"w_xs={len(w_xs)}, w_hs={n}, b_wides={len(b_wides)}, "
            f"h0s={len(h0s)}, c0s={len(c0s)}")
    out, h_fin, c_fin = _qlstm_pallas(
        x_int, tuple(w_xs), tuple(w_hs), tuple(b_wides), tuple(h0s),
        tuple(c0s),
        cfg=cfg, hs_method=hs_method, hs_slope_shift=hs_slope_shift,
        hs_bound=hs_bound, ht_min=ht_min, ht_max=ht_max,
        compute_unit=compute_unit, batch_block=batch_block,
        interpret=interpret)
    return out, tuple(zip(h_fin, c_fin))


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "hs_method", "hs_slope_shift", "hs_bound",
                     "ht_min", "ht_max", "compute_unit", "interpret"))
def qlstm_seq_slot_pallas(x_int: Array, gather_slots: Array,
                          scatter_slots: Array, table: Array,
                          w_xs: Tuple[Array, ...], w_hs: Tuple[Array, ...],
                          b_wides: Tuple[Array, ...], *,
                          cfg: FixedPointConfig,
                          hs_method: str = "arithmetic",
                          hs_slope_shift: int = 3, hs_bound: float = 3.0,
                          ht_min: float = -1.0, ht_max: float = 1.0,
                          compute_unit: str = "mxu",
                          interpret: bool = True):
    """The fused multi-layer stack with DEVICE-RESIDENT stream state.

    x_int: (T, B, M) integer codes; ``table``: the persistent int32 state
    table, shaped ``state_table_shape(n_slots + 2, L, 2, H)`` — axis 1
    indexes slots, slot ``n_slots`` is the always-zero RESET slot and
    slot ``n_slots + 1`` the write-only TRASH slot; ``gather_slots``/
    ``scatter_slots``: (B,) int32 slot ids, one per batch row.  Weight
    tuples as in :func:`qlstm_seq_multilayer_pallas`.

    The table stays in HBM and the slot ids are scalar-prefetched into
    SMEM.  At t == 0 the kernel DMAs row i's per-layer carry from slot
    ``gather_slots[i]`` into VMEM; at t == T-1 it DMAs the final
    per-layer (h, c) into slot ``scatter_slots[i]`` of the output table,
    which aliases the input — only the B scattered rows are written.  The
    host therefore ships only the integer inputs and two (B,) slot
    vectors per wave.  Because all gathers complete before any scatter
    starts, a slot evicted and reassigned within the same wave still
    sources its old owner's carry correctly.

    The whole batch runs as ONE grid block (grid is over time only): every
    row scatters into one shared table, so the grid must not parallelise
    over batch.  Returns ``(out, new_table)``: the final layer's (T, B, H)
    hidden codes and the updated state table.  Bit-exact with gathering
    ``(h0, c0)`` on the host and calling
    :func:`qlstm_seq_multilayer_pallas` with the same carries.
    """
    n = len(w_hs)
    if not (len(w_xs) == len(b_wides) == n):
        raise ValueError(
            f"per-layer tuples disagree on the layer count: "
            f"w_xs={len(w_xs)}, w_hs={n}, b_wides={len(b_wides)}")
    t_len, bsz, m = x_int.shape
    hdim = w_hs[0].shape[0]
    n_rows = table.shape[1] if table.ndim == 3 else 0
    if n_rows < 3 or table.shape != state_table_shape(n_rows, n, 2, hdim):
        raise ValueError(
            f"state table must be state_table_shape(n_slots + 2, {n}, 2, "
            f"{hdim}) with n_slots >= 1, got {table.shape}")
    sd = x_int.dtype

    kernel = _make_slot_kernel(cfg, hdim, hs_method, hs_slope_shift,
                               hs_bound, ht_min, ht_max, compute_unit,
                               t_len, n, bsz)
    res2 = lambda t, g, s: (0, 0)                       # resident across t
    per_t = lambda t, g, s: (t, 0, 0)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((1, bsz, m), per_t), hbm]
    in_specs += [pl.BlockSpec(w.shape, res2) for w in w_xs]
    in_specs += [pl.BlockSpec(w.shape, res2) for w in w_hs]
    in_specs += [pl.BlockSpec((1, 4 * hdim), res2)] * n
    scratch = [pltpu.VMEM((table.shape[0], bsz, TABLE_LANES), jnp.int32)]
    scratch += [pltpu.VMEM((bsz, hdim), jnp.int32)] * (2 * n)
    scratch += [pltpu.SemaphoreType.DMA(())]
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(t_len,), in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, bsz, hdim), per_t), hbm],
            scratch_shapes=scratch),
        out_shape=[jax.ShapeDtypeStruct((t_len, bsz, hdim), sd),
                   jax.ShapeDtypeStruct(table.shape, jnp.int32)],
        input_output_aliases={3: 1},            # table -> new table
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(gather_slots.reshape(bsz).astype(jnp.int32),
      scatter_slots.reshape(bsz).astype(jnp.int32), x_int,
      table.astype(jnp.int32), *w_xs, *w_hs,
      *(b.reshape(1, -1).astype(jnp.int32) for b in b_wides))
    return outs[0], outs[1]
