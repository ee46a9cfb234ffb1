"""Self-check of the yardstick: the operation and byte counts, the peak
table, the reference contract, the plain reference against a scalar loop,
and the trace reduction on a small recorded trace and on a hand-made
one."""

import json
import os
import sys
import types

import numpy as np
import pytest

import references
import run as harness
import trace_reduce
import work
from references import qlstm

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("model, ops", [
    # 2*(1+20)*80*6 + 2*20*1
    ({}, 20_200),
    # a 2 x 32 stack over 128 x 9 with 6 outputs:
    # (2*(9+32)*128 + 2*(32+32)*128)*128 + 2*32*6
    ({"input_size": 9, "hidden_size": 32, "num_layers": 2,
      "out_features": 6, "seq_len": 128}, 3_441_024),
])
def test_ops_per_window(model, ops):
    cfg = config("lstm_pems")
    assert qlstm.ops_per_window({**cfg, "model": {**cfg["model"],
                                                  **model}}) == ops


def test_bytes_per_wave_pems():
    cfg = config("lstm_pems")
    x = 256 * 6 * 1 * 4                      # float32 inputs
    w = (1 + 20) * 80 + 80 * 2 + 20 * 1 + 1 * 2   # int8 weights, int16 biases
    carry = 2 * 256 * 1 * 2 * 20              # h and c, read and written
    y = 256 * 1 * 4
    assert qlstm.weight_bytes(cfg) == w == 1_862
    assert work.bytes_per_wave(qlstm, cfg, 256) == x + w + carry + y == 29_510


def test_reference_without_carry_codes_fails_at_cell_spec(tmp_path,
                                                          monkeypatch):
    broken = types.ModuleType("references.nocarry")
    for name in references.REQUIRED:
        if name != "carry_codes":
            setattr(broken, name, getattr(qlstm, name))
    monkeypatch.setitem(sys.modules, "references.nocarry", broken)
    cfg = {**config("lstm_pems"), "name": "nocarry", "reference": "nocarry"}
    (tmp_path / "nocarry.json").write_text(json.dumps(cfg))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "mix.json").write_text("{}")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "nocarry", "file": "nocarry.json"}],
        "workloads": [{"name": "cell", "config": "nocarry",
                       "traffic": "mix", "chips": 1}]}))
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    with pytest.raises(AttributeError, match="carry_codes"):
        harness.cell_spec("cell")


def test_peaks_table():
    pk = work.peaks("TPU v5 lite")
    assert pk["int8_ops_per_s"] == 393e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")


def _scalar_reference(cfg, codes, x):
    """One window of the configuration's datapath in plain Python ints."""
    frac, bits = (cfg["number_format"]["frac_bits"],
                  cfg["number_format"]["total_bits"])
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    one, half = 1 << frac, 1 << (frac - 1)
    a = cfg["activations"]
    bound = int(round(a["hs_bound"] * one))

    def rnd(v):
        return min(hi, max(lo, (v + half) >> frac))

    def hsig(v):
        if v < -bound:
            return 0
        if v >= bound:
            return one
        return min(one, max(0, (v >> a["hs_slope_shift"]) + half))

    def htanh(v):
        return min(int(a["ht_max"] * one), max(int(a["ht_min"] * one), v))

    hid = cfg["model"]["hidden_size"]
    seq = [[min(hi, max(lo, int(np.floor(v * one + 0.5)))) for v in row]
           for row in x]
    for p in codes["layers"]:
        wx, wh, b = (np.asarray(p[k]).tolist() for k in ("w_x", "w_h", "b"))
        h, c = [0] * hid, [0] * hid
        out = []
        for xt in seq:
            pre = [rnd(sum(xt[i] * wx[i][j] for i in range(len(xt)))
                       + sum(h[i] * wh[i][j] for i in range(hid)) + b[j])
                   for j in range(4 * hid)]
            gi = [hsig(v) for v in pre[:hid]]
            gf = [hsig(v) for v in pre[hid:2 * hid]]
            gg = [htanh(v) for v in pre[2 * hid:3 * hid]]
            go = [hsig(v) for v in pre[3 * hid:]]
            c = [rnd(gf[j] * c[j] + gi[j] * gg[j]) for j in range(hid)]
            h = [rnd(go[j] * htanh(c[j])) for j in range(hid)]
            out.append(h)
        seq = out
    wd = np.asarray(codes["dense"]["w"]).tolist()
    bd = np.asarray(codes["dense"]["b"]).tolist()
    return [rnd(sum(h[i] * wd[i][j] for i in range(hid)) + bd[j])
            for j in range(len(bd))]


@pytest.mark.parametrize("model", [
    {}, {"input_size": 3, "hidden_size": 8, "num_layers": 2,
         "out_features": 2, "seq_len": 5}])
def test_reference_matches_scalar_loop(model):
    import jax
    import jax.numpy as jnp

    import loadgen
    from references import qlstm as ref

    cfg = config("lstm_pems")
    cfg = {**cfg, "name": f"lstm_pems{sorted(model.items())}",
           "model": {**cfg["model"], **model}}
    m = cfg["model"]
    params = ref.make_params(cfg, jax.random.key(3))
    codes = ref.weight_codes(cfg, params)
    x = loadgen.make_pool(np.random.default_rng(4), 3, m["seq_len"],
                          m["input_size"], cfg["input"])
    y, _ = ref.window_fn(cfg)(codes, jnp.asarray(x), ref.zero_carry(cfg, 3))
    for i in range(3):
        assert np.asarray(y)[i].tolist() == _scalar_reference(cfg, codes,
                                                              x[i])


def test_control_differs_from_reference():
    import jax
    import jax.numpy as jnp

    import loadgen
    from references import qlstm as ref

    cfg = config("lstm_pems")
    params = ref.make_params(cfg, jax.random.key(5))
    x = jnp.asarray(loadgen.make_pool(np.random.default_rng(6), 256, 6, 1,
                                      cfg["input"]))
    fn = ref.window_fn(cfg)
    y, _ = fn(ref.weight_codes(cfg, params), x, ref.zero_carry(cfg, 256))
    yc, _ = fn(ref.control_codes(cfg, params), x, ref.zero_carry(cfg, 256))
    assert np.abs(np.asarray(yc) - np.asarray(y)).max() > 0


def _brute_busy(ops, t0, t1):
    """Busy nanoseconds by marking a 1-ns timeline."""
    line = np.zeros(int(t1 - t0), bool)
    for _, s, d in ops:
        a, b = int(max(s, t0) - t0), int(min(s + d, t1) - t0)
        if b > a:
            line[a:b] = True
    return int(line.sum())


def test_reduce_recorded_trace():
    with open(os.path.join(HERE, "tests", "trace_small.json")) as f:
        trace = json.load(f)
    r = trace_reduce.reduce(trace)
    (dev, lines), = trace["devices"].items()
    assert r["window_s"] == pytest.approx(0.04)
    busy_ns = _brute_busy([(n, round(s), round(d)) for n, s, d
                           in lines["ops"]], 0, 40_000_000)
    assert r["busy_s"] == pytest.approx(busy_ns * 1e-9, abs=2e-8)
    # Four runs of the slot-path wave program in the 40 ms.
    assert r["modules"]["jit_slot_path"]["runs"] == 4
    assert r["modules"]["jit_slot_path"]["seconds"] == pytest.approx(
        sum(d for _, _, d in lines["modules"]) * 1e-9)
    assert r["device_ops"][0][0] == "copy.9 (copy)"
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_reduce_hand_made_trace():
    trace = {"devices": {"/device:TPU:0": {
        "ops": [["%a = s32[] add(x)", 0, 10], ["%b = s32[] copy(y)", 5, 15],
                ["%a = s32[] add(x)", 30, 10]],
        "modules": [["jit_f(1)", 0, 20], ["jit_f(1)", 30, 10]]}},
        "host": [["bench.traced", 0, 50], ["shard_args", 20, 5],
                 ["bench.submit", 40, 10]]}
    r = trace_reduce.reduce(trace)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(30e-9)          # [0,20] + [30,40]
    assert r["modules"]["jit_f"]["runs"] == 2
    assert r["modules"]["jit_f"]["seconds"] == pytest.approx(30e-9)
    assert r["device_ops"][0] == ["a (add)", pytest.approx(20e-9)]
    gaps = dict(r["idle_gaps"])
    assert gaps["shard_args | none"] == pytest.approx(10e-9)     # [20, 30]
    assert gaps["python | bench.submit"] == pytest.approx(10e-9)  # [40, 50]
