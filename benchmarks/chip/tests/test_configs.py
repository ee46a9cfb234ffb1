"""A configuration enters the benchmark as files, with no edit to the
harness.

Two variants of ``lstm_pems.json`` are written under ``tmp_path``, each with
its own cell and traffic mix, and served through the whole run at the
mix's rehearsal size on the CPU (``run.run_cell(rehearse=True)``):

* stateless (``serving.stateful`` false, the multilayer-kernel path): each
  window is checked against the reference run alone from the zero carry,
  and ``carries_held`` must read 0;
* two stacked layers of 32 units over 128 x 9 inputs with 6 outputs,
  stateful: its carry rows are the reference's 128 codes.

The faults of ``test_faults.py`` that each can have make it not correct.
A stub server whose carry is one array a layer shows that the read-back
takes its layout from the reference alone.
"""

import json
import os
import types

import numpy as np
import pytest

import references
import run as harness
from test_faults import answer_altered, broken, half_batch, state_unchanged

SEED = 2 ** 33 + 11
BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CONFIGS = os.path.join(harness.HERE, "configs")
TRAFFIC = os.path.join(harness.HERE, "traffic")

STATELESS = {"serving": {"stateful": False}}
TWO_LAYERS = {"model": {"input_size": 9, "hidden_size": 32, "num_layers": 2,
                        "out_features": 6, "seq_len": 128, "cell": "lstm"}}
# 128-step windows through the interpreted kernel: few enough that the CPU
# keeps up, with every stream sending four windows in the run.
TWO_LAYERS_MIX = {"rehearsal": {"streams": 16, "rate_per_s": 32,
                                "warmup_s": 1.0, "pool_windows": 64,
                                "serving": {"max_streams": 16}}}


@pytest.fixture
def variant(tmp_path, monkeypatch):
    """Writes ``lstm_pems.json`` with ``change`` and ``pems_steady.json``
    with ``mix_change`` as a new configuration, mix and cell ``name``, and
    points the harness's ``cell_spec`` at them."""
    def make(name, change, mix_change=None):
        cfg = {**harness.load_json(os.path.join(CONFIGS, "lstm_pems.json")),
               **change, "name": name}
        mix = {**harness.load_json(os.path.join(TRAFFIC, "pems_steady.json")),
               **(mix_change or {})}
        (tmp_path / f"{name}.json").write_text(json.dumps(cfg))
        (tmp_path / f"{name}_mix.json").write_text(json.dumps(mix))
        cell = {"name": name, "config": name, "traffic": f"{name}_mix",
                "chips": 1}

        def cell_spec(workload):
            assert workload == name
            cfg = harness.load_json(str(tmp_path / f"{name}.json"))
            mix = harness.load_json(str(tmp_path / f"{name}_mix.json"))
            return BENCH, cell, cfg, mix, references.load(cfg["reference"])

        monkeypatch.setattr(harness, "cell_spec", cell_spec)
        return name
    return make


def run(workload, **kw):
    return harness.run_cell(workload, seed=SEED, seconds=1.0, trace=False,
                            rehearse=True, **kw)


def test_stateless_sound_run_and_control(variant):
    name = variant("lstm_pems_stateless", STATELESS)
    served = harness.serve_cell(name, SEED, 1.0, False, rehearse=True)
    assert served["rec"].sink["faults"]["backend"] == "pallas"
    res = harness.result_of(served, control=True)
    assert res["correct"] is True
    assert res["checks"]["max_gap_lsb"]["value"] == 0
    assert res["checks"]["carries_held"]["value"] == 0
    assert "carry_gap_lsb" not in res["checks"]
    assert res["info"]["serving"]["stateful"] is False
    assert res["info"]["control"]["correct"] is False


@pytest.mark.parametrize("fault", [half_batch, answer_altered])
def test_stateless_fault_is_caught(variant, fault):
    res = run(variant("lstm_pems_stateless", STATELESS),
              wrap_server=broken(fault))
    assert res["checks"]["max_gap_lsb"]["value"] > 0
    assert res["correct"] is False


def test_two_layers_sound_run(variant):
    name = variant("lstm_2x32", TWO_LAYERS, TWO_LAYERS_MIX)
    served = harness.serve_cell(name, SEED, 1.0, False, rehearse=True)
    rec = served["rec"]
    assert rec.carry_read.shape == (len(rec.carry_pos), 2 * 2 * 32)
    assert rec.carry_held.all()
    res = harness.result_of(served)
    assert res["correct"] is True
    assert res["checks"]["carry_gap_lsb"]["value"] == 0


def test_two_layers_state_unchanged_is_caught(variant):
    res = run(variant("lstm_2x32", TWO_LAYERS, TWO_LAYERS_MIX),
              wrap_server=broken(state_unchanged))
    assert res["checks"]["carry_gap_lsb"]["value"] > 0
    assert res["correct"] is False


def test_read_carries_takes_the_layout_from_the_reference():
    """A GRU-like carry: one array of H codes a layer, no (h, c) pair."""
    layers, hid, streams = 2, 3, 8
    ref = types.SimpleNamespace(
        carry_codes=lambda cfg: layers * hid,
        carry_vector=lambda cfg, st: np.concatenate(
            [a for layer in st for a in layer]).astype(np.int64))

    def state(sid):
        return [(np.full(hid, 10 * sid + li, np.int32),)
                for li in range(layers)]

    server = types.SimpleNamespace(
        read_stream_state=lambda sid: None if sid % 2 else state(sid))
    traffic = types.SimpleNamespace(rate=10.0, streams=streams,
                                    order=np.arange(streams)[::-1])
    rec = harness.Record(traffic, 1.0, 0.0, 1)
    rec.n_sub = 2 * streams
    rec.status[:rec.n_sub] = 1
    harness.read_carries(server, rec, SEED, {}, ref)
    assert rec.carry_read.shape == (streams, layers * hid)
    for pos, row, held in zip(rec.carry_pos, rec.carry_read, rec.carry_held):
        sid = int(traffic.order[pos])
        assert held == (sid % 2 == 0)
        want = (ref.carry_vector({}, state(sid)) if held
                else np.full(layers * hid, harness.MISSING))
        assert row.tolist() == want.tolist()
    assert not rec.errors
