"""Data pipeline determinism + HLO collective parser + energy model."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import collective_bytes
from repro.core import energy
from repro.data.lm_data import SyntheticLM
from repro.data.pipeline import Pipeline
from repro.data.timeseries import make_windows, pems_like_dataset


def test_pems_windows_shapes_and_range():
    d = pems_like_dataset(seq_len=6)
    x, y = d["train"]
    assert x.shape[1:] == (6, 1) and y.shape[1:] == (1,)
    assert 0.0 <= x.min() and x.max() <= 1.0
    # windows are shifted views of the same series
    np.testing.assert_allclose(x[1, :-1, 0], x[0, 1:, 0])


def test_lm_data_step_keyed_determinism():
    src = SyntheticLM(1000, seed=5)
    a = src.batch(3, 4, 8)
    b = src.batch(3, 4, 8)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = src.batch(4, 4, 8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_pipeline_prefetch_order():
    seen = []
    src = SyntheticLM(100, seed=1)

    def source(step):
        seen.append(step)
        return src.batch(step, 2, 4)

    p = Pipeline(source, start_step=10, prefetch=2)
    b0 = next(p)
    b1 = next(p)
    p.close()
    exp0 = src.batch(10, 2, 4)
    np.testing.assert_array_equal(np.asarray(b0["tokens"]), exp0["tokens"])
    assert seen[:2] == [10, 11]


HLO = """
  %ag = bf16[64,512]{1,0} all-gather(bf16[4,512]{1,0} %p), replica_groups={}
  %ar.1 = f32[128]{0} all-reduce(f32[128]{0} %x), to_apply=%add
  %ars = (f32[256]{0}, f32[256]{0}) all-reduce-start(f32[256]{0} %y)
  %ard = f32[256]{0} all-reduce-done(%ars)
  %rs = s8[32,16]{1,0} reduce-scatter(s8[512,16]{1,0} %z), dimensions={0}
  %cp = bf16[8,8]{1,0} collective-permute(bf16[8,8]{1,0} %w)
"""


def test_collective_parser():
    out = collective_bytes(HLO)
    assert out["all-gather"] == 64 * 512 * 2
    # plain all-reduce + async start (tuple halved => one payload)
    assert out["all-reduce"] == 128 * 4 + 256 * 4
    assert out["reduce-scatter"] == 32 * 16
    assert out["collective-permute"] == 8 * 8 * 2
    assert out["count"] == 4 + 1  # -done excluded
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "collective-permute"))


def test_roofline_terms_and_bound():
    t = energy.roofline_terms(flops=197e12, hbm_bytes=0, collective_bytes=0)
    assert t.compute_s == pytest.approx(1.0)
    assert t.bound == "compute"
    t2 = energy.roofline_terms(flops=0, hbm_bytes=819e9, collective_bytes=0)
    assert t2.memory_s == pytest.approx(1.0)
    assert t2.bound == "memory"


def test_energy_model_refuses_unmodelled_tpus():
    """The constants describe a v5e: another TPU raises instead of being
    reported with v5e numbers; the CPU keeps the v5e projection, and so
    does ``Accelerator.report()`` here."""
    import types

    import repro
    dev = lambda platform, kind: types.SimpleNamespace(platform=platform,
                                                       device_kind=kind)
    energy.check_modelled_device(dev("tpu", "TPU v5 lite"))
    energy.check_modelled_device(dev("cpu", "cpu"))
    with pytest.raises(RuntimeError, match="TPU v4"):
        energy.check_modelled_device(dev("tpu", "TPU v4"))
    assert repro.build().report()["energy"]["total_w"] > 0


def test_power_report_static_dynamic_split():
    rep = energy.power_report(flops=1e12, hbm_bytes=1e9, ici_bytes=0,
                              latency_s=0.01, dtype="int8")
    assert rep["static_w"] == energy.P_STATIC_W
    assert rep["total_w"] > rep["static_w"]
    assert rep["gops_per_watt"] > 0
    # int8 ops burn less than bf16 flops (C1's energy argument)
    rep_bf16 = energy.power_report(flops=1e12, hbm_bytes=1e9, ici_bytes=0,
                                   latency_s=0.01, dtype="bf16")
    assert rep["dynamic_w"] < rep_bf16["dynamic_w"]


def test_model_flops():
    assert energy.model_flops_train(1e9, 1e6) == 6e15
    assert energy.model_flops_decode(1e9, 128) == pytest.approx(2.56e11)
