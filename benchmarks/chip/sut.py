"""The system under test, built from a configuration file.

``repro.build(model, accelerator, params=...)`` with the benchmark's own
float weights, then ``quantize()``, served by one ``StreamServer`` on the
chip.  This is the only module of the benchmark that imports the program.
"""

from __future__ import annotations

from typing import Dict


def session(cfg: Dict, params):
    """A quantised ``repro.Accelerator`` for ``cfg`` holding ``params``."""
    import repro
    from repro.core.accelerator import AcceleratorConfig
    from repro.core.fixed_point import FixedPointConfig
    from repro.core.qlstm import ActivationConfig, QLSTMConfig

    a = cfg["activations"]
    nf = cfg["number_format"]
    acts = ActivationConfig(gate=a["gate"], cell=a["cell"],
                            hs_slope_shift=a["hs_slope_shift"],
                            hs_bound=a["hs_bound"])
    model = QLSTMConfig(acts=acts, **cfg["model"])
    accel = AcceleratorConfig(
        fxp=FixedPointConfig(nf["frac_bits"], nf["total_bits"]),
        ht_min=a["ht_min"], ht_max=a["ht_max"], **cfg["accelerator"])
    return repro.build(model, accel, params=params).quantize()


def serve(cfg: Dict, params, serving: Dict):
    """A ``StreamServer`` for ``cfg``; ``serving`` holds further
    ``ServingConfig`` fields."""
    from repro.serving import StreamServer
    return StreamServer(session(cfg, params), **{**cfg["serving"], **serving})


def prime(server, window) -> None:
    """Compile and run the wave program once, outside the clock, then
    fresh metrics."""
    server.submit("bench.prime", window)
    server.drain()
    server.end_stream("bench.prime")
    server.reset_metrics()

