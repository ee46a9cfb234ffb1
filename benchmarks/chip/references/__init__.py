"""Plain references, one module per architecture, and the contract the
harness holds them to.

A configuration file names its reference (``"reference": "<name>"``), and
``references/<name>.py`` is the one place in the benchmark that knows the
architecture: its weights, its arithmetic, its work and the layout of its
per-stream carry.  The harness (``run.py``), ``work.py`` and the metric
readers ask it and assume no shape.  A reference imports nothing of the
system under test.  It exports:

``make_params(cfg, key)``
    Float32 master weights on the device, in one jitted call from ``key``;
    the same weights are handed to the system under test.
``weight_codes(cfg, params)``, ``control_codes(cfg, params)``
    The integer codes the reference runs: the configured ones, and the
    control's (the configured precision's step down).
``output_codes(cfg, y)``
    A served float output -> its integer codes, ``np.int64``.
``run_chains(cfg, codes, x_of, ids, n_windows, block, keep=())``
    Stream ``ids[r]`` runs its windows ``0 .. n_windows[r] - 1`` in order
    from the zero carry; ``x_of(streams, k)`` gives window ``k`` of each
    listed stream as float32 ``(len, T, M)``.  Returns the output codes
    ``(len(ids), max(n_windows), P)`` and the carries after the last window
    of the streams at positions ``keep`` as ``(len(keep), carry_codes(cfg))``
    ``np.int64`` rows, in ``carry_vector``'s order.  A chain of one window
    is the reference of a stateless server.
``ops_per_window(cfg)``, ``weight_bytes(cfg)``
    What the algorithm needs, whatever implements it: the operations of
    one window (2 per multiply-accumulate) and the bytes of its weights and
    biases at their code storage width (``work.storage_bytes``).
``carry_codes(cfg)``
    How many integer codes one stream's carry holds, over all layers.  A
    stateful wave reads and writes that many codes a row.
``carry_vector(cfg, state)``
    What ``StreamServer.read_stream_state`` returns for one stream (per
    layer, a tuple of state arrays) -> one 1-D ``np.int64`` row of
    ``carry_codes(cfg)`` codes.

:func:`load` imports a reference and refuses one that lacks any of these,
naming what is missing, before anything is built.
"""

from __future__ import annotations

import importlib

REQUIRED = ("make_params", "weight_codes", "control_codes", "output_codes",
            "run_chains", "ops_per_window", "weight_bytes", "carry_codes",
            "carry_vector")


def load(name: str):
    """The reference module ``references/<name>.py``, checked against the
    contract."""
    ref = importlib.import_module(f"{__name__}.{name}")
    missing = [f for f in REQUIRED if not callable(getattr(ref, f, None))]
    if missing:
        raise AttributeError(
            f"reference {name!r} lacks {', '.join(missing)} (the contract "
            f"is in references/__init__.py)")
    return ref
