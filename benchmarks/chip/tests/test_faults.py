"""The correctness check catches a broken timed path.

Each test drives a whole run of a cell at its rehearsal size on the CPU
(``run.run_cell(rehearse=True)`` skips only the look for a chip), with
the server's wave program wrapped so that it breaks one guarantee, and
sees ``correct`` come out false.  The faults that these cells can have:

* a step that returns its state unchanged (the carry table comes back as
  it went in);
* half of the batch left out (every other row of a wave answers 0);
* an answer altered where it is produced (row 0 of every wave, one LSB).

The cells run on one chip, so no exchange between chips can be left out.
A sound run, and the control (the reference at 4-bit weights in the
program's place, held to the same checks), close the file.
"""

import pytest

import run as harness

LSB = 2.0 ** -4          # one code of the (4,8) output


def _split(out):
    return (out[0], out[1:]) if isinstance(out, tuple) else (out, ())


def state_unchanged(fn):
    def wave(x, table, gather, scatter):
        y, _ = fn(x, table, gather, scatter)
        return y, table
    return wave


def half_batch(fn):
    def wave(*args):
        y, rest = _split(fn(*args))
        y = y.at[1::2].set(0.0)
        return (y, *rest) if rest else y
    return wave


def answer_altered(fn):
    def wave(*args):
        y, rest = _split(fn(*args))
        y = y.at[0, 0].add(LSB)
        return (y, *rest) if rest else y
    return wave


def broken(fault):
    def wrap(server):
        server._fns = [[(name, fault(fn) if i == 0 else fn)
                        for i, (name, fn) in enumerate(per_session)]
                       for per_session in server._fns]
    return wrap


def run(workload, **kw):
    return harness.run_cell(workload, seed=2 ** 33 + 7, seconds=1.0,
                            trace=False, rehearse=True, **kw)


@pytest.mark.parametrize("workload, fault, number", [
    ("pems-saturate", state_unchanged, "carry_gap_lsb"),
    ("pems-saturate", half_batch, "max_gap_lsb"),
    ("pems-saturate", answer_altered, "max_gap_lsb"),
])
def test_fault_is_caught(workload, fault, number):
    res = run(workload, wrap_server=broken(fault))
    assert res["checks"][number]["value"] > 0
    assert res["correct"] is False


@pytest.mark.parametrize("workload", ["pems-saturate", "pems-steady"])
def test_sound_run_and_control(workload):
    res = run(workload, control=True)
    assert res["correct"] is True
    assert res["checks"]["max_gap_lsb"]["value"] == 0
    assert res["checks"]["carry_gap_lsb"]["value"] == 0
    assert res["info"]["control"]["correct"] is False
