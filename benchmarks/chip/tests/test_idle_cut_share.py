"""The ``idle_cut_share`` reader on hand-made sinks."""

from types import SimpleNamespace

import pytest

from metrics.idle_cut_share import read


def _rec(**sink):
    return SimpleNamespace(sink=sink)


def test_none_with_no_waves():
    assert read(_rec(waves=0, idle_cuts=0)) is None
    assert read(_rec()) is None


@pytest.mark.parametrize("waves, idle_cuts, want", [
    (4, 4, 100.0),
    (8, 6, 75.0),
    (5, 0, 0.0),
])
def test_share_of_waves_cut_for_an_idle_thread(waves, idle_cuts, want):
    assert read(_rec(waves=waves, idle_cuts=idle_cuts,
                     deadline_flushes=waves - idle_cuts)) == \
        pytest.approx(want)


def test_none_without_the_counter():
    """A sink from before the program counted idle cuts."""
    assert read(_rec(waves=3, deadline_flushes=3)) is None
