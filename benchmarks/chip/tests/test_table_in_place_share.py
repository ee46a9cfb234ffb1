"""The ``table_in_place_share`` reader on hand-made sinks."""

from types import SimpleNamespace

import pytest

from metrics.table_in_place_share import read


def _rec(state_transfer):
    return SimpleNamespace(sink={"waves": 3,
                                 "state_transfer": state_transfer})


@pytest.mark.parametrize("counts, want", [
    ({"table_in_place": 3, "table_copied": 1}, 75.0),
    ({"table_in_place": 4, "table_copied": 0}, 100.0),
    ({"table_in_place": 0, "table_copied": 2}, 0.0),
])
def test_share_of_waves_in_place(counts, want):
    assert read(_rec({"slot_id_bytes": 8, **counts})) == pytest.approx(want)


def test_none_without_either_counter():
    """A program that keeps neither counter (before they existed)."""
    assert read(_rec({"slot_id_bytes": 8})) is None
    assert read(SimpleNamespace(sink={"waves": 0})) is None


def test_none_with_zero_waves():
    assert read(_rec({"table_in_place": 0, "table_copied": 0})) is None
