"""Median per-window latency in ms (see ``_latency``)."""

from metrics._latency import percentile


def read(rec):
    return percentile(rec, 50)
