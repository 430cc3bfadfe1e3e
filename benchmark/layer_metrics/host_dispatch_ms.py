"""Median of the benchmark's own span around the step call until it
returns (the host's share of one step; the device runs meanwhile)."""
import statistics


def read(ctx):
    spans = ctx["spans"].get("dispatch")
    return statistics.median(spans) if spans else None
