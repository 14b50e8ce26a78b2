"""Useful model FLOPs of the window over the busy seconds of every chip
of the pod times one chip's peak bf16 FLOP/s (``flops.busy_mfu``)."""

from benchmarks.chip import flops


def read(run):
    return flops.busy_mfu(run)
