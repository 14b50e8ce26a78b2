"""Useful model FLOPs of the window over device busy seconds times the
chip's peak bf16 FLOP/s (``flops.busy_mfu``)."""

from benchmarks.chip import flops


def read(run):
    return flops.busy_mfu(run)
