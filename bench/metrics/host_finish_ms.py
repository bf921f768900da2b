"""Readback and readout (``AerSessionPool.finish_step``), including its wait
on the device: ms per step."""

from bench.metrics import _spans


def read(run):
    return _spans.ms_per_step(run, "finish_step")
