"""Roofline share of the ``fused_deliver`` kernel (stage 1 and stage 2 fused)."""

from bench.metrics import _roofline


def read(run):
    return _roofline.share(run, "fused_deliver")
