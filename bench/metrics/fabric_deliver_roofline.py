"""Roofline share of the ``fabric_deliver`` time-wheel kernel."""

from bench.metrics import _roofline


def read(run):
    return _roofline.share(run, "fabric_deliver")
