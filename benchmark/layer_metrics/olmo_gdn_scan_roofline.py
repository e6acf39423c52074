"""Kernels: ``gdn_scan_roofline`` as the ``olmo_hybrid`` family counts it —
the chunk's products and bytes for keys of 96, values of 192 and 30 heads
(``families/olmo_hybrid.py``: ``kernel["gdn_scan"]``) over the device time
under ``gdn/scan``.  A name of its own because a metric has one list of
cells and that entry's may not be edited by the PR that adds a cell; the
number is read by the same code."""

from .gdn_scan_roofline import read  # noqa: F401
