"""Kernels: ``flash_roofline`` as the ``olmo_hybrid`` family counts it —
the attention kernels at 30 heads of 128 with keys of their own over 16384
causal positions (``families/olmo_hybrid.py``: ``kernel["flops_per_step"]``,
``kernel["bytes_per_step"]``) over the summed device time of the step's
custom-call events.  A name of its own for ``olmo_gdn_scan_roofline``'s
reason; the number is read by the same code."""

from .flash_roofline import read  # noqa: F401
