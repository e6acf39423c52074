"""Kernels: the attention kernels' share of their roofline as the ``jamba``
family counts it — 20 query heads on one key-value head of 128 over 8192
causal positions (``families/jamba.py``: ``kernel["flops_per_step"]``,
``kernel["bytes_per_step"]``) — over the device time of the flash kernels
alone: the convolution's and the selective scan's kernels are
``tpu_custom_call`` events too, so the attention kernels are found by the
``flash_`` in their instructions' names, as ``nemotron_flash_roofline``
finds them.  The number is read by the same code."""

from .nemotron_flash_roofline import read  # noqa: F401
