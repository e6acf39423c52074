"""Kernels: ``expert_matmul_roofline`` as the ``nemotron_h`` family counts
it — the grouped products of 16 held ``relu^2`` experts of two matrices,
1024 x 2688, over the assignments the fixed batch sends them
(``families/nemotron_h.py``: ``kernel["experts"]``) over the device time
under ``moe/experts``.  A name of its own for ``nemotron_moe_ms``'s reason;
the number is read by the same code."""

from .expert_matmul_roofline import read  # noqa: F401
