"""Kernels: ``expert_matmul_roofline`` as the ``laguna`` family counts it —
the grouped products of 16 held SwiGLU experts of three matrices, 3072 x
1024, over the assignments the fixed batch sends them
(``families/laguna.py``: ``kernel["experts"]``) over the device time under
``moe/experts``.  A name of its own because a share of a roofline ends in
``_roofline`` (``nemotron_expert_matmul_roofline``'s reason); the number is
read by the same code."""

from .expert_matmul_roofline import read  # noqa: F401
