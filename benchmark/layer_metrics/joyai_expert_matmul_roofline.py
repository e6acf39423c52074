"""Kernels: ``expert_matmul_roofline`` as the ``joyai`` family counts it —
the grouped products of 32 held SwiGLU experts of three matrices, 2048 x
768, in five expert layers (the prediction module's among them) over the
assignments the fixed batch sends them (``families/joyai.py``:
``kernel["experts"]``) over the device time under ``moe/experts``.  A name
of its own because a share of a roofline ends in ``_roofline``
(``nemotron_expert_matmul_roofline``'s reason); the number is read by the
same code."""

from .expert_matmul_roofline import read  # noqa: F401
