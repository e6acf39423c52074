"""Kernels: the main layers' latent-attention kernels' share of their
roofline as the ``joyai`` family counts it — 32 heads with keys of 192 and
values of 128 in five layers over 16384 causal positions
(``families/joyai.py``: ``kernel["latent_flash"]``; per pair and head 2 x
(192 + 128) operations forward and twice that backward, recomputation not
counted; q and k of 192, v and o of 128 read or written once forward, q,
k, v, o, do read and dq, dk, dv written once backward; the larger of
operations over the bf16 peak and bytes over the HBM peak) — over the
device time of the flash kernels that the family's ``scopes`` table gives
to ``attn/latent``: ``window_flash_roofline``'s function with another
scope.  The module's sixth call lies under ``mtp`` and is in neither side.
``latent_flash_bound`` in the notes says which peak bounds them."""

from .window_flash_roofline import scoped_flash_roofline


def read(ctx):
    return scoped_flash_roofline(ctx, "attn/latent", "latent_flash")
