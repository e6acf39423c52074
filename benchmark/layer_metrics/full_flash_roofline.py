"""Kernels: the full-attention layers' attention kernels' share of their
roofline as the ``laguna`` family counts it — 48 query heads on 8 key-value
heads of 128 in two layers over 16384 causal positions
(``families/laguna.py``: ``kernel["full_flash"]``) — over the device time
of the flash kernels that the family's ``scopes`` table gives to
``attn/full``: ``window_flash_roofline``'s function with the other scope.
``full_flash_bound`` in the notes says which peak bounds them."""

from .window_flash_roofline import scoped_flash_roofline


def read(ctx):
    return scoped_flash_roofline(ctx, "attn/full", "full_flash")
