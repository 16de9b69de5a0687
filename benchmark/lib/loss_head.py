"""The loss head's kernels (``ops/pallas_xent``, since PR 29) by name, and
the least work they hold. Kept here, not in ``layer_common`` / ``roofline``:
a PR adds to the benchmark by new files alone."""

from __future__ import annotations

# the Mosaic calls the program names fused_xent_fwd and fused_xent_bwd; by
# name alone, as the other kernels' patterns
XENT_KERNELS = r"fused_xent"

# products of the tied head the two kernels hold between them, each
# 2 x rows x d x vocab: the logits (forward), and BOTH gradients (dh and
# dwte form inside fused_xent_bwd; neither is left to XLA). The backward's
# recomputed logits are the program's choice, not work the loss needs: not
# counted, as `flash_roofline` leaves the remat's second forward out.
PRODUCTS = 3


def head_flops(seqs: int, block: int, d: int, vocab: int) -> int:
    """Least FLOPs of the tied head and its two gradients for ``seqs``
    sequences of ``block`` tokens: a sequence has ``block - 1`` labelled
    positions (the kernels run all ``block``; the last weighs nothing)."""
    return PRODUCTS * 2 * seqs * (block - 1) * d * vocab
