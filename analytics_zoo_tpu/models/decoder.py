"""The causal decoders' block and stack (``Qwen3Next``, ``AFMoE``,
``GraniteHybrid``, ``GlmMoeLite``, ``Ouro``).

A block is a token mixer and a feed-forward, each behind a norm and on the
residual stream; what differs between the families is which norms surround
them, the norm's kind and a residual multiplier.  A model builds its blocks
once, as a table of mixers and feed-forwards, and :func:`stack` wraps each
for recomputation.  What a block keeps across its recomputation is what its
mixer's kernels tag for their own backward (``Module.remat_keep``), so a
kernel's author changes it in the mixer, not in the models.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax

import analytics_zoo_tpu.nn as nn
from analytics_zoo_tpu.nn.module import Module, Scope


class DecoderBlock(Module):
    """``x += m * post(mixer(pre(x))); x += m * post(ff(pre(x)))``.

    ``norms`` names the norms: ``(pre_mixer, pre_ff)``, the pre-norm block,
    or ``(pre_mixer, post_mixer, pre_ff, post_ff)``, the sandwich, each of
    them the module ``norm`` under that name.  ``mixer`` and ``ff`` are
    children under their own ``name``.  ``residual_multiplier`` scales both
    sublayers' outputs where a family publishes one; None leaves them as
    they are."""

    def __init__(self, mixer: Module, ff: Module, norms: Sequence[str],
                 norm: Module, residual_multiplier: Optional[float] = None,
                 name: Optional[str] = None):
        super().__init__(name)
        if len(norms) not in (2, 4):
            raise ValueError("norms names (pre_mixer, pre_ff) or (pre_mixer, "
                             f"post_mixer, pre_ff, post_ff); got {norms}")
        self.mixer, self.ff, self.norm = mixer, ff, norm
        # (pre, post) of the mixer, then of the feed-forward
        self.norms = tuple(norms) if len(norms) == 4 else (
            norms[0], None, norms[1], None)
        self.residual_multiplier = residual_multiplier

    @property
    def remat_keep(self) -> Tuple[str, ...]:
        return self.mixer.remat_keep + self.ff.remat_keep

    def forward(self, scope: Scope, x: jax.Array) -> jax.Array:
        for sub, pre, post in ((self.mixer, *self.norms[:2]),
                               (self.ff, *self.norms[2:])):
            h = scope.child(sub, scope.child(self.norm, x, name=pre),
                            name=sub.name)
            if post is not None:
                h = scope.child(self.norm, h, name=post)
            if self.residual_multiplier is not None:
                h = h * self.residual_multiplier
            x = x + h
        return x


def stack(blocks: Sequence[DecoderBlock], remat: bool,
          keep: Optional[Sequence[str]] = None) -> List[Module]:
    """The blocks as a model's ``forward`` runs them, each as the child of
    its own ``name``: under ``remat`` each block is recomputed in the
    backward pass (``nn.Remat``, ``remat_<i>``) and keeps ``keep`` if given,
    else its own ``remat_keep``; otherwise the blocks as they are."""
    if not remat:
        return list(blocks)
    return [nn.Remat(b, name=f"remat_{i}",
                     save_names=b.remat_keep if keep is None else keep)
            for i, b in enumerate(blocks)]
