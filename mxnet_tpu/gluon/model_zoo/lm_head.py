"""The untied head the decoders of this zoo share, and their loss through it
taken over stretches of the sequence (``chunked_token_loss``)."""
from __future__ import annotations

from ..block import HybridBlock
from ..nn.decoder import _linear

__all__ = ["chunked_token_loss"]


class _LMHead(HybridBlock):
    """The untied head; with labels the summed cross-entropy of its logits,
    and with a ``mask`` (a weight a row: 1 counted, 0 left out) the weighted
    sum."""

    def __init__(self, vocab, units, dtype, weight_initializer, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.proj = _linear(vocab, units, dtype, weight_initializer, "head_")

    def hybrid_forward(self, F, x, labels=None, mask=None):
        if labels is None:
            return self.proj(x)
        # a 2-D product, as F.softmax_cross_entropy wants its producer
        logits = self.proj(F.reshape(x, shape=(-1, self._units)))
        labels = F.reshape(labels, shape=(-1,))
        if mask is None:
            return F.softmax_cross_entropy(logits, labels)
        return F.softmax_cross_entropy(logits, labels, F.reshape(mask, shape=(-1,)))


def chunked_token_loss(F, head, x, labels, chunks, *more):
    """The summed loss of ``head(x, labels, *more)`` (``more``: other per-token
    arrays, a mask) over ``chunks`` stretches of the sequence one after the
    other (with the head marked for remat one stretch's logits are live)."""
    seq = x.shape[1]
    if seq % chunks:
        raise ValueError(f"loss_chunks {chunks} does not divide the length {seq}")
    loss = None
    for i in range(chunks):
        lo, hi = i * seq // chunks, (i + 1) * seq // chunks
        part = head(*(F.slice_axis(a, axis=1, begin=lo, end=hi)
                      for a in (x, labels) + more))
        loss = part if loss is None else loss + part
    return loss
