"""BERT model family (the BASELINE config #3 flagship).

The reference-era BERT lives in GluonNLP (external repo, composed from
batch_dot+softmax primitive ops — SURVEY §6); here it is a first-class
model-zoo member built on the fused TransformerEncoder
(gluon/nn/transformer.py → Pallas flash attention + fused LayerNorm).

API mirrors GluonNLP's BERTModel: ``model(inputs, token_types)`` →
(sequence_output, pooled_output); MLM/NSP heads are separate blocks so
pretraining and fine-tuning share the trunk.
"""
from __future__ import annotations

from ... import initializer as init
from ..block import HybridBlock
from ..nn import Dense, Dropout, Embedding, LayerNorm, TransformerEncoder
from ..nn.basic_layers import Activation

__all__ = ["BERTModel", "BERTMLMHead", "BERTNSPHead", "bert_base", "bert_large",
           "get_bert", "bert_serving_entry"]


class BERTEmbeddings(HybridBlock):
    """token + position + segment embeddings, LN, dropout."""

    def __init__(self, vocab_size, units, max_length, token_types=2,
                 dropout=0.1, layer_norm_eps=1e-12, dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._max_length = max_length
        with self.name_scope():
            self.word_embed = Embedding(vocab_size, units, dtype=dtype,
                                        prefix="word_")
            self.token_type_embed = Embedding(token_types, units, dtype=dtype,
                                              prefix="type_")
            self.position_embed = Embedding(max_length, units, dtype=dtype,
                                            prefix="pos_")
            self.ln = LayerNorm(epsilon=layer_norm_eps, prefix="ln_")
            self.dropout = Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, inputs, token_types, positions=None):
        # positions 0..S-1 derived from the input itself (jit-static)
        # unless the caller supplies explicit per-token positions — the
        # packed path does: each packed sequence's positions restart at
        # 0 (io/packing.py), not at its row offset. Embedding's take()
        # clips out-of-range ids, which would silently alias every
        # position past max_length — reject instead.
        try:
            seq_len = inputs.shape[1]
        except Exception:
            seq_len = None
        if positions is None and seq_len is not None \
                and seq_len > self._max_length:
            raise ValueError(
                f"sequence length {seq_len} exceeds max_length "
                f"{self._max_length} of the position table")
        x = self.word_embed(inputs) + self.token_type_embed(token_types)
        if positions is None:
            pos = F.arange_like(inputs, axis=1)
            x = x + F.expand_dims(self.position_embed(pos), 0)
        else:
            # caller contract: every position id < max_length (packers
            # bound ids by each SAMPLE's length, so keep packed sample
            # lengths <= max_length even when rows are longer).
            # Concrete (eager) positions are validated here; traced
            # values cannot be (take() would clip silently — the same
            # aliasing the seq_len guard above rejects).
            try:
                pmax = int(positions.asnumpy().max())
            except Exception:
                pmax = None
            if pmax is not None and pmax >= self._max_length:
                raise ValueError(
                    f"position id {pmax} exceeds the position table "
                    f"(max_length {self._max_length}); packed samples "
                    "must each be at most max_length tokens")
            x = x + self.position_embed(positions)
        x = self.ln(x)
        if self.dropout is not None:
            x = self.dropout(x)
        return x


class BERTModel(HybridBlock):
    """Trunk: embeddings → TransformerEncoder → (seq_out, pooled_out)."""

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 token_types=2, dropout=0.1, attention_dropout=0.1,
                 layer_norm_eps=1e-12, use_pooler=True, dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.units = units
        self.vocab_size = vocab_size
        with self.name_scope():
            self.embeddings = BERTEmbeddings(
                vocab_size, units, max_length, token_types=token_types,
                dropout=dropout, layer_norm_eps=layer_norm_eps, dtype=dtype,
                prefix="embed_")
            self.encoder = TransformerEncoder(
                num_layers, units, hidden_size, num_heads, dropout=dropout,
                attention_dropout=attention_dropout, activation="gelu",
                pre_norm=False, layer_norm_eps=layer_norm_eps, dtype=dtype,
                prefix="enc_")
            self.pooler = (Dense(units, flatten=False, activation="tanh",
                                 dtype=dtype, prefix="pooler_")
                           if use_pooler else None)

    def hybrid_forward(self, F, inputs, token_types, valid_length=None,
                       mask=None, segment_ids=None, positions=None):
        """``valid_length`` (B,) per-example token counts — third
        positional input, matching the GluonNLP BERTModel signature
        (inputs, token_types, valid_length); rides the flash kernel's
        native per-row kv-length path. ``mask`` stays the general
        additive escape hatch (composed attention).

        Packed batches (io/packing.py) pass ``segment_ids`` (B, S) —
        attention goes block-diagonal per packed sequence — and
        ``positions`` (B, S), the per-segment position ids (each
        sequence's positional embedding restarts at 0). With packing
        the pooled output is meaningless (row slot 0 is only the FIRST
        packed sequence's [CLS]); slice per-segment outputs with the
        packer's placements instead."""
        x = self.embeddings(inputs, token_types, positions)
        seq = self.encoder(x, mask, valid_length, segment_ids)
        if self.pooler is None:
            return seq
        pooled = self.pooler(F.slice_axis(seq, axis=1, begin=0, end=1)
                             .reshape((0, -1)))
        return seq, pooled


class BERTMLMHead(HybridBlock):
    """transform (dense+gelu+LN) then decode to vocab logits."""

    def __init__(self, vocab_size, units, layer_norm_eps=1e-12,
                 dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        with self.name_scope():
            self.transform = Dense(units, flatten=False, dtype=dtype,
                                   prefix="transform_")
            self.act = Activation("gelu")
            self.ln = LayerNorm(epsilon=layer_norm_eps, prefix="ln_")
            self.decoder = Dense(vocab_size, flatten=False, dtype=dtype,
                                 prefix="decoder_")

    def hybrid_forward(self, F, seq):
        # the vocabulary projection is a 2-D product: behind one XLA
        # writes the logits in the layout F.softmax_cross_entropy reads
        # (a caller's reshape to 2-D folds with the one back to 3-D)
        h = self.ln(self.act(self.transform(seq)))
        logits = self.decoder(F.reshape(h, shape=(-1, self._units)))
        return F.reshape_like(logits, seq, lhs_begin=0, lhs_end=1,
                              rhs_begin=0, rhs_end=-1)


class BERTNSPHead(HybridBlock):
    def __init__(self, dtype="float32", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.classifier = Dense(2, flatten=False, dtype=dtype,
                                    prefix="cls_")

    def hybrid_forward(self, F, pooled):
        return self.classifier(pooled)


_BERT_SPECS = {
    "bert_base": dict(units=768, hidden_size=3072, num_layers=12,
                      num_heads=12),
    "bert_large": dict(units=1024, hidden_size=4096, num_layers=24,
                       num_heads=16),
}


def get_bert(spec="bert_base", vocab_size=30522, max_length=512,
             dropout=0.1, dtype="float32", **kwargs):
    cfg = dict(_BERT_SPECS[spec])
    cfg.update(kwargs)
    return BERTModel(vocab_size=vocab_size, max_length=max_length,
                     dropout=dropout, attention_dropout=dropout,
                     dtype=dtype, **cfg)


def bert_base(**kwargs):
    """BERT-base (L=12, H=768, A=12) — the v5p north-star config."""
    return get_bert("bert_base", **kwargs)


def bert_large(**kwargs):
    return get_bert("bert_large", **kwargs)


def bert_serving_entry(model, head=None, hybridize=True):
    """Adapt a (initialized) BERT trunk to the ``ServingEngine`` model
    contract: ``entry(ids, token_types, valid_length, segment_ids,
    positions) -> (B, S, U)`` per-token outputs on packed rows.

    The packed pooled output is meaningless (row slot 0 is only the
    first packed sequence's [CLS]) so only the sequence output rides;
    the engine slices per-request outputs by placement and pools
    per SEGMENT (``pool="cls"/"mean"``) — the packed-correct analog of
    the pooler. ``head`` (e.g. a scorer Dense/BERTMLMHead) applies to
    the sequence output inside the same traced graph. ``hybridize``
    activates the CachedOp so each (rows, row_len) shape bucket
    compiles once and is cached — the serving fast path.
    """
    if hybridize:
        model.hybridize()
        if head is not None:
            head.hybridize()

    def entry(ids, token_types, valid_length, segment_ids, positions):
        out = model(ids, token_types, valid_length, None, segment_ids,
                    positions)
        seq = out[0] if isinstance(out, (list, tuple)) else out
        return head(seq) if head is not None else seq

    return entry
