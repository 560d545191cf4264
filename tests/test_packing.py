"""Sequence packing (io/packing.py): round-trip exactness, layout
contract and data-layer wiring.

The segment-isolation numerics (packed == unpacked through the flash
kernel and the full BERT stack) live in test_pallas.py /
test_transformer.py; this file owns the packing layer itself.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.io.packing import (PackedBatchify, PackedSeqIter,
                                  StreamingPacker, pack_sequences,
                                  packing_efficiency, stream_pack,
                                  unpack_sequences)


def _samples(rs, n, lo=3, hi=17, vocab=100):
    return [rs.randint(1, vocab, rs.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def test_pack_roundtrip_restores_every_sample():
    rs = np.random.RandomState(0)
    seqs = _samples(rs, 37)
    labels = [s * 2 + 1 for s in seqs]
    batch = pack_sequences(seqs, 16, extras=[labels])
    back = unpack_sequences(batch)
    assert len(back) == len(seqs)
    for a, b in zip(back, seqs):
        assert np.array_equal(a, b)
    # extras share the layout: unpack any parallel array by placements
    back_l = unpack_sequences(batch.extras[0], batch.placements)
    for a, b in zip(back_l, labels):
        assert np.array_equal(a, b)


def test_pack_layout_contract():
    rs = np.random.RandomState(1)
    seqs = _samples(rs, 25)
    batch = pack_sequences(seqs, 16)
    R, L = batch.data.shape
    assert batch.segment_ids.shape == (R, L)
    assert batch.positions.shape == (R, L)
    assert batch.valid_length.shape == (R,)
    for r in range(R):
        vl = batch.valid_length[r]
        seg = batch.segment_ids[r]
        # contiguous from 0, padding strictly after, ids monotone 1..n
        assert (seg[:vl] > 0).all() and (seg[vl:] == 0).all()
        assert (np.diff(seg[:vl]) >= 0).all()
        # positions restart at 0 per segment and count up
        for sid in np.unique(seg[:vl]):
            pos = batch.positions[r][seg == sid]
            assert np.array_equal(pos, np.arange(len(pos)))
    # first-fit on arrival order: every sample placed, none split
    assert sum(len(s) for s in seqs) == int(batch.valid_length.sum())
    assert 0.0 < packing_efficiency(batch) <= 1.0


def test_pack_rejects_bad_lengths():
    with pytest.raises(ValueError):
        pack_sequences([np.arange(20)], 16)
    with pytest.raises(ValueError):
        pack_sequences([np.arange(0)], 16)
    with pytest.raises(ValueError):
        pack_sequences([np.arange(5)], 16, extras=[[np.arange(4)]])
    # max_rows refuses overflow placements instead of opening rows
    with pytest.raises(ValueError):
        pack_sequences([np.arange(1, 11)] * 3, 16, max_rows=1)


def test_packed_batchify_in_dataloader():
    from mxnet_tpu.gluon.data import DataLoader, SimpleDataset

    rs = np.random.RandomState(2)
    seqs = _samples(rs, 24)
    labels = [s + 1 for s in seqs]
    ds = SimpleDataset(list(zip(seqs, labels)))
    # process workers: PackedBatchify must stay numpy-only (worker-safe)
    dl = DataLoader(ds, batch_size=8, batchify_fn=PackedBatchify(16),
                    num_workers=2)
    seen = 0
    for data, seg, pos, vl, lab in dl:
        data, seg, lab = (x.asnumpy() if isinstance(x, nd.NDArray) else
                          np.asarray(x) for x in (data, seg, lab))
        assert data.shape == seg.shape == lab.shape
        assert ((lab == data + 1) | (seg == 0)).all()
        seen += int((np.asarray(seg) > 0).sum())
    assert seen == sum(len(s) for s in seqs)


def test_packed_seq_iter_module_contract():
    rs = np.random.RandomState(3)
    seqs = _samples(rs, 21)
    labels = [s + 3 for s in seqs]
    it = PackedSeqIter(seqs, 16, batch_size=4, labels=labels)
    names = [d.name for d in it.provide_data]
    assert names == ["data", "segment_ids", "positions", "valid_length"]
    rows = 0
    last = None
    for db in it:
        assert len(db.data) == 4 and len(db.label) == 1
        assert db.data[0].shape[0] == 4
        rows += 4 - (db.pad or 0)
        last = db
    assert rows == it.packed.data.shape[0]
    assert last is not None
    it.reset()
    assert it.next().data[0].shape[0] == 4


def test_streaming_packer_bounded_buffer_no_loss():
    """Online first-fit with a bounded open-row set: every token of an
    arbitrary stream comes back exactly once, rows respect the layout
    contract, and the open buffer never exceeds its bound."""
    rs = np.random.RandomState(4)
    seqs = _samples(rs, 83)
    labels = [s * 3 for s in seqs]
    packer = StreamingPacker(16, open_rows=3)
    rows = []
    for s, l in zip(seqs, labels):
        rows.extend(packer.add(s, (l,)))
        assert len(packer.open_rows) <= 3
    rows.extend(packer.flush())
    assert not packer.open_rows
    got, got_labels = [], []
    for row in rows:
        assert row.data.shape == (1, 16)
        vl = int(row.valid_length[0])
        assert (row.segment_ids[0, :vl] > 0).all()
        assert (row.segment_ids[0, vl:] == 0).all()
        got.extend(unpack_sequences(row))
        got_labels.extend(unpack_sequences(row.extras[0], row.placements))
    # rows close out of arrival order; compare as multisets of samples
    want = {s.tobytes() for s in seqs}
    assert {g.tobytes() for g in got} == want
    assert len(got) == len(seqs)
    for g, gl in zip(got, got_labels):
        assert np.array_equal(gl, g * 3)


def test_streaming_packer_validation():
    p = StreamingPacker(8, open_rows=2)
    with pytest.raises(ValueError):
        p.add(np.arange(9))
    with pytest.raises(ValueError):
        p.add(np.arange(1, 4), (np.arange(2),))
    p.add(np.arange(1, 4), (np.arange(3),))
    with pytest.raises(ValueError):
        p.add(np.arange(1, 4))          # extras arity changed
    with pytest.raises(ValueError):
        StreamingPacker(8, open_rows=0)


def test_stream_pack_batches_feed_epochs():
    """The corpus-reader entry: a generator of samples in, fixed
    (batch_rows, L) PackedBatches out, bounded memory, exact
    round-trip through placements."""
    rs = np.random.RandomState(5)
    seqs = _samples(rs, 41)
    labels = [s + 7 for s in seqs]
    batches = list(stream_pack(iter(zip(seqs, labels)), 16,
                               batch_rows=4, open_rows=3))
    total = 0
    for b in batches[:-1]:
        assert b.data.shape == (4, 16)
    assert batches[-1].data.shape[0] <= 4   # final flush may be short
    seen = set()
    for b in batches:
        for tok, lab in zip(unpack_sequences(b),
                            unpack_sequences(b.extras[0], b.placements)):
            assert np.array_equal(lab, tok + 7)
            seen.add(tok.tobytes())
            total += len(tok)
    assert total == sum(len(s) for s in seqs)
    assert seen == {s.tobytes() for s in seqs}
    # steady-state rows are dense on this mix
    effs = [packing_efficiency(b.segment_ids) for b in batches[:-1]]
    assert sum(effs) / len(effs) > 0.7


def test_segment_valid_len_op_dispatch():
    seg = nd.array(np.array([[1, 1, 2, 2, 0, 0], [1, 0, 0, 0, 0, 0]],
                            np.int32), dtype="int32")
    out = nd.segment_valid_len(seg)
    assert out.asnumpy().tolist() == [4, 1]
