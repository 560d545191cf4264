"""Sequence packing for variable-length token batches.

The padded BERT leg burns ~26% of every step attending over and
backpropagating through padding (BENCH_r05: valid_frac 0.74 at seq512).
Packing recovers it: multiple variable-length sequences share one fixed
(batch, seq_len) row, and the flash-attention kernel's ``segment_ids``
path (ops/pallas/flash_attention.py) keeps attention block-diagonal so
sequences never see each other — the T5/MaxText-style TPU fix, and the
TPU-native continuation of the reference's bucketing heritage
(BucketingModule binned lengths into a few compiled shapes; packing
bins them into ONE shape with near-zero waste).

Layout contract (shared with the kernel and the gluon/bench consumers):

- ``data``        (R, L): tokens, first-fit-packed, padded with
                  ``pad_value``;
- ``segment_ids`` (R, L) int32: 1..n per row in placement order, 0 on
                  padding — contiguous, monotonically non-decreasing
                  within a row (what makes the kernel's min/max
                  block-skip tight);
- ``positions``   (R, L) int32: PER-SEGMENT 0-based positions (each
                  sequence's positional embedding restarts at 0), 0 on
                  padding;
- ``valid_length``(R,) int32: used slots per row (segments are packed
                  from position 0, so this is also the kv length the
                  kernel masks with).

Loss masks derive as ``segment_ids > 0``.

Positions are bounded by each SAMPLE's length, not the row length —
so a model with a finite position table (BERT ``max_length``) can pack
into rows LONGER than the table as long as every individual sample
stays within it (e.g. 512-max samples in 2048-slot rows against a
512-entry table).

``pack_sequences`` is greedy first-fit in arrival order — the online
algorithm a streaming corpus reader can run (rows stay open until the
stream ends). For a fixed row budget, pack a modest oversample and
keep the fullest rows (first-fit's open tail rows are the only
low-occupancy ones).
"""
from __future__ import annotations

from collections import namedtuple

import numpy as np

__all__ = ["PackedBatch", "Placement", "pack_sequences", "unpack_sequences",
           "packing_efficiency", "PackedBatchify", "PackedSeqIter",
           "StreamingPacker", "stream_pack"]


PackedBatch = namedtuple(
    "PackedBatch",
    ["data", "segment_ids", "positions", "valid_length", "placements",
     "extras"])

# where sample i landed: data[row, offset:offset+length] (segment_ids
# there are == segment; kept per-sample so unpack is exact)
Placement = namedtuple("Placement", ["row", "offset", "length", "segment"])


def pack_sequences(sequences, seq_len, extras=None, pad_value=0,
                   dtype=None, max_rows=None):
    """Greedy first-fit packing of 1-D samples into (R, seq_len) rows.

    Parameters
    ----------
    sequences : list of 1-D arrays (the token samples), each with
        0 < len <= seq_len.
    extras : optional list of lists of 1-D arrays, each parallel to
        ``sequences`` (labels, weights, ...) and length-equal per
        sample; packed into identical layouts.
    max_rows : refuse placements that would open row max_rows+1 —
        samples that no open row can hold raise (the bench packs with
        an unbounded row count and selects rows afterwards).

    Returns a :class:`PackedBatch`; ``extras`` in the result is a list
    of (R, seq_len) arrays parallel to the input extras.
    """
    seqs = [np.asarray(s).reshape(-1) for s in sequences]
    extras = [list(map(np.asarray, ex)) for ex in (extras or [])]
    for ex in extras:
        if len(ex) != len(seqs):
            raise ValueError("extras must parallel sequences")
    if dtype is None:
        dtype = seqs[0].dtype if seqs else np.int32

    used = []          # per open row: slots consumed
    counts = []        # per open row: number of segments placed
    placements = []
    for idx, s in enumerate(seqs):
        n = len(s)
        if not 0 < n <= seq_len:
            raise ValueError(
                f"sample {idx} has length {n}, outside (0, {seq_len}]")
        if extras:
            for ex in extras:
                if len(ex[idx]) != n:
                    raise ValueError(
                        f"extra for sample {idx} has length "
                        f"{len(ex[idx])} != {n}")
        for r in range(len(used)):      # first fit
            if used[r] + n <= seq_len:
                break
        else:
            r = len(used)
            if max_rows is not None and r >= max_rows:
                raise ValueError(
                    f"sample {idx} (len {n}) does not fit in any of the "
                    f"{max_rows} allowed rows")
            used.append(0)
            counts.append(0)
        placements.append(Placement(r, used[r], n, counts[r] + 1))
        used[r] += n
        counts[r] += 1

    rows = len(used)
    data = np.full((rows, seq_len), pad_value, dtype=dtype)
    seg = np.zeros((rows, seq_len), np.int32)
    pos = np.zeros((rows, seq_len), np.int32)
    packed_extras = [
        np.zeros((rows, seq_len), ex[0].dtype if ex else np.int32)
        for ex in extras]
    for s, pl, i in zip(seqs, placements, range(len(seqs))):
        sl = slice(pl.offset, pl.offset + pl.length)
        data[pl.row, sl] = s
        seg[pl.row, sl] = pl.segment
        pos[pl.row, sl] = np.arange(pl.length)
        for ex, out in zip(extras, packed_extras):
            out[pl.row, sl] = ex[i]
    valid = np.asarray(used, np.int32)
    return PackedBatch(data, seg, pos, valid, placements, packed_extras)


def unpack_sequences(packed, placements=None):
    """Restore the original sample list from a packed array.

    ``packed`` is a PackedBatch (its own placements are used) or a bare
    (R, L[, ...]) array with ``placements`` given — the latter unpacks
    any array sharing the packed layout (model outputs: per-token
    logits/hidden states slice the same way)."""
    if placements is None:
        placements = packed.placements
        packed = packed.data
    return [np.asarray(packed)[p.row, p.offset:p.offset + p.length]
            for p in placements]


def packing_efficiency(batch):
    """Fraction of slots holding real tokens (PackedBatch or a
    segment_ids array)."""
    seg = batch.segment_ids if isinstance(batch, PackedBatch) else batch
    seg = np.asarray(seg)
    return float((seg > 0).sum()) / seg.size


class PackedBatchify:
    """``DataLoader(..., batchify_fn=PackedBatchify(seq_len))``: pack
    the sampled variable-length sequences into fixed rows.

    Samples are 1-D token arrays, or (tokens, label_arrays...) tuples
    with per-token labels packed into the same layout. Returns
    ``(data, segment_ids, positions, valid_length[, labels...])`` as
    numpy — worker-process safe (never touches device arrays; the
    parent wraps, matching default_mp_batchify_fn's contract)."""

    def __init__(self, seq_len, pad_value=0):
        self._seq_len = seq_len
        self._pad = pad_value

    def __call__(self, samples):
        if isinstance(samples[0], tuple):
            cols = list(zip(*samples))
            seqs, label_cols = cols[0], cols[1:]
        else:
            seqs, label_cols = samples, ()
        batch = pack_sequences(seqs, self._seq_len,
                               extras=[list(c) for c in label_cols],
                               pad_value=self._pad)
        return (batch.data, batch.segment_ids, batch.positions,
                batch.valid_length, *batch.extras)


class PackedSeqIter:
    """DataIter over packed rows (the Module-path consumer).

    Packs the whole sample list up front (first-fit, arrival order) and
    yields DataBatch(data=[tokens, segment_ids, positions, valid_length],
    label=[packed labels...]) of ``batch_size`` rows. The final partial
    row-batch pads with empty rows and reports ``pad`` (NDArrayIter's
    last-batch convention).
    """

    def __init__(self, sequences, seq_len, batch_size, labels=None,
                 pad_value=0, data_name="data", label_name="softmax_label"):
        from . import io as _io

        self._io = _io
        batch = pack_sequences(
            sequences, seq_len,
            extras=[labels] if labels is not None else None,
            pad_value=pad_value)
        self.packed = batch
        self.batch_size = batch_size
        self._seq_len = seq_len
        arrays = [batch.data, batch.segment_ids, batch.positions,
                  batch.valid_length]
        self._data_names = [data_name, "segment_ids", "positions",
                            "valid_length"]
        self._arrays = arrays
        self._labels = list(batch.extras)
        self._label_names = [label_name] if self._labels else []
        self._rows = batch.data.shape[0]
        self._cursor = 0

    @property
    def provide_data(self):
        return [self._io.DataDesc(n, (self.batch_size,) + a.shape[1:],
                                  a.dtype)
                for n, a in zip(self._data_names, self._arrays)]

    @property
    def provide_label(self):
        return [self._io.DataDesc(n, (self.batch_size,) + a.shape[1:],
                                  a.dtype)
                for n, a in zip(self._label_names, self._labels)]

    def reset(self):
        self._cursor = 0

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        from .. import ndarray as nd

        if self._cursor >= self._rows:
            raise StopIteration
        lo = self._cursor
        hi = min(lo + self.batch_size, self._rows)
        self._cursor = hi
        pad = self.batch_size - (hi - lo)

        def take(a):
            out = a[lo:hi]
            if pad:
                out = np.concatenate(
                    [out, np.zeros((pad,) + a.shape[1:], a.dtype)])
            return nd.array(out, dtype=str(out.dtype))

        return self._io.DataBatch(
            data=[take(a) for a in self._arrays],
            label=[take(a) for a in self._labels],
            pad=pad)


class StreamingPacker:
    """Online first-fit packer over a BOUNDED set of open rows.

    ``pack_sequences`` needs the whole sample list up front; a corpus
    reader (or a serving batcher) sees samples one at a time and cannot
    hold an unbounded open-row set. This packer keeps at most
    ``open_rows`` rows open: a sample first-fits into an open row, and
    when none fits and the buffer is full, the FULLEST open row is
    closed and emitted — the bounded-buffer variant of the same greedy
    algorithm (what the module docstring calls "the online algorithm a
    streaming corpus reader can run", now actually runnable on an
    endless stream).

    ``add`` returns the list of rows the call closed (usually empty);
    ``flush`` closes and returns everything still open. Each emitted
    row is a 1-row :class:`PackedBatch` sharing the layout contract
    above; ``placements`` are in the order the samples were added to
    that row.
    """

    def __init__(self, seq_len, open_rows=8, pad_value=0, dtype=None):
        if open_rows < 1:
            raise ValueError("open_rows must be >= 1")
        self._seq_len = seq_len
        self._open_rows = open_rows
        self._pad = pad_value
        self._dtype = dtype
        self._open = []   # list of dicts: used, samples=[(seq, extras)]

    @property
    def open_rows(self):
        """(used_slots, n_samples) per currently-open row."""
        return [(row["used"], len(row["samples"])) for row in self._open]

    def _emit(self, row):
        seqs = [s for s, _ in row["samples"]]
        n_extras = len(row["samples"][0][1])
        extras = [[ex[e] for _, ex in row["samples"]]
                  for e in range(n_extras)] or None
        # the samples fit one row by construction, so offline first-fit
        # over just them reproduces the exact single-row layout
        return pack_sequences(seqs, self._seq_len, extras=extras,
                              pad_value=self._pad, dtype=self._dtype,
                              max_rows=1)

    def add(self, seq, extras=()):
        """Place one sample; returns the rows this call closed."""
        seq = np.asarray(seq).reshape(-1)
        n = len(seq)
        if not 0 < n <= self._seq_len:
            raise ValueError(
                f"sample has length {n}, outside (0, {self._seq_len}]")
        extras = tuple(np.asarray(e) for e in extras)
        for e in extras:
            if len(e) != n:
                raise ValueError(
                    f"extra has length {len(e)} != sample length {n}")
        if self._open and len(extras) != len(self._open[0]["samples"][0][1]):
            raise ValueError("extras arity changed mid-stream")
        closed = []
        for row in self._open:                      # first fit
            if row["used"] + n <= self._seq_len:
                row["used"] += n
                row["samples"].append((seq, extras))
                return closed
        if len(self._open) >= self._open_rows:
            # no open row fits: close the fullest (it has the least
            # headroom left — the row least likely to ever fit again)
            fullest = max(range(len(self._open)),
                          key=lambda i: self._open[i]["used"])
            closed.append(self._emit(self._open.pop(fullest)))
        self._open.append({"used": n, "samples": [(seq, extras)]})
        return closed

    def flush(self):
        """Close every open row (stream end); returns them in the
        order they were opened."""
        out = [self._emit(row) for row in self._open]
        self._open = []
        return out


def stream_pack(samples, seq_len, batch_rows=None, open_rows=8,
                pad_value=0, dtype=None):
    """Generator: first-fit-pack a sample stream on the fly.

    ``samples`` yields 1-D token arrays or (tokens, extra, ...) tuples
    (per-token labels/weights, as in :class:`PackedBatchify`). Rows are
    packed through a :class:`StreamingPacker` with a bounded
    ``open_rows`` buffer; with ``batch_rows=None`` each completed row
    is yielded as a 1-row :class:`PackedBatch`, otherwise rows are
    accumulated and yielded as (batch_rows, seq_len) batches (the final
    flush may yield a short batch). This is the epoch feeder the
    offline ``pack_sequences`` could not be: memory is bounded by
    ``open_rows + batch_rows`` rows regardless of corpus size."""
    packer = StreamingPacker(seq_len, open_rows=open_rows,
                             pad_value=pad_value, dtype=dtype)
    pending = []
    for sample in samples:
        if isinstance(sample, tuple):
            seq, extras = sample[0], tuple(sample[1:])
        else:
            seq, extras = sample, ()
        pending.extend(packer.add(seq, extras))
        yield from _drain(pending, batch_rows, done=False)
    pending.extend(packer.flush())
    yield from _drain(pending, batch_rows, done=True)


def _drain(pending, batch_rows, done):
    """Yield ready batches out of ``pending`` single-row packs."""
    if batch_rows is None:
        while pending:
            yield pending.pop(0)
        return
    while len(pending) >= batch_rows or (done and pending):
        rows = [pending.pop(0) for _ in range(min(batch_rows, len(pending)))]
        placements = []
        for r, row in enumerate(rows):
            placements.extend(Placement(r, p.offset, p.length, p.segment)
                              for p in row.placements)
        yield PackedBatch(
            np.concatenate([r.data for r in rows]),
            np.concatenate([r.segment_ids for r in rows]),
            np.concatenate([r.positions for r in rows]),
            np.concatenate([r.valid_length for r in rows]),
            placements,
            [np.concatenate([r.extras[e] for r in rows])
             for e in range(len(rows[0].extras))])
