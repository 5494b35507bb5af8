"""Sequence packing: fuse variable-length documents into fixed [B, S] rows.

Reference analog: the T5/MaxText pack_dataset idiom. Real pretraining
corpora have skewed document lengths, so padded batches burn 30-60% of
attention/MLP FLOPs on pad tokens; packing makes every token in the batch a
real, loss-bearing token. The packed format is consumed end-to-end:

  * `segment_ids` drive the segment-aware flash kernel
    (paddle_tpu.ops.pallas.flash_attention) / the equivalent XLA mask in
    `F.scaled_dot_product_attention` — attention is block-diagonal per
    document, and whole K blocks are skipped when no segment overlaps;
  * `position_ids` restart at 0 per document so RoPE sees within-document
    positions, not row offsets;
  * `labels` are the within-document next-token targets, with the LAST token
    of every document (and all padding) set to `ignore_index` so no document
    predicts its neighbor's first token.

Format invariants the tests pin down:

  * per row, documents occupy a contiguous prefix in arrival order and
    padding (if any) is a contiguous tail;
  * `segment_ids` are NON-DECREASING along the row (documents numbered
    1..n in placement order, padding = n+1) — this keeps the kernel's
    per-block min/max segment ranges tight, i.e. maximal block skipping;
  * every input token of every document appears exactly once across the
    emitted batches (first-fit never drops or duplicates).

The packer is a plain streaming generator: wrap it in
`paddle_tpu.io.prefetch_to_device` and the packing work runs on the
DeviceFeeder's background thread, off the training loop's critical path.
`segment_ids`/`position_ids` are [B, S] integer leaves exactly like
`input_ids`, so `BatchSpecCache` shards them identically (batch dim over
dp/sharding, sequence dim over 'sep') with no extra configuration.
"""
from __future__ import annotations

from typing import Iterable, Iterator, Sequence

import numpy as np

__all__ = ["SequencePacker", "pack_examples", "pad_examples",
           "packing_stats", "unpack_batch"]

IGNORE_INDEX = -100  # the fused-CE / F.cross_entropy ignore_index default


def _as_tokens(example) -> np.ndarray:
    toks = np.asarray(example)
    if toks.ndim != 1:
        raise ValueError(
            f"each example must be a 1-D token sequence, got shape "
            f"{toks.shape}")
    return toks


class _Row:
    __slots__ = ("docs", "used")

    def __init__(self):
        self.docs: list[np.ndarray] = []
        self.used = 0

    def fits(self, n: int, seq_len: int) -> bool:
        return self.used + n <= seq_len

    def add(self, toks: np.ndarray):
        self.docs.append(toks)
        self.used += len(toks)


class SequencePacker:
    """Streaming first-fit packer producing `(input_ids, labels,
    segment_ids, position_ids)` batches of fixed shape [batch_size, seq_len].

    feed(example) -> list of zero or more completed batches;
    flush() -> the final partial batch (incomplete rows padded, missing rows
    all-padding) or None.

    Documents longer than seq_len are split into seq_len-sized chunks, each
    chunk its own segment (the chunk boundary token's label is ignored, like
    a document boundary). A batch is emitted as soon as an arriving document
    fits in NO open row and all batch_size rows are open — first-fit keeps
    rows open until then, so short documents backfill earlier rows' gaps.
    """

    def __init__(self, seq_len: int, batch_size: int, pad_id: int = 0,
                 ignore_index: int = IGNORE_INDEX, dtype=np.int32):
        if seq_len < 2:
            raise ValueError(f"seq_len must be >= 2, got {seq_len}")
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        self.seq_len = int(seq_len)
        self.batch_size = int(batch_size)
        self.pad_id = pad_id
        self.ignore_index = ignore_index
        self.dtype = dtype
        self._rows: list[_Row] = []
        # diagnostics (cumulative over the stream)
        self.docs_packed = 0
        self.tokens_packed = 0
        self.batches_emitted = 0
        self.pad_tokens_emitted = 0

    # -- packing --------------------------------------------------------------
    def feed(self, example) -> list[dict]:
        """Pack one document; returns the batches completed by it (0+)."""
        toks = _as_tokens(example)
        out = []
        if len(toks) == 0:
            return out
        for start in range(0, len(toks), self.seq_len):
            chunk = toks[start:start + self.seq_len]
            row = next((r for r in self._rows
                        if r.fits(len(chunk), self.seq_len)), None)
            if row is None:
                if len(self._rows) >= self.batch_size:
                    out.append(self._emit())
                row = _Row()
                self._rows.append(row)
            row.add(chunk)
            self.docs_packed += 1
            self.tokens_packed += len(chunk)
        return out

    def flush(self) -> dict | None:
        """Emit the final partial batch (None when nothing is buffered)."""
        if not self._rows:
            return None
        return self._emit()

    def _emit(self) -> dict:
        B, S = self.batch_size, self.seq_len
        ids = np.full((B, S), self.pad_id, self.dtype)
        labels = np.full((B, S), self.ignore_index, self.dtype)
        seg = np.zeros((B, S), self.dtype)
        pos = np.zeros((B, S), self.dtype)
        for r, row in enumerate(self._rows):
            off = 0
            for d, toks in enumerate(row.docs):
                n = len(toks)
                ids[r, off:off + n] = toks
                # within-document next-token labels; the boundary token
                # predicts nothing (ignore_index)
                labels[r, off:off + n - 1] = toks[1:]
                seg[r, off:off + n] = d + 1
                pos[r, off:off + n] = np.arange(n)
                off += n
            # the padded tail is its own (loss-free) trailing segment, so
            # segment ids stay non-decreasing along the row
            if off < S:
                seg[r, off:] = len(row.docs) + 1
                pos[r, off:] = np.arange(S - off)
                self.pad_tokens_emitted += S - off
        # rows that never opened are all-padding (segment 1, no loss)
        for r in range(len(self._rows), B):
            seg[r] = 1
            pos[r] = np.arange(S)
            self.pad_tokens_emitted += S
        self._rows = []
        self.batches_emitted += 1
        return {"input_ids": ids, "labels": labels,
                "segment_ids": seg, "position_ids": pos}


def pack_examples(examples: Iterable, seq_len: int, batch_size: int,
                  pad_id: int = 0, ignore_index: int = IGNORE_INDEX,
                  flush_remainder: bool = True,
                  packer: SequencePacker | None = None) -> Iterator[dict]:
    """Generator: stream documents through a first-fit `SequencePacker`,
    yielding packed [batch_size, seq_len] batches. Wrap the result in
    `prefetch_to_device` to run the packing on the feeder thread."""
    p = packer or SequencePacker(seq_len, batch_size, pad_id=pad_id,
                                 ignore_index=ignore_index)
    for ex in examples:
        yield from p.feed(ex)
    if flush_remainder:
        tail = p.flush()
        if tail is not None:
            yield tail


def pad_examples(examples: Iterable, seq_len: int, batch_size: int,
                 pad_id: int = 0,
                 ignore_index: int = IGNORE_INDEX) -> Iterator[dict]:
    """The PADDED baseline with the same schema: one document per row,
    truncated to seq_len. Same labels/positions semantics as the packer, so
    packed-vs-padded comparisons (the equivalence
    test) differ ONLY in row layout."""
    rows: list[dict] = []

    def one_row(toks):
        # a batch_size-1 packer fed one document IS the padded row: same
        # label/segment/position semantics as the packed layout, no fusing
        p = SequencePacker(seq_len, 1, pad_id=pad_id,
                           ignore_index=ignore_index)
        p.feed(toks)
        row = p.flush()
        if row is None:  # no document: the packer's all-pad filler row
            row = {"input_ids": np.full((1, seq_len), pad_id, np.int32),
                   "labels": np.full((1, seq_len), ignore_index, np.int32),
                   "segment_ids": np.ones((1, seq_len), np.int32),
                   "position_ids": np.arange(seq_len, dtype=np.int32)[None]}
        return row

    def emit(rows):
        empty = one_row(np.zeros(0, np.int32))
        rows = rows + [empty] * (batch_size - len(rows))
        return {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}

    for ex in examples:
        toks = _as_tokens(ex)[:seq_len]
        if len(toks) == 0:
            continue
        rows.append(one_row(toks))
        if len(rows) == batch_size:
            yield emit(rows)
            rows = []
    if rows:
        yield emit(rows)


def unpack_batch(batch: dict, pad_id: int = 0,
                 ignore_index: int = IGNORE_INDEX) -> list[np.ndarray]:
    """Recover the per-document token sequences from a packed batch (the
    round-trip check): split each row on segment-id changes and drop the
    trailing pad segment (all-`pad_id` ids with all-ignored labels at the row
    suffix; exact unless a real document IS a single pad_id token placed at a
    row end). Returns documents in row-major placement order."""
    ids = np.asarray(batch["input_ids"])
    seg = np.asarray(batch["segment_ids"])
    labels = np.asarray(batch["labels"])
    docs = []
    for r in range(ids.shape[0]):
        bounds = [0] + (1 + np.flatnonzero(np.diff(seg[r]))).tolist() + [
            ids.shape[1]]
        for a, b in zip(bounds[:-1], bounds[1:]):
            if (b == ids.shape[1] and (ids[r, a:b] == pad_id).all()
                    and (labels[r, a:b] == ignore_index).all()):
                continue  # the padded tail
            docs.append(ids[r, a:b])
    return docs


def packing_stats(lengths: Sequence[int], seq_len: int,
                  batch_size: int) -> dict:
    """What padding costs for a corpus of document `lengths`: the padded
    baseline's pad fraction, and the rows/batches the packed layout needs.
    Purely combinatorial, but replays the REAL policies: the packed side
    feeds full lengths through a `SequencePacker` (documents longer than
    seq_len chunk, exactly as `pack_examples` does), the padded side
    truncates to seq_len (exactly as `pad_examples` does) — so the two
    token totals can differ on corpora with overlong documents."""
    lengths = [int(n) for n in lengths if int(n) > 0]
    capped = [min(n, seq_len) for n in lengths]
    padded_tokens_real = sum(capped)  # pad_examples truncates overflow
    padded_rows = len(lengths)
    padded_tokens = padded_rows * seq_len
    total = sum(lengths)  # the packer keeps every token (chunking)
    p = SequencePacker(seq_len, batch_size)
    batches = sum(len(p.feed(np.zeros(n, np.int32))) for n in lengths)
    if p._rows:
        packed_rows = batches * batch_size + len(p._rows)
        batches += 1
    else:
        packed_rows = batches * batch_size
    # *_emitted: what pack_examples actually ships — final partial batches
    # are padded to full [batch_size, seq_len] shape with all-pad filler
    # rows, which the training step really computes
    rows_emitted = batches * batch_size
    return {
        "documents": len(lengths),
        "real_tokens": total,
        "real_tokens_padded": padded_tokens_real,
        "padded_rows": padded_rows,
        "padding_frac_padded": 1.0 - padded_tokens_real / max(padded_tokens, 1),
        "packed_rows": packed_rows,
        "packed_batches": batches,
        "packed_rows_emitted": rows_emitted,
        "padding_frac_packed": 1.0 - total / max(packed_rows * seq_len, 1),
        "padding_frac_packed_emitted":
            1.0 - total / max(rows_emitted * seq_len, 1),
        "row_compression": padded_rows / max(packed_rows, 1),
    }
