"""Paper-faithful dictionary codec (Listings 2–4) — host-side numpy.

This is the *reference/validation* codec: byte-exact reimplementation of the
paper's escape-stream format, used to reproduce Table 1's compression ratios
and the losslessness claim.  The TPU-parallel format lives in
``blocked_codec.py`` (see DESIGN.md §2 for why the stream layout changes).

Format (paper Listing 3):
  stream of uint16; a value < ESCAPE is a codeword for a ``sequence_length``
  run of uint8 quantized weights; ESCAPE (0xFFFF) is followed by
  ``sequence_length`` raw weights stored one-per-uint16.  A trailing
  ESCAPE + remainder handles lengths not divisible by sequence_length.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ESCAPE = 0xFFFF
DEFAULT_SEQ_LEN = 4
MAX_TABLE = ESCAPE  # codewords 0..0xFFFE


def gram_keys(grams: np.ndarray) -> np.ndarray:
    """(n, S) uint8 grams → (n,) uint64 keys, first byte most significant,
    so key order is the rows' lexicographic order."""
    keys = np.zeros(len(grams), np.uint64)
    for j in range(grams.shape[1]):
        keys = (keys << np.uint64(8)) | grams[:, j].astype(np.uint64)
    return keys


class GramIndex:
    """A {gram → codeword} table as sorted key/code arrays, so a whole
    tensor's grams look up in one vectorized ``np.searchsorted``.  Build it
    once per table; every encoder accepts it in place of the dict."""

    def __init__(self, table: dict, sequence_length: int = DEFAULT_SEQ_LEN):
        self.table = table
        grams = np.array(list(table.keys()), dtype=np.uint8).reshape(
            -1, sequence_length)
        codes = np.fromiter(table.values(), dtype=np.int64, count=len(table))
        keys = gram_keys(grams)
        order = np.argsort(keys)
        self.keys, self.codes = keys[order], codes[order]

    def lookup(self, grams: np.ndarray, missing: int = -1) -> np.ndarray:
        """(n, S) uint8 grams → (n,) int64 codewords, ``missing`` where
        the gram is not in the table."""
        keys = gram_keys(grams)
        if not len(self.keys):
            return np.full(len(keys), missing, np.int64)
        idx = np.minimum(np.searchsorted(self.keys, keys), len(self.keys) - 1)
        return np.where(self.keys[idx] == keys, self.codes[idx], missing)


def as_index(table, sequence_length: int = DEFAULT_SEQ_LEN) -> GramIndex:
    return (table if isinstance(table, GramIndex)
            else GramIndex(table, sequence_length))


def find_frequent_sequences(weights_list: list[np.ndarray],
                            sequence_length: int = DEFAULT_SEQ_LEN,
                            max_codes: int = MAX_TABLE,
                            min_count: int = 2,
                            sample_cap: int | None = 50_000_000) -> dict:
    """Paper Listing 2: frequency table over length-``sequence_length``
    subsequences of the flattened quantized weights.

    Returns {tuple(seq) -> codeword}, codewords dense in [0, n_codes):
    by count, descending; ties in first-seen order (arrays in list order,
    each array's grams in ascending byte order).
    """
    budget = sample_cap if sample_cap is not None else float("inf")
    all_keys, all_counts, all_first = [], [], []
    for i, w in enumerate(weights_list):
        flat = np.ascontiguousarray(w).reshape(-1).astype(np.uint8)
        n = (len(flat) // sequence_length) * sequence_length
        if n == 0:
            continue
        grams = flat[:n].reshape(-1, sequence_length)
        if len(grams) > budget:
            grams = grams[: int(budget)]
        budget -= len(grams)
        u, c = np.unique(gram_keys(grams), return_counts=True)
        all_keys.append(u)
        all_counts.append(c)
        all_first.append(np.full(len(u), i))
        if budget <= 0:
            break
    if not all_keys:
        return {}
    # keys are unique within an array and arrays are concatenated in
    # order, so a key's first occurrence names the array it was first seen in
    u, at, inv = np.unique(np.concatenate(all_keys), return_index=True,
                           return_inverse=True)
    counts = np.bincount(inv, weights=np.concatenate(all_counts)).astype(
        np.int64)
    first = np.concatenate(all_first)[at]
    frequent = np.nonzero(counts >= min_count)[0]
    order = frequent[np.lexsort((u[frequent], first[frequent],
                                 -counts[frequent]))][:max_codes]
    shifts = np.arange(sequence_length - 1, -1, -1, dtype=np.uint64) * 8
    grams = ((u[order][:, None] >> shifts) & np.uint64(0xFF)).astype(int)
    return {tuple(g): code for code, g in enumerate(grams.tolist())}


def compress_array(weights: np.ndarray, table,
                   sequence_length: int = DEFAULT_SEQ_LEN) -> np.ndarray:
    """Paper Listing 3, vectorized but format-identical.

    Produces the exact uint16 stream the paper's serial loop produces:
    a hit gram is its codeword, a missed one ESCAPE + its raw values.
    """
    flat = np.ascontiguousarray(weights).reshape(-1).astype(np.uint8)
    n_full = len(flat) // sequence_length
    head = flat[: n_full * sequence_length].reshape(-1, sequence_length)
    tail = flat[n_full * sequence_length:]

    codes = as_index(table, sequence_length).lookup(head)
    hit = codes >= 0
    width = np.where(hit, 1, 1 + sequence_length)
    start = np.concatenate([[0], np.cumsum(width)[:-1]]).astype(np.int64)
    n_out = int(width.sum()) + (1 + len(tail) if tail.size else 0)
    out = np.empty(n_out, np.uint16)
    out[start[hit]] = codes[hit]
    miss = np.nonzero(~hit)[0]
    out[start[miss]] = ESCAPE
    for j in range(sequence_length):
        out[start[miss] + 1 + j] = head[miss, j]
    if tail.size > 0:
        out[n_out - 1 - len(tail)] = ESCAPE
        out[n_out - len(tail):] = tail
    return out


def decompress_array(stream: np.ndarray, table: dict, orig_len: int,
                     sequence_length: int = DEFAULT_SEQ_LEN) -> np.ndarray:
    """Paper Listing 4."""
    inv = {code: np.asarray(seq, dtype=np.uint8) for seq, code in table.items()}
    out = np.empty(orig_len + sequence_length, dtype=np.uint8)
    pos = 0
    i = 0
    n = len(stream)
    while i < n:
        cw = int(stream[i]); i += 1
        if cw == ESCAPE:
            remaining = min(sequence_length, orig_len - pos, n - i)
            out[pos:pos + remaining] = stream[i:i + remaining].astype(np.uint8)
            pos += remaining
            i += remaining
        else:
            seq = inv[cw]
            out[pos:pos + sequence_length] = seq
            pos += sequence_length
    return out[:orig_len]


@dataclasses.dataclass
class CompressedStream:
    """One tensor compressed in the paper's stream format."""

    stream: np.ndarray        # uint16
    orig_len: int
    shape: tuple
    sequence_length: int = DEFAULT_SEQ_LEN

    @property
    def nbytes(self) -> int:
        return int(self.stream.nbytes)


def compress_model_arrays(arrays: dict[str, np.ndarray],
                          sequence_length: int = DEFAULT_SEQ_LEN,
                          table: dict | None = None,
                          max_codes: int = MAX_TABLE):
    """Paper's ``compress_model`` over a {name: uint8 array} dict.

    Returns (table, {name: CompressedStream}).  One table for the whole
    model, as in the paper.
    """
    if table is None:
        table = find_frequent_sequences(list(arrays.values()),
                                        sequence_length, max_codes)
    out = {}
    for name, arr in arrays.items():
        stream = compress_array(arr, table, sequence_length)
        out[name] = CompressedStream(stream, arr.size, arr.shape,
                                     sequence_length)
    return table, out


def decompress_model_arrays(table: dict,
                            streams: dict[str, "CompressedStream"]):
    out = {}
    for name, cs in streams.items():
        flat = decompress_array(cs.stream, table, cs.orig_len,
                                cs.sequence_length)
        out[name] = flat.reshape(cs.shape)
    return out


def table_nbytes(table: dict, sequence_length: int = DEFAULT_SEQ_LEN) -> int:
    """Bytes to ship the decode LUT (counted against the compressed size,
    as the paper's on-disk format must include it)."""
    return len(table) * sequence_length


def compression_ratio(arrays: dict[str, np.ndarray],
                      streams: dict[str, CompressedStream],
                      table: dict,
                      original_bytes_per_weight: int = 2) -> dict:
    """Table-1-style accounting.

    original: fp16/bf16 model bytes; quantized: 1 byte/weight; compressed:
    escape-stream bytes + LUT.
    """
    n_weights = sum(a.size for a in arrays.values())
    original = n_weights * original_bytes_per_weight
    quantized = n_weights
    compressed = sum(s.nbytes for s in streams.values()) + table_nbytes(table)
    return {
        "n_weights": int(n_weights),
        "original_bytes": int(original),
        "quantized_bytes": int(quantized),
        "compressed_bytes": int(compressed),
        "ratio_vs_original": original / max(compressed, 1),
        "ratio_vs_quantized": quantized / max(compressed, 1),
    }
