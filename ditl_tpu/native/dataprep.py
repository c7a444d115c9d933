"""ctypes bridge to the C++ data-prep library (csrc/dataprep.cpp).

Build-on-first-use via native/build.NativeLib (no pip/pybind11 involved —
plain ``ctypes`` per the zero-new-dependency rule). Every entry point has a
pure-Python/numpy fallback, so a machine without a toolchain still runs —
just slower on the host data path.

Used by data/loader.py for the byte-tokenizer hot path: packing a shard's
documents into fixed (rows, seq_len) training batches. HF tokenizers bring
their own native code and bypass this.
"""

from __future__ import annotations

import ctypes

import numpy as np

from ditl_tpu.native.build import NativeLib

__all__ = ["available", "loaded", "pack_stream", "segments_positions", "tokenize_padded"]

_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")


def _register(lib: ctypes.CDLL) -> None:
    lib.dp_stream_size.restype = ctypes.c_int64
    lib.dp_stream_size.argtypes = [_i64p, ctypes.c_int64]
    lib.dp_pack_stream.restype = ctypes.c_int64
    lib.dp_pack_stream.argtypes = [
        _u8p, _i64p, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, _i32p, ctypes.c_int64,
    ]
    lib.dp_segments_positions.restype = None
    lib.dp_segments_positions.argtypes = [
        _i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, _i32p, _i32p,
    ]
    lib.dp_tokenize_padded.restype = ctypes.c_int64
    lib.dp_tokenize_padded.argtypes = [
        _u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.c_int32, _i32p, _f32p,
    ]


_LIB = NativeLib("dataprep", _register)


def _get() -> ctypes.CDLL | None:
    return _LIB.get()


def available() -> bool:
    return _LIB.available()


def loaded() -> bool:
    """Whether the library is loaded in this process (never builds)."""
    return _LIB.loaded


def _concat_docs(texts: list[str]) -> tuple[np.ndarray, np.ndarray]:
    blobs = [t.encode("utf-8") for t in texts]
    offsets = np.zeros(len(blobs) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    return np.frombuffer(b"".join(blobs), dtype=np.uint8), offsets


def pack_stream(
    texts: list[str], *, bos: int, eos: int, byte_offset: int
) -> np.ndarray:
    """[bos] + utf8-bytes+offset + [eos] per doc, concatenated. int32."""
    lib = _get()
    if lib is None:  # Python fallback, identical semantics
        out: list[int] = []
        for t in texts:
            out.append(bos)
            out.extend(b + byte_offset for b in t.encode("utf-8"))
            out.append(eos)
        return np.asarray(out, dtype=np.int32)
    data, offsets = _concat_docs(texts)
    if len(data) == 0:
        data = np.zeros(1, dtype=np.uint8)  # ctypes needs a real pointer
    out = np.empty(int(lib.dp_stream_size(offsets, len(texts))), dtype=np.int32)
    n = lib.dp_pack_stream(
        data, offsets, len(texts), bos, eos, byte_offset, out, out.size
    )
    assert n == out.size, f"native pack wrote {n}, expected {out.size}"
    return out


def segments_positions(
    rows: np.ndarray, *, bos: int
) -> tuple[np.ndarray, np.ndarray]:
    """Per-row packed-document segment ids and restarting positions."""
    rows = np.ascontiguousarray(rows, dtype=np.int32)
    lib = _get()
    if lib is None:  # numpy fallback (same as the original loader code)
        is_bos = rows == bos
        segments = np.cumsum(is_bos, axis=1).astype(np.int32) + 1
        col = np.broadcast_to(np.arange(rows.shape[1]), rows.shape)
        last_bos = np.maximum.accumulate(np.where(is_bos, col, 0), axis=1)
        return segments, (col - last_bos).astype(np.int32)
    segments = np.empty_like(rows)
    positions = np.empty_like(rows)
    lib.dp_segments_positions(
        rows, rows.shape[0], rows.shape[1], bos, segments, positions
    )
    return segments, positions


def tokenize_padded(
    text: str, seq_len: int, *, bos: int, eos: int, pad: int, byte_offset: int
) -> tuple[np.ndarray, np.ndarray]:
    """One padded row + loss mask (the non-packed path)."""
    if seq_len < 2:
        raise ValueError(f"seq_len must be >= 2 (bos+eos), got {seq_len}")
    lib = _get()
    data = np.frombuffer(text.encode("utf-8"), dtype=np.uint8)
    if lib is None:
        ids = [bos] + [int(b) + byte_offset for b in data[: seq_len - 2]] + [eos]
        row = np.full(seq_len, pad, dtype=np.int32)
        row[: len(ids)] = ids
        mask = np.zeros(seq_len, dtype=np.float32)
        mask[: len(ids)] = 1.0
        return row, mask
    if len(data) == 0:
        data = np.zeros(1, dtype=np.uint8)
        n_bytes = 0
    else:
        n_bytes = len(data)
    row = np.empty(seq_len, dtype=np.int32)
    mask = np.empty(seq_len, dtype=np.float32)
    lib.dp_tokenize_padded(
        np.ascontiguousarray(data), n_bytes, seq_len, bos, eos, pad,
        byte_offset, row, mask,
    )
    return row, mask
