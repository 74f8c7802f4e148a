"""Snappy codec bindings + the eth2 framed/raw compression layers.

Reference: @chainsafe/snappy-stream (reqresp ssz_snappy framing) and
snappyjs (gossip raw-block compression) — SURVEY.md §2.3.  The codec
itself is native (lodestar_tpu/native/snappy.cpp, ctypes ABI); this
module adds:

  - compress/decompress: raw snappy blocks (gossip messages),
  - frame_compress/frame_decompress: the snappy FRAMED format
    (stream identifier + compressed/uncompressed chunks with masked
    crc32c) used by reqresp ssz_snappy payloads,
  - encode_reqresp_chunk/decode_reqresp_chunk: <ssz-len varint> +
    framed body (reference: reqresp/encoders).
"""

from __future__ import annotations

import ctypes
import struct
from typing import Optional, Tuple

from ..native import build

_LIB_PATH = build("libsnappy_tpu.so")

_lib: Optional[ctypes.CDLL] = None
if _LIB_PATH is not None:
    try:
        _lib = ctypes.CDLL(_LIB_PATH)
        _lib.snappy_compress.restype = ctypes.c_size_t
        _lib.snappy_compress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p
        ]
        _lib.snappy_decompress.restype = ctypes.c_size_t
        _lib.snappy_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t
        ]
        _lib.snappy_uncompressed_length.restype = ctypes.c_size_t
        _lib.snappy_uncompressed_length.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t
        ]
        _lib.snappy_max_compressed_length.restype = ctypes.c_size_t
        _lib.snappy_max_compressed_length.argtypes = [ctypes.c_size_t]
        _lib.snappy_crc32c.restype = ctypes.c_uint32
        _lib.snappy_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_size_t]
    except OSError:  # pragma: no cover
        _lib = None


def native_available() -> bool:
    return _lib is not None


class SnappyError(ValueError):
    pass


def compress(data: bytes) -> bytes:
    """Raw snappy block (the gossip message codec)."""
    if _lib is None:
        raise SnappyError("libsnappy_tpu.so not built")
    out = ctypes.create_string_buffer(
        _lib.snappy_max_compressed_length(len(data))
    )
    n = _lib.snappy_compress(data, len(data), out)
    return out.raw[:n]


def decompress(data: bytes, max_len: int = 1 << 27) -> bytes:
    if _lib is None:
        raise SnappyError("libsnappy_tpu.so not built")
    size = _lib.snappy_uncompressed_length(data, len(data))
    if size == ctypes.c_size_t(-1).value or size > max_len:
        raise SnappyError("malformed or oversized snappy block")
    out = ctypes.create_string_buffer(max(size, 1))
    n = _lib.snappy_decompress(data, len(data), out, size)
    if n == ctypes.c_size_t(-1).value:
        raise SnappyError("malformed snappy block")
    return out.raw[:n]


def crc32c(data: bytes) -> int:
    if _lib is None:
        raise SnappyError("libsnappy_tpu.so not built")
    return _lib.snappy_crc32c(data, len(data))


def _masked_crc(data: bytes) -> int:
    """Framing-format checksum mask: rotr15(crc) + 0xa282ead8."""
    c = crc32c(data)
    return ((((c >> 15) | (c << 17)) & 0xFFFFFFFF) + 0xA282EAD8) % (1 << 32)


# -- framed format (reqresp ssz_snappy payload body) ------------------------

_STREAM_ID = b"\xff\x06\x00\x00sNaPpY"
_CHUNK_COMPRESSED = 0x00
_CHUNK_UNCOMPRESSED = 0x01
_MAX_CHUNK = 65536


def frame_compress(data: bytes) -> bytes:
    out = bytearray(_STREAM_ID)
    for i in range(0, max(len(data), 1), _MAX_CHUNK):
        chunk = data[i : i + _MAX_CHUNK]
        crc = _masked_crc(chunk)
        comp = compress(chunk)
        if len(comp) < len(chunk):
            body = struct.pack("<I", crc) + comp
            out += bytes([_CHUNK_COMPRESSED]) + struct.pack(
                "<I", len(body)
            )[:3] + body
        else:
            body = struct.pack("<I", crc) + chunk
            out += bytes([_CHUNK_UNCOMPRESSED]) + struct.pack(
                "<I", len(body)
            )[:3] + body
        if not data:
            break
    return bytes(out)


def frame_decompress(data: bytes) -> bytes:
    if not data.startswith(_STREAM_ID):
        raise SnappyError("missing snappy stream identifier")
    pos = len(_STREAM_ID)
    out = bytearray()
    while pos < len(data):
        if pos + 4 > len(data):
            raise SnappyError("truncated chunk header")
        ctype = data[pos]
        length = int.from_bytes(data[pos + 1 : pos + 4], "little")
        pos += 4
        if pos + length > len(data):
            raise SnappyError("truncated chunk body")
        body = data[pos : pos + length]
        pos += length
        if ctype in (_CHUNK_COMPRESSED, _CHUNK_UNCOMPRESSED):
            if length < 4:
                raise SnappyError("chunk too short for checksum")
            (crc,) = struct.unpack("<I", body[:4])
            payload = body[4:]
            chunk = (
                decompress(payload)
                if ctype == _CHUNK_COMPRESSED
                else payload
            )
            if _masked_crc(chunk) != crc:
                raise SnappyError("chunk checksum mismatch")
            out += chunk
        elif 0x80 <= ctype <= 0xFE:
            continue  # skippable padding chunks
        else:
            raise SnappyError(f"unknown chunk type {ctype:#x}")
    return bytes(out)


# -- reqresp ssz_snappy chunk (reference: reqresp/encoders/sszSnappy) -------


def _uvarint(v: int) -> bytes:
    out = bytearray()
    while v >= 0x80:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _read_uvarint(data: bytes, pos: int) -> Tuple[int, int]:
    v = 0
    shift = 0
    while pos < len(data):
        b = data[pos]
        pos += 1
        v |= (b & 0x7F) << shift
        if not (b & 0x80):
            return v, pos
        shift += 7
    raise SnappyError("truncated varint")


def encode_reqresp_chunk(ssz_bytes: bytes) -> bytes:
    """<ssz length varint> + framed-snappy body."""
    return _uvarint(len(ssz_bytes)) + frame_compress(ssz_bytes)


def decode_reqresp_chunk(data: bytes, max_len: int = 1 << 27) -> bytes:
    """One chunk filling the whole buffer (delegates to the positional
    decoder so there is exactly ONE frame-parsing state machine)."""
    payload, pos = decode_reqresp_chunk_at(data, 0, max_len)
    if pos != len(data):
        raise SnappyError(f"{len(data) - pos} trailing bytes after chunk")
    return payload


def decode_reqresp_chunk_at(
    data: bytes, start: int, max_len: int = 1 << 27
) -> Tuple[bytes, int]:
    """Decode ONE ssz_snappy chunk out of a concatenated response stream
    (reference: response/responseDecode.ts reads chunk-by-chunk).

    Decompresses snappy frames until the declared ssz length is reached;
    returns (payload, next_position)."""
    declared, pos = _read_uvarint(data, start)
    if declared > max_len:
        raise SnappyError("declared length over limit")
    if data[pos : pos + len(_STREAM_ID)] != _STREAM_ID:
        raise SnappyError("missing snappy stream identifier")
    pos += len(_STREAM_ID)
    out = bytearray()
    data_frames = 0
    # declared == 0 still carries ONE (empty) DATA frame — consume it so
    # the stream position stays aligned for the next chunk (padding and
    # repeated stream-identifier frames do not count)
    while len(out) < declared or (declared == 0 and data_frames == 0):
        if pos + 4 > len(data):
            raise SnappyError("truncated chunk header")
        ctype = data[pos]
        length = int.from_bytes(data[pos + 1 : pos + 4], "little")
        pos += 4
        if pos + length > len(data):
            raise SnappyError("truncated chunk body")
        body = data[pos : pos + length]
        pos += length
        if ctype in (_CHUNK_COMPRESSED, _CHUNK_UNCOMPRESSED):
            data_frames += 1
            if length < 4:
                raise SnappyError("chunk too short for checksum")
            (crc,) = struct.unpack("<I", body[:4])
            payload = body[4:]
            chunk = (
                decompress(payload)
                if ctype == _CHUNK_COMPRESSED
                else payload
            )
            if _masked_crc(chunk) != crc:
                raise SnappyError("chunk checksum mismatch")
            out += chunk
        elif ctype == 0xFF:
            # repeated stream identifier: legal anywhere in a stream
            if body != _STREAM_ID[4:]:
                raise SnappyError("bad repeated stream identifier")
        elif 0x80 <= ctype <= 0xFE:
            continue
        else:
            raise SnappyError(f"unknown chunk type {ctype:#x}")
    if len(out) != declared:
        raise SnappyError(
            f"length mismatch: declared {declared}, got {len(out)}"
        )
    return bytes(out), pos
