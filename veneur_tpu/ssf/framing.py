"""SSF stream framing: length-prefixed protobuf frames over TCP/UNIX.

Parity: protocol/wire.go (sym: protocol.ReadSSF, protocol.WriteSSF,
protocol.Message, protocol.ValidateTrace). PROVENANCE: frame layout from
prior knowledge of the reference (empty mount — see SURVEY.md): one
version byte, then a little-endian uint32 payload length, then the
SSFSpan protobuf; re-verify the byte layout before claiming wire interop
with an existing deployment.

Robustness contract (mirrors the reference's): a frame that is
oversized, truncated, or fails protobuf decoding raises a framing error
the caller can distinguish from connection EOF, so one bad client cannot
wedge a listener.
"""

from __future__ import annotations

import struct

from .protos import ssf_pb2

# The frame layout, written once: native/vtpu_ingest.cpp reads framed
# streams itself and holds the same four (kSsfFrameVersion,
# kSsfFrameLengthBytes, kSsfFrameLengthLittleEndian, kSsfMaxFrameLength;
# vlint NA03 keeps each pair equal).
VERSION_BYTE = 0x00
LENGTH_BYTES = 4
LENGTH_LITTLE_ENDIAN = 1
_LEN = struct.Struct(("<" if LENGTH_LITTLE_ENDIAN else ">")
                     + {2: "H", 4: "I", 8: "Q"}[LENGTH_BYTES])

# Defensive bound mirroring the reference's refusal to allocate
# attacker-controlled buffer sizes.
MAX_FRAME_LENGTH = 16 * 1024 * 1024

# Parity boundary shared with native/vtpu_ingest.cpp (kPbSkipMaxDepth,
# enforced by vlint NA02): the native parser skips unknown-field groups
# only to this nesting depth — anything deeper falls back to THIS
# module's decoder (the google.protobuf runtime, whose own recursion
# limit is far larger), so the two paths accept the same datagrams.
PB_SKIP_MAX_DEPTH = 16


class FramingError(ValueError):
    """Bad frame (version, length, or protobuf decode)."""


def write_ssf(span: ssf_pb2.SSFSpan) -> bytes:
    """Encode one span as a stream frame (protocol.WriteSSF)."""
    payload = span.SerializeToString()
    return bytes([VERSION_BYTE]) + _LEN.pack(len(payload)) + payload


def _read_exact(read, n: int) -> bytes:
    """Read exactly n bytes from `read` (a socket-style or file-style
    callable is normalised by read_ssf); b'' mid-message = truncation."""
    chunks = []
    got = 0
    while got < n:
        c = read(n - got)
        if not c:
            raise EOFError(f"stream closed mid-frame ({got}/{n} bytes)")
        chunks.append(c)
        got += len(c)
    return b"".join(chunks)


def read_ssf_frame(stream) -> bytes | None:
    """Read one frame's raw protobuf payload (for consumers with their
    own decoder, e.g. the native span fast path). Returns None on clean
    EOF; raises FramingError on a corrupt frame and EOFError on
    truncation mid-frame."""
    read = stream.recv if hasattr(stream, "recv") else stream.read
    first = read(1)
    if not first:
        return None
    if first[0] != VERSION_BYTE:
        raise FramingError(f"unknown SSF frame version {first[0]:#x}")
    (length,) = _LEN.unpack(_read_exact(read, LENGTH_BYTES))
    if length > MAX_FRAME_LENGTH:
        raise FramingError(f"frame length {length} exceeds max "
                           f"{MAX_FRAME_LENGTH}")
    return _read_exact(read, length)


def read_ssf(stream) -> ssf_pb2.SSFSpan | None:
    """Read one framed span (protocol.ReadSSF). Returns None on clean
    EOF (closed between frames); raises FramingError on a corrupt frame
    and EOFError on truncation mid-frame."""
    payload = read_ssf_frame(stream)
    if payload is None:
        return None
    try:
        return ssf_pb2.SSFSpan.FromString(payload)
    except Exception as e:
        raise FramingError(f"bad SSF protobuf payload: {e}") from e


def parse_ssf_datagram(data: bytes) -> ssf_pb2.SSFSpan:
    """UDP SSF: the datagram is a bare SSFSpan protobuf, no framing
    (Server.ReadSSFPacketSocket)."""
    try:
        return ssf_pb2.SSFSpan.FromString(data)
    except Exception as e:
        raise FramingError(f"bad SSF datagram: {e}") from e


def validate_trace(span: ssf_pb2.SSFSpan) -> bool:
    """Is this span a *trace* span (id + start/end present), as opposed
    to a bare metrics carrier (protocol.ValidateTrace)?"""
    return bool(span.id and span.start_timestamp and span.end_timestamp
                and span.name)
