"""The page wire checksum (core/paging): its chunked definition, that it
reads every byte in place, and that any single-bit flip is caught.

A param's wire checksum is the CRC32 over the 4-byte little-endian CRC32s
of the consecutive ``CRC_CHUNK_BYTES`` chunks of its payload, then of its
scales.  The tests shrink the chunk so that the buffers stay small.
"""

import tracemalloc
import zlib

import numpy as np
import pytest

from repro.core import paging
from repro.core.paging import HostParam, page_crc, page_crc_of_buffers

CHUNK = 64 << 10


def _by_definition(payload: np.ndarray, scales: np.ndarray,
                   chunk: int) -> int:
    words = b"".join(zlib.crc32(b[i:i + chunk]).to_bytes(4, "little")
                     for b in (payload.tobytes(), scales.tobytes())
                     for i in range(0, len(b), chunk))
    return zlib.crc32(words)


def _host_param(payload: np.ndarray, scales: np.ndarray) -> HostParam:
    hp = HostParam(bits=8, orig_shape=payload.shape,
                   packed_shape=payload.shape, scale_shape=scales.shape,
                   page_bits=None, payload=payload, scales=scales)
    hp.crc32 = hp.wire_crc()
    return hp


def _flip(buf: np.ndarray, byte: int, bit: int = 0) -> np.ndarray:
    out = buf.copy()
    out.reshape(-1).view(np.uint8)[byte] ^= 1 << bit
    return out


# (payload shape, scales shape): int8 stacked carriers [layers, rows, cols]
# and 2-D float32 per-channel scales, below / at / above one chunk
CASES = {
    "payload_below_chunk": ((2, 16, 96), (2, 16)),
    "payload_one_chunk": ((4, 128, 128), (4, 128)),
    "payload_three_chunks": ((3, 256, 256), (3, 256)),
    "payload_not_chunk_multiple": ((5, 300, 211), (5, 300)),
    "scales_above_chunk": ((2, 64, 64), (80, 300)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_wire_checksum_definition_and_bit_flips(case, monkeypatch):
    monkeypatch.setattr(paging, "CRC_CHUNK_BYTES", CHUNK)
    p_shape, s_shape = CASES[case]
    rng = np.random.default_rng(sum(p_shape) + sum(s_shape))
    payload = rng.integers(-128, 128, p_shape, dtype=np.int8)
    scales = rng.random(s_shape, dtype=np.float32)
    hp = _host_param(payload, scales)
    crc = hp.crc32
    assert crc == _by_definition(payload, scales, CHUNK)

    # a non-contiguous view reads as its contiguous copy
    wide = np.zeros(p_shape[:-1] + (2 * p_shape[-1],), np.int8)
    wide[..., ::2] = payload
    view = wide[..., ::2]
    assert not view.flags.c_contiguous
    assert hp.wire_crc(view, scales) == crc

    # one flipped bit anywhere is caught, by the param and by its page
    n = payload.nbytes
    where = {"first_byte": 0,
             "first_byte_of_last_chunk": (n - 1) // CHUNK * CHUNK,
             "middle_chunk": n // CHUNK // 2 * CHUNK + min(n, CHUNK) // 2,
             "last_byte": n - 1}
    expected = page_crc([hp])
    for byte in where.values():
        flipped = _flip(payload, byte, bit=byte % 8)
        assert hp.wire_crc(flipped, scales) != crc
        assert page_crc_of_buffers([("w", hp, flipped, scales)]) != expected
    for byte in (0, scales.nbytes - 1):
        flipped = _flip(scales, byte, bit=7)
        assert hp.wire_crc(payload, flipped) != crc
        assert page_crc_of_buffers([("w", hp, payload, flipped)]) != expected
    assert page_crc_of_buffers([("w", hp, payload, scales)]) == expected


def test_page_verify_copies_no_wire_bytes():
    """Verifying a 64 MB page, split over the pool, allocates well under
    1 % of its bytes at peak: no ``tobytes`` and no contiguous copy."""
    rng = np.random.default_rng(0)
    wire = []
    for i in range(2):
        payload = rng.integers(-128, 128, (8, 1024, 4096), dtype=np.int8)
        scales = rng.random((8, 1024), dtype=np.float32)
        wire.append((f"w{i}", _host_param(payload, scales), payload, scales))
    nbytes = sum(p.nbytes + s.nbytes for _n, _hp, p, s in wire)
    assert nbytes > 64 << 20
    expected = page_crc([hp for _n, hp, _p, _s in wire])
    tracemalloc.start()
    try:
        got = page_crc_of_buffers(wire)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert got == expected
    assert peak < nbytes // 100
