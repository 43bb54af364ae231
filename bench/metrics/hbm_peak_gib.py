"""Device memory at its high-water mark during a step, on the fullest chip:
the bytes in use when the window starts (parameters, optimizer state,
tables, inputs) plus the chip's compiled step's temporaries, which the
allocator's peak statistic misses."""


def read(m):
    if any(b is None for b in m.bytes_in_use_start):
        return None
    return max(b + t for b, t in zip(m.bytes_in_use_start, m.temp_bytes)) \
        / 2 ** 30
