"""Device memory at its high-water mark during a step: the bytes in use when
the window starts (parameters, optimizer state, tables, inputs) plus the
compiled step's temporaries, which the allocator's peak statistic misses."""


def read(m):
    if m.bytes_in_use_start is None:
        return None
    return (m.bytes_in_use_start + m.temp_bytes) / 2 ** 30
