"""Device: peak bytes in use (``memory_stats()["peak_bytes_in_use"]``)
after the window on the fullest of the cell's chips, in GB (1e9 bytes).
Headroom: past the chip's 16 GB the spill path sets the time."""


def read(run):
    if not run.peak_bytes:
        return None
    return max(run.peak_bytes) / 1e9
