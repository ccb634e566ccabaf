"""hbm_peak_gb: on the fullest chip, peak_bytes_in_use plus
peak_bytes_reserved from memory_stats() after the window, in GB.
Executables' temporaries (such as a relayout copy of the corpus) are
reserved apart from live buffers, so the sum is the true peak."""


def read(rec):
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in rec.memory]
    return max(peaks) / 1e9 if any(peaks) else None
