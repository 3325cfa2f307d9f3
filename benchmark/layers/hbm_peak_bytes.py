"""hbm_peak_bytes: memory_stats()["peak_bytes_in_use"] of the fullest
chip after the window."""


def read(run):
    return run["device"].get("memory_peak_bytes")
