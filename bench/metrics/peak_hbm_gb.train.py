"""peak_bytes_in_use of the fullest chip after the window, in GB (1e9)."""


def read(r):
    if not r.window.get("steps") or not r.memory_peak_bytes:
        return None
    return r.memory_peak_bytes / 1e9
