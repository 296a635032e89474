"""1 - (union of device op intervals) / (traced window), in %, averaged
over the cell's chips."""


def read(r):
    if r.trace is None or not r.window.get("steps"):
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)
