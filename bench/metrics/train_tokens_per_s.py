"""Training tokens completed in the window over the window's time (host
clock; every step's loss is read back, so the last step is blocked on)."""


def read(r):
    w = r.window
    if not w.get("steps"):
        return None
    return w["tokens"] / w["window_s"]
