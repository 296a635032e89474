"""Host time the program's data loader takes per batch, in ms: total over
count of the program's ``smlt.data.next_batch`` span (``repro.obs``), over
every batch of the run (set-up, window and traced steps). None where the
program keeps no such span."""


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    s = obs.snapshot()["spans"].get("smlt.data.next_batch")
    if not s or not s["count"]:
        return None
    return 1000.0 * s["total_s"] / s["count"]
