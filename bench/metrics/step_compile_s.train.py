"""Seconds the program spent making its train step runnable: tracing,
lowering to MLIR, and the backend compile or persistent-cache load of the
jitted ``train_step``, from ``repro.obs``'s compile record of its latest
build (the events since ``make_train_step`` last ran). None where the
program keeps no such record or the step never compiled."""


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    rec = obs.snapshot()["last_build"].get("train_step")
    if not rec or not rec["compiles"]:
        return None
    return rec["trace_s"] + rec["lower_s"] + rec["compile_s"]
