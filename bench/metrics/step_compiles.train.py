"""Backend compiles (or persistent-cache loads) of the jitted
``train_step`` since ``make_train_step`` last ran, from ``repro.obs``: 1
means the step the run built was never compiled twice. None where the
program keeps no such record."""


def read(r):
    try:
        from repro import obs
    except ImportError:
        return None
    rec = obs.snapshot()["last_build"].get("train_step")
    return None if rec is None else rec["compiles"]
