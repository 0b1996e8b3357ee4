"""Host scheduler loop: seconds the interpreter's cyclic garbage collector
ran inside the window's waves (all generations, `gc.callbacks`, traced runs
only) over the summed wave time. The collector stops every thread of the
process; its full collections walk the whole heap the scheduler keeps."""


def read(obs):
    gc, w = obs.get("gc"), obs.get("window", {})
    if not gc or not w.get("wave_s"):
        return None
    return 100.0 * gc["gc_s"] / w["wave_s"]
