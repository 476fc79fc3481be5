"""Share of the HBM roofline: the bytes the engine iterations of the traced
slice must move (``bench/roofline.py``, from the instances' logical sizes)
over the device busy time inside the ``solve`` spans times the chip's HBM
bandwidth (``bench/peaks.py``).  The busy time holds more than the engine,
so this is a lower bound on the engine's own share."""


def read(run):
    from bench.roofline import iteration_bytes

    if run.trace is None or run.peaks is None:
        return None
    busy = run.trace["span_busy_s"].get("solve", 0.0)
    moved = 0.0
    for r in run.traced:
        for (_, _, key), iters in zip(r.answers, r.engine_iters):
            inst = run.instances[key]
            moved += iters * iteration_bytes(inst, run.part(inst))
    if not busy or not moved:
        return None
    return 100.0 * moved / (busy * run.peaks.hbm_bw)
