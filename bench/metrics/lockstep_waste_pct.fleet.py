"""Share of the batched driver's lockstep engine work that no instance
needed: 1 - sum_i iters_i / (B * max_i iters_i) per ``solve_many`` call,
pooled over the calls of the window (B: the real instances of a call)."""


def read(run):
    used = slots = 0
    for r in run.requests:
        if r.engine_iters:
            used += sum(r.engine_iters)
            slots += len(r.engine_iters) * max(r.engine_iters)
    return 100.0 * (1.0 - used / slots) if slots else None
