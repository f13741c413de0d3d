"""Front end / scheduler: live rows over slots across the window's decode
steps, in %, from the scheduler's counters (``SchedulerStats.steps`` and
``slot_steps_active``, summed over the pools of the window)."""


def read(ctx):
    c0, c1 = ctx.counters
    steps = c1["steps"] - c0["steps"]
    if steps <= 0:
        return None
    live = c1["slot_steps_active"] - c0["slot_steps_active"]
    return 100.0 * live / (steps * ctx.slots)
