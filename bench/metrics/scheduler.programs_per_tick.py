"""scheduler.programs_per_tick: block programs (``decode_block`` calls)
per scheduler tick over the window, from the program's ServeMetrics
(difference of the snapshots at the window's edges). Nothing when the
program does not count block programs."""


def read(run):
    b, a = run.before, run.after
    if "block_programs" not in a or "ticks" not in a:
        return None
    ticks = a["ticks"] - b["ticks"]
    if ticks <= 0:
        return None
    return (a["block_programs"] - b["block_programs"]) / ticks
