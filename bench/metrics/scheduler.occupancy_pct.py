"""scheduler.occupancy_pct: live rows over max_slots, time-weighted over
the window, from the program's ServeMetrics (difference of the
snapshots at the window's edges)."""


def read(run):
    b, a = run.before, run.after
    wall = a["wall_time_s"] - b["wall_time_s"]
    if wall <= 0:
        return None
    occ = (a["mean_occupancy"] * a["wall_time_s"]
           - b["mean_occupancy"] * b["wall_time_s"])
    return 100.0 * occ / wall
