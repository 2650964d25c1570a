"""Server layer: mean real streams per scheduler tick in the window, from
the program's ``ServerStats.batch_fill`` counter (pad streams excluded)."""


def read(run):
    ticks = run.counts.get("ticks")
    if not ticks:
        return None
    return run.counts["real_streams_per_tick"] / ticks
