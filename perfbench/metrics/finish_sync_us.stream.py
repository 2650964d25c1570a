"""Engine layer: median microseconds the host waits for a finished
window's scores (the program's ``engine.finish_sync``: the decode on the
device and the copy back)."""

from perfbench import telemetry


def read(run):
    return telemetry.p50_us(run, "engine.finish_sync")
