"""Engine layer: median host milliseconds of a ``score`` call outside its
wait for the result (the program's ``engine.score_host``: the batch's
copy to the device, the dispatch, the conversion), time in which a caller
that waits for each call leaves the chip idle."""

from perfbench import telemetry


def read(run):
    us = telemetry.p50_us(run, "engine.score_host")
    return None if us is None else us * 1e-3
