"""Engine layer: mean host milliseconds per ``push_many`` call in the
window, from the benchmark's own span around each call (the engine call
returns after the device work of any window it completes)."""


def read(run):
    calls = run.counts.get("push_many_calls")
    if not calls:
        return None
    return run.counts["push_many_s"] / calls * 1e3
