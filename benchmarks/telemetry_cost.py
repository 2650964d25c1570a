"""Host cost of the serving path's recording (``repro.telemetry``).

    PYTHONPATH=src python -m benchmarks.telemetry_cost [--n 100000]

Times ``n`` empty spans (flat, and nested three deep), ``record`` calls and
``count`` calls, with no profiler session, inside a default one, and inside
one with its Python tracer off, and prints one JSON line of microseconds
per call.  The cost of a tick or a ``score``
call is these costs times the spans, records and counts it makes (the
``count`` of each span in ``telemetry.snapshot()`` over a run, divided by
its ticks or calls).
"""

from __future__ import annotations

import argparse
import json
import tempfile
import time

import jax

from repro import telemetry


def unit_costs(n: int) -> dict:
    """Microseconds per empty span, per span of a three-deep nest, per
    record and per count, each the best of three passes of ``n``."""
    rec = telemetry.Recorder()

    def flat():
        for _ in range(n):
            with rec.span("flat"):
                pass
        return n

    def nested():
        for _ in range(n // 3):
            with rec.span("outer"):
                with rec.span("middle"):
                    with rec.span("inner"):
                        pass
        return n // 3 * 3

    def records():
        for _ in range(n):
            rec.record("record", 1e-5)
        return n

    def counts():
        for _ in range(n):
            rec.count("count")
        return n

    out = {}
    for name, fn in (("span_us", flat), ("nested_span_us", nested),
                     ("record_us", records), ("count_us", counts)):
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            calls = fn()
            best = min(best, (time.perf_counter() - t0) / calls * 1e6)
        out[name] = best
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=100_000)
    args = ap.parse_args(argv)
    result = {"backend": jax.default_backend(),
              "profiler_off": unit_costs(args.n)}
    no_python = jax.profiler.ProfileOptions()
    no_python.python_tracer_level = 0
    # the default session also traces every Python call, which is most of
    # what a span costs under it
    for key, options in (("profiler_on", None),
                         ("profiler_on_no_python_tracer", no_python)):
        with tempfile.TemporaryDirectory() as log_dir:
            jax.profiler.start_trace(log_dir, profiler_options=options)
            try:
                result[key] = unit_costs(args.n)
            finally:
                jax.profiler.stop_trace()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
