"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps labelled by what the host was doing.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane, named by their HLO op (``lstm_stack_step.1``); host spans are the benchmark's own
``TraceAnnotation``s (names starting with ``pb.``) on any host thread.
Both sit on the profiler's one clock.  The functions below work on plain
``(name, start_ns, end_ns)`` tuples, so they are checked on a synthetic
trace without a chip.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

SPAN_PREFIX = "pb."
DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

Event = tuple  # (name, start_ns, end_ns)


def op_name(text: str) -> str:
    """An op event's own name: its HLO text up to `` = ``, without ``%``
    (``%lstm_stack_step.1 = (f32[...]) custom-call(...)`` ->
    ``lstm_stack_step.1``); the operands that follow may name other ops."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_kernel(name: str, kernel: str) -> bool:
    """``name`` is an instance of ``kernel`` (``kernel`` or ``kernel.N``)."""
    return name == kernel or name.startswith(kernel + ".")


def merge(intervals) -> list[tuple[float, float]]:
    """Union of ``(start, end)`` intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of ``[lo, hi]`` covered by the union of ``intervals``."""
    return sum(b - a for a, b in clip(merge(intervals), lo, hi))


def overlap(xs, ys) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals, in one pass over both."""
    tot, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            tot += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return tot


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of ``[lo, hi]`` left between busy intervals."""
    out, t = [], lo
    for a, b in clip(merge(busy), lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""

    device_ops: dict[int, list[Event]] = field(default_factory=dict)
    spans: list[Event] = field(default_factory=list)

    @classmethod
    def from_xplane(cls, path: Path) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(path))
        tr = cls()
        for plane in data.planes:
            if plane.name.startswith(DEVICE_PLANE_PREFIX):
                chip = int(plane.name[len(DEVICE_PLANE_PREFIX):].split()[0])
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        tr.device_ops[chip] = [
                            (op_name(ev.name), ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            tr.spans.append((ev.name, ev.start_ns,
                                             ev.start_ns + ev.duration_ns))
        return tr

    @classmethod
    def from_dir(cls, log_dir: Path) -> "Trace":
        found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_xplane(found[-1])

    # -- the window ---------------------------------------------------------

    def window(self, name: str = "pb.window") -> tuple[float, float]:
        """The measured window: the host span ``name``."""
        for n, a, b in self.spans:
            if n == name:
                return a, b
        raise LookupError(f"no span {name!r} in the trace")

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds in ``[lo, hi]`` in which an operation ran on the device,
        averaged over the chips that ran any."""
        chips = [ops for ops in self.device_ops.values() if ops]
        if not chips:
            return 0.0
        return sum(covered([(a, b) for _, a, b in ops], lo, hi)
                   for ops in chips) / len(chips) * 1e-9

    def kernel_s(self, kernel: str, lo: float, hi: float) -> float:
        """Device seconds of the instances of ``kernel``, clipped to
        ``[lo, hi]``, summed over chips."""
        return sum(
            b - a
            for ops in self.device_ops.values()
            for name, a, b in clip_events(ops, lo, hi)
            if is_kernel(name, kernel)
        ) * 1e-9

    def kernel_count(self, kernel: str, lo: float, hi: float) -> int:
        return sum(
            1 for ops in self.device_ops.values()
            for name, _, _ in clip_events(ops, lo, hi)
            if is_kernel(name, kernel))

    def top_ops(self, lo: float, hi: float, k: int = 10):
        """The ``k`` device operations that took the most time, by name."""
        tot: dict[str, float] = defaultdict(float)
        for ops in self.device_ops.values():
            for name, a, b in clip_events(ops, lo, hi):
                tot[name] += (b - a) * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]

    def idle_by_span(self, lo: float, hi: float, k: int = 10):
        """Device idle seconds in ``[lo, hi]`` (chip 0), by the host span
        that overlapped them: for each span name, the idle time during
        which a span of that name was open; ``(no span)`` is idle time
        during which none was.  Largest ``k`` first."""
        ops = self.device_ops.get(min(self.device_ops), []) \
            if self.device_ops else []
        idle = gaps([(a, b) for _, a, b in ops], lo, hi)
        by_name: dict[str, list] = defaultdict(list)
        for name, a, b in self.spans:
            if name != "pb.window":
                by_name[name].append((a, b))
        out = {}
        for name, iv in by_name.items():
            tot = overlap(idle, merge(iv))
            if tot > 0:
                out[name] = tot * 1e-9
        every = merge(iv for ivs in by_name.values() for iv in ivs)
        none = sum(b - a for a, b in idle) - overlap(idle, every)
        if none > 0:
            out["(no span)"] = none * 1e-9
        return sorted(out.items(), key=lambda kv: -kv[1])[:k]


def clip_events(ops, lo: float, hi: float):
    for name, a, b in ops:
        a2, b2 = max(a, lo), min(b, hi)
        if b2 > a2:
            yield name, a2, b2
