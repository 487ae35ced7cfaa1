"""From the profiler's trace of a window to device times.

``read`` takes the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two kinds of interval, all on the profiler's one clock in nanoseconds:

* ``ops`` — every operation that ran on a chip (the ``XLA Ops`` line of
  each ``/device:TPU:<n>`` plane): ``(device, name, start, end)``;
* ``modules`` — every run of a compiled program (the ``XLA Modules``
  line): ``(device, name, start, end)``, named by the plan of the batch
  it served where the runs and the window's batches pair up one to one;
* ``marks`` — the host's annotations: ``window`` round the measured
  window and ``exec:<plan>`` round each batch's call into the executor.

The rest reduces those intervals: the busy time (the union of the ops'
intervals, averaged over the chips used), kernel time by name, and the
idle gaps named by what the host was doing in them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class DeviceTrace:
    ops: list = field(default_factory=list)  # (device, name, start_ns, end_ns)
    marks: list = field(default_factory=list)  # (name, start_ns, end_ns)
    modules: list = field(default_factory=list)  # (device, name, start_ns, end_ns)

    @property
    def window(self) -> tuple[int, int]:
        """The ``window`` mark, else the span of the ops."""
        for name, t0, t1 in self.marks:
            if name == "window":
                return t0, t1
        if not self.ops:
            return 0, 0
        return min(o[2] for o in self.ops), max(o[3] for o in self.ops)

    @property
    def window_s(self) -> float:
        t0, t1 = self.window
        return (t1 - t0) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some op ran, inside the window, averaged over
        the chips that ran any."""
        devices = sorted({o[0] for o in self.ops})
        if not devices:
            return 0.0
        t0, t1 = self.window
        total = 0
        for d in devices:
            total += sum(b - a for a, b in clip(merge(
                [(o[2], o[3]) for o in self.ops if o[0] == d]), t0, t1))
        return total * 1e-9 / len(devices)

    def name_modules(self, labels: list) -> None:
        """Name chip 0's program runs in the window by ``labels`` (the plan
        of each batch, in order) where the counts agree."""
        t0, t1 = self.window
        inside = [i for i, m in enumerate(self.modules)
                  if m[0] == 0 and m[3] > t0 and m[2] < t1]
        if len(inside) == len(labels):
            for i, label in zip(inside, labels):
                d, _, a, b = self.modules[i]
                self.modules[i] = (d, label, a, b)

    def to_json(self) -> dict:
        return {k: [list(x) for x in getattr(self, k)] for k in ("ops", "marks", "modules")}

    @classmethod
    def from_json(cls, d: dict) -> "DeviceTrace":
        return cls(*([tuple(x) for x in d.get(k, [])] for k in ("ops", "marks", "modules")))


def merge(intervals) -> list[tuple[int, int]]:
    """The union of ``(start, end)`` intervals, as sorted disjoint ones."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals if b > t0 and a < t1]


def read(log_dir: str) -> DeviceTrace:
    """The ops and marks of the one trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profiler trace, found {paths}")
    data = ProfileData.from_file(paths[0])
    trace = DeviceTrace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                dev = int(m.group(1))
                out = trace.ops if line.name == OPS_LINE else trace.modules
                out += [
                    (dev, ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                ]
            elif plane.name.startswith("/host:"):
                trace.marks += [
                    (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                    for ev in line.events
                    if ev.name == "window" or ev.name.startswith("exec:")
                ]
    return trace


def short_name(op: str) -> str:
    """``%fusion.64 = f32[...] fusion(...)`` → ``fusion.64``; a Pallas
    kernel's custom call gets `` (tpu_custom_call)`` after its name."""
    name = op.split(" = ", 1)[0].lstrip("%")
    return name + " (tpu_custom_call)" if "tpu_custom_call" in op else name


def _module_of(trace: DeviceTrace, dev: int):
    """A function from a time on chip ``dev`` to the name of the program
    running then (``""`` outside every program)."""
    mods = sorted((a, b, n) for d, n, a, b in trace.modules if d == dev)
    starts = [a for a, _, _ in mods]

    def at(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return mods[i][2] if i >= 0 and t < mods[i][1] else ""

    return at


def self_times(trace: DeviceTrace) -> dict[str, int]:
    """Nanoseconds each op ran inside the window, less the ops nested in it
    (a ``while`` op's event spans its body's ops), by ``<program>/<op>``."""
    t0, t1 = trace.window
    out: dict[str, int] = {}
    for d in sorted({o[0] for o in trace.ops}):
        module = _module_of(trace, d)
        evs = sorted((o for o in trace.ops if o[0] == d), key=lambda o: (o[2], -o[3]))
        stack: list[list] = []  # [end, name, start, nested ns]

        def pop():
            end, name, a, child = stack.pop()
            span = max(min(end, t1) - max(a, t0), 0)
            out[name] = out.get(name, 0) + max(span - child, 0)
            if stack:
                stack[-1][3] += span

        for _, name, a, b in evs:
            while stack and stack[-1][0] <= a:
                pop()
            prog = module(a)
            key = f"{prog}/{short_name(name)}" if prog else short_name(name)
            stack.append([b, key, a, 0])
        while stack:
            pop()
    return out


def kernel_seconds(trace: DeviceTrace, pattern: str, program: str = "") -> tuple[float, int]:
    """Summed device seconds and count of the ops whose name matches
    ``pattern``, inside the window and, where ``program`` is given, inside
    the program runs whose name starts with it."""
    t0, t1 = trace.window
    rx = re.compile(pattern)
    hits = [o for o in trace.ops if rx.search(o[1]) and o[3] > t0 and o[2] < t1]
    if program:
        module = {d: _module_of(trace, d) for d in {o[0] for o in hits}}
        hits = [o for o in hits if module[o[0]](o[2]).startswith(program)]
    return sum(min(o[3], t1) - max(o[2], t0) for o in hits) * 1e-9, len(hits)


def idle_gaps(trace: DeviceTrace) -> list[tuple[str, int, int]]:
    """Every gap in the window in which no op ran on chip 0, named by what
    was going on: ``in <program>`` between the ops of one program run,
    ``exec:<plan>`` where the host was inside a batch's call into the
    executor, ``server`` elsewhere (planning, batching, cache, reading the
    answers back)."""
    t0, t1 = trace.window
    busy = clip(merge([(o[2], o[3]) for o in trace.ops if o[0] == 0]), t0, t1)
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    execs = sorted((a, b, n) for n, a, b in trace.marks if n.startswith("exec:"))
    module = _module_of(trace, 0)
    out = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        prog = module(mid)
        if prog:
            name = f"in {prog}"
        else:
            name = next((n for s, e, n in execs if s <= mid < e), "server")
        out.append((name, a, b))
    return out


def breakdown(trace: DeviceTrace, top: int = 10) -> dict:
    """The ops that took most device time (their own time, not their nested
    ops'), and the idle time by what the host was doing, each at most
    ``top`` entries of ``[name, seconds]``."""
    by_op = self_times(trace)
    by_gap: dict[str, list[int]] = {}
    for name, a, b in idle_gaps(trace):
        acc = by_gap.setdefault(name, [0, 0])
        acc[0] += b - a
        acc[1] += 1
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(by_gap.items(), key=lambda kv: -kv[1][0])[:top]
    return {
        "device_ops": [[n, ns * 1e-9] for n, ns in ops],
        "idle_gaps": [[f"{n} ({c} gaps)", ns * 1e-9] for n, (ns, c) in gaps],
    }
