"""Reduce a JAX profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` a ``jax.profiler`` trace writes, with
nothing but JAX, into two lists of events on one clock (seconds):

* device ops: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane,
  named by their HLO instruction (a Pallas kernel by its jitted wrapper:
  ``decode_attention_pallas.5``); a ``while`` op spans its body's ops;
* host spans: every line of the ``/host:CPU`` plane, the harness's own
  ``TraceAnnotation`` spans and JAX's dispatch spans among them.

The reduction is plain interval arithmetic on those lists, so a test can
hand it a synthetic trace.
"""
from __future__ import annotations

import glob
import heapq
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


#: device ops that only contain other ops (their bodies are traced too)
CONTAINERS = ("while", "conditional", "call")


@dataclass(frozen=True)
class Event:
    name: str  # a device op's HLO instruction name, e.g. "fusion.12"
    start: float
    end: float
    where: str  # device plane, or host line

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def base(self) -> str:
        """The name without its numeric suffix ("fusion.12" -> "fusion")."""
        head, _, tail = self.name.rpartition(".")
        return head if head and tail.isdigit() else self.name


def op_name(text: str) -> str:
    """An XLA op event's instruction name from its HLO text
    ('%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12')."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


@dataclass
class Trace:
    device_ops: list[Event]
    host: list[Event] = field(default_factory=list)

    @property
    def devices(self) -> list[str]:
        return sorted({e.where for e in self.device_ops})

    def spans(self, name: str) -> list[tuple[float, float]]:
        """Host spans called ``name`` (the harness's annotations)."""
        return sorted((e.start, e.end) for e in self.host if e.name == name)

    def window(self, name: str) -> tuple[float, float]:
        """From the first ``name`` span's start to the last one's end."""
        s = self.spans(name)
        if not s:
            raise ValueError(f"no {name!r} span in the trace")
        return s[0][0], max(e for _, e in s)

    def _busy(self, device: str, t0: float, t1: float):
        return _union(_clip([(e.start, e.end) for e in self.device_ops
                             if e.where == device], t0, t1))

    def busy_s(self, t0: float, t1: float) -> float:
        """Seconds of [t0, t1] in which some op ran, averaged over the
        devices."""
        devs = self.devices
        if not devs:
            return 0.0
        return sum(sum(e - s for s, e in self._busy(d, t0, t1))
                   for d in devs) / len(devs)

    def idle_within(self, spans) -> float:
        """Device idle seconds inside the given host spans (first device)."""
        if not self.devices:
            return 0.0
        dev = self.devices[0]
        total = 0.0
        for s, e in spans:
            total += (e - s) - sum(b - a for a, b in self._busy(dev, s, e))
        return total

    def kernel_s(self, names, t0: float, t1: float) -> float:
        """Summed device time, inside [t0, t1], of the ops named one of
        ``names`` (any numeric suffix: a kernel's every call)."""
        return sum(min(e.end, t1) - max(e.start, t0)
                   for e in self.device_ops
                   if e.end > t0 and e.start < t1 and e.base in names)

    def top_ops(self, t0: float, t1: float, n: int = 10):
        """The ``n`` ops that took most device time inside [t0, t1], by
        name without suffix, loops and calls left out (their bodies'
        ops are counted), with seconds."""
        tot: dict[str, float] = {}
        for e in self.device_ops:
            if e.end > t0 and e.start < t1 and e.base not in CONTAINERS:
                tot[e.base] = tot.get(e.base, 0.0) + (
                    min(e.end, t1) - max(e.start, t0))
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, t0: float, t1: float, n: int = 10):
        """Idle device time in [t0, t1] summed by what the host was doing
        in the middle of each gap (the innermost host span covering it,
        'host' where none does): the ``n`` largest, with seconds."""
        if not self.devices:
            return []
        dev = self.devices[0]
        gaps, cur = [], t0
        for s, e in self._busy(dev, t0, t1) + [(t1, t1)]:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        hosts = sorted((e for e in self.host if e.end > t0 and e.start < t1),
                       key=lambda e: e.start)
        active: list = []  # heap of (duration, index, event), lazily pruned
        nxt, tot = 0, {}
        for s, e in gaps:  # midpoints increase, so one sweep serves all
            t = (s + e) / 2
            while nxt < len(hosts) and hosts[nxt].start <= t:
                heapq.heappush(active, (hosts[nxt].dur, nxt, hosts[nxt]))
                nxt += 1
            while active and active[0][2].end <= t:
                heapq.heappop(active)
            # an event still in the heap below the top may have ended:
            # take the shortest one that covers t
            label = "host"
            for _, _, ev in sorted(active)[:64]:
                if ev.end > t:
                    label = ev.name
                    break
            tot[label] = tot.get(label, 0.0) + (e - s)
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def load(log_dir) -> Trace:
    """The trace under ``log_dir`` (the newest ``.xplane.pb``)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    dev, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name != DEVICE_OP_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    dev.append(Event(op_name(ev.name), s,
                                     s + ev.duration_ns * 1e-9, plane.name))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns <= 0:
                        continue
                    s = ev.start_ns * 1e-9
                    host.append(Event(ev.name, s, s + ev.duration_ns * 1e-9,
                                      line.name))
    return Trace(dev, host)

