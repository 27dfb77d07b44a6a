"""From the profiler's `.xplane.pb` to numbers: the one reduction every PR's
per-layer metrics go through, checked against a small trace recorded on the
chip (tests/data). Reads the file with nothing but jax.

What the trace holds (TPU v5e, jax 0.9.0): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Modules` has one event per executed
program (named `jit_<function>(<fingerprint>)`) and whose line `XLA Ops` has
one event per operation inside it (fusions, custom calls — the Pallas
kernels — copies), named by its whole HLO text, of which the part before
` = ` is kept (`%fusion.29`, `%build_histograms_pallas_factored.6`); and the
plane `/host:CPU` with one line per host thread, whose events
(`PjitFunction(...)`, transfers, `np.asarray`, the benchmark's own
`bench.window`) say what the host was doing. Times are nanoseconds on one
clock for all planes. `%while` and `%conditional` events enclose the events
of their bodies: the busy union counts them once, the per-name sums leave
the wrappers out."""

from __future__ import annotations

import gzip
import re
import shutil
import tempfile

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WRAPPERS = re.compile(r"^%(while|conditional|call)\b")
NS = 1e-9


def union_seconds(intervals) -> float:
    """Length of the union of (start, duration) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals):
    """The union as a sorted list of disjoint (start, end)."""
    out = []
    for s, d in sorted(intervals):
        e = s + d
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Trace:
    """Device events per chip and host events, in seconds."""

    def __init__(self, devices: list, host: list):
        # devices: [{"ops": [(name, start, dur)], "modules": [...]}, ...]
        # host:    [(thread, name, start, dur)]
        self.devices, self.host = devices, host

    # -- device ----------------------------------------------------------------
    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(union_seconds((s, d) for _, s, d in dev["ops"])
                   for dev in self.devices) / len(self.devices)

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of the operations whose name matches, averaged
        over the chips."""
        rx = re.compile(pattern)
        if not self.devices:
            return 0.0
        return sum(d for dev in self.devices for n, _, d in dev["ops"]
                   if rx.search(n)) / len(self.devices)

    def program_events(self, pattern: str) -> list:
        """(start, duration) of the executed programs whose name matches, on
        the first chip (every chip of a mesh runs the same programs)."""
        rx = re.compile(pattern)
        if not self.devices:
            return []
        return sorted((s, d) for n, s, d in self.devices[0]["modules"]
                      if rx.search(n))

    def idle_between(self, events: list) -> list:
        """For consecutive (start, duration) events: the seconds between one's
        end and the next one's start in which no operation ran."""
        busy = merged((s, d) for _, s, d in self.devices[0]["ops"])
        out, j = [], 0
        for (s0, d0), (s1, _) in zip(events, events[1:]):
            lo, hi = s0 + d0, s1
            covered = 0.0
            while j < len(busy) and busy[j][1] <= lo:
                j += 1
            k = j
            while k < len(busy) and busy[k][0] < hi:
                covered += max(0.0, min(busy[k][1], hi) - max(busy[k][0], lo))
                k += 1
            out.append(max(hi - lo - covered, 0.0))
        return out

    def by_name(self) -> dict:
        """Operation name → summed seconds on the first chip."""
        acc = {}
        if self.devices:
            for n, _, d in self.devices[0]["ops"]:
                if not WRAPPERS.match(n):
                    acc[n] = acc.get(n, 0.0) + d
        return acc

    def idle_gaps(self, window: str | None = None) -> list:
        """(start, end) of every idle stretch of the first chip between its
        first and last operation; with `window`, the name of a host event,
        also from that event's start to the first operation and from the last
        one to its end."""
        if not self.devices:
            return []
        busy = merged((s, d) for _, s, d in self.devices[0]["ops"])
        gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
        span = [(s, s + d) for _, n, s, d in self.host if n == window]
        if span and busy:
            lo, hi = min(s for s, _ in span), max(e for _, e in span)
            if busy[0][0] > lo:
                gaps.insert(0, (lo, busy[0][0]))
            if hi > busy[-1][1]:
                gaps.append((busy[-1][1], hi))
        return gaps

    # -- host ------------------------------------------------------------------
    def host_event_start(self, name: str):
        """Start of the first host event of that name, or None."""
        starts = [s for _, n, s, _ in self.host if n == name]
        return min(starts) if starts else None

    def add_spans(self, spans, offset: float, thread: str = "program") -> None:
        """Spans measured on another clock, (name, start, duration), moved
        onto the trace's clock by `offset` and kept as host events."""
        self.host.extend((thread, n, s + offset, d) for n, s, d in spans
                         if d > 0)

    def host_doing(self, lo: float, hi: float) -> str:
        """What the host was doing in [lo, hi]: the SHORTEST host event that
        covers at least half of it (the innermost span names the work best,
        an enclosing `train` names nothing); failing that, the event that
        overlaps it longest."""
        inner, inner_d = None, None
        widest, widest_over = "host (no span)", 0.0
        for _, name, s, d in self.host:
            over = min(s + d, hi) - max(s, lo)
            if over <= 0:
                continue
            if over >= 0.5 * (hi - lo) and (inner_d is None or d < inner_d):
                inner, inner_d = name, d
            if over > widest_over:
                widest, widest_over = name, over
        return inner if inner is not None else widest

    def breakdown(self, top: int = 10, window: str | None = None) -> dict:
        """The operations that took most device time and the longest idle
        gaps, each named by what the host was doing; a gap that only the
        `window` event itself covers has no finer span to name it."""
        ops = sorted(self.by_name().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(window), key=lambda g: g[0] - g[1])[:top]
        named = []
        for a, b in gaps:
            what = self.host_doing(a, b)
            named.append(["host inside train() (no finer span)"
                          if what == window else what, b - a])
        return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": named}


def _events(line, short: bool = False):
    return [(e.name.split(" = ", 1)[0] if short else e.name,
             e.start_ns * NS, e.duration_ns * NS) for e in line.events]


def load(path: str) -> Trace:
    """Read an `.xplane.pb` (or `.xplane.pb.gz`) file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with tempfile.NamedTemporaryFile(suffix=".xplane.pb") as tmp:
            with gzip.open(path, "rb") as src:
                shutil.copyfileobj(src, tmp)
            tmp.flush()
            return _reduce(ProfileData.from_file(tmp.name))
    return _reduce(ProfileData.from_file(path))


def _reduce(profile) -> Trace:
    devices, host = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = _events(line, short=True)
                elif line.name == MODULES_LINE:
                    dev["modules"] = _events(line)
            devices.append((plane.name, dev))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((line.name, n, s, d) for n, s, d in _events(line)
                            if d > 0)
    devices.sort(key=lambda kv: kv[0])
    return Trace([d for _, d in devices], host)
