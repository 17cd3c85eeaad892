"""Reading the profiler's trace of a traced window.

``read_dir`` reduces the ``.xplane.pb`` that ``jax.profiler`` writes to a
compact :class:`Trace`:

* the device operations (name, start, end, device) of each TPU plane's
  ``XLA Ops`` line, read with ``jax.profiler.ProfileData``;
* the host spans (name, start, end) of the benchmark and of ``repro.obs``,
  all on the profiler's one clock, and the window that the benchmark's
  ``bench.window`` span marks;
* each HLO operation that ran, with its framework op name and its self
  time summed over the trace, as the profiler's own ``hlo_stats`` tool
  (``xprof``) reports them. The framework op name is the op_name of the
  operation's metadata, which holds the path of ``jax.named_scope``s
  (e.g. ``jit(frame)/repro.execute_plan/...``); the scope readers match
  their patterns there. An operation's self time leaves out the
  operations it holds, so the times of a scope's operations add up
  without counting a loop twice.

The reductions below work on that compact form alone, so a small
recorded trace checks them.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
# host spans that idle gaps are named by: the benchmark's own, and the
# span taxonomy of repro.obs (DESIGN.md section 9)
HOST_SPANS = ("bench.",)
OBS_SPANS = frozenset({"query", "step", "plan", "compile", "launch", "sync",
                       "admit", "admit/enqueue", "drain", "stage", "split",
                       "resolve"})


@dataclasses.dataclass
class Trace:
    window: tuple                # (start_s, end_s)
    device_ops: list             # [name, start_s, end_s, device]
    host_spans: list             # [name, start_s, end_s]
    hlo_ops: list = dataclasses.field(default_factory=list)
    # [HLO op name, framework op name, self seconds summed over devices]

    # -- persistence (the recorded test trace) --------------------------
    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(window=tuple(d["window"]),
                   device_ops=[list(o) for o in d["device_ops"]],
                   host_spans=[list(s) for s in d["host_spans"]],
                   hlo_ops=[list(o) for o in d.get("hlo_ops", [])])

    # -- reductions -----------------------------------------------------
    def devices(self) -> list:
        return sorted({o[3] for o in self.device_ops})

    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clipped(self, device):
        lo, hi = self.window
        return [(max(o[1], lo), min(o[2], hi)) for o in self.device_ops
                if o[3] == device and o[2] > lo and o[1] < hi]

    def busy_s(self) -> float:
        """Seconds in which some operation ran on a device, within the
        window, averaged over the devices that ran any."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(union_length(self._clipped(d))
                   for d in devs) / len(devs)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    def scope_s(self, patterns) -> float | None:
        """Device seconds, averaged over the devices, of the operations
        whose framework op name holds any of ``patterns``; None where
        none does."""
        hit = [o[2] for o in self.hlo_ops
               if any(p in o[1] for p in patterns)]
        if not hit or not self.devices():
            return None
        return sum(hit) / len(self.devices())

    def top_ops(self, n: int) -> list:
        """The ``n`` operations with the most self time, each named by
        its HLO op and framework op name: [[name, seconds], ...],
        averaged over the devices."""
        ndev = max(1, len(self.devices()))
        top = sorted(self.hlo_ops, key=lambda o: -o[2])[:n]
        return [[f"{o[0]} {o[1]}".strip()[:200], o[2] / ndev] for o in top]

    def idle_gaps(self, n: int) -> list:
        """Idle time of the first device in the window, summed by the
        innermost host span open at each gap's middle:
        [[span, seconds], ...]."""
        devs = self.devices()
        if not devs:
            return []
        busy = merge(self._clipped(devs[0]))
        lo, hi = self.window
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        total: dict = {}
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = self.host_span_at((a + b) / 2)
                total[name] = total.get(name, 0.0) + (b - a)
        return sorted(([k, v] for k, v in total.items()),
                      key=lambda kv: -kv[1])[:n]

    def host_span_at(self, t: float) -> str:
        """The innermost host span open at ``t``: the latest started, and
        of those the first to end."""
        open_ = [(a, -b, name) for name, a, b in self.host_spans
                 if a <= t <= b and name != WINDOW_SPAN]
        return max(open_)[2] if open_ else "(no span)"


def merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def union_length(intervals) -> float:
    return sum(b - a for a, b in merge(intervals))


# ---------------------------------------------------------------------------
# xplane -> Trace
# ---------------------------------------------------------------------------

def hlo_stats(path: str) -> list:
    """[HLO op name, framework op name, self seconds] of every operation
    in the xplane at ``path``, from xprof's ``hlo_stats`` tool."""
    from xprof.convert import raw_to_tool_data
    raw = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})[0]
    table = json.loads(raw)
    cols = [c["id"] for c in table["cols"]]
    ops = []
    for row in table.get("rows", []):
        r = dict(zip(cols, (c.get("v") for c in row["c"])))
        ops.append([r["hlo_op_name"], r.get("tf_op_name") or "",
                    1e-6 * float(r["total_self_time"])])
    return ops


def read_xspace(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        t0 = e.start_ns * 1e-9
                        ops.append([e.name, t0, t0 + e.duration_ns * 1e-9,
                                    dev])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPANS) or e.name in OBS_SPANS:
                        t0 = e.start_ns * 1e-9
                        spans.append([e.name, t0,
                                      t0 + e.duration_ns * 1e-9])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    window = (win[0][1], win[0][2])
    # keep the host spans that overlap the window: the rest only cost room
    spans = [s for s in spans if s[2] > window[0] and s[1] < window[1]]
    hlo = []
    if ops:
        try:
            hlo = hlo_stats(path)
        except Exception as e:  # noqa: BLE001 - any failure of the tool
            # the scope metrics then read nothing and are left out of the
            # line; the timeline metrics still stand
            print(f"bench: xprof hlo_stats failed on {path}: {e!r}",
                  file=sys.stderr)
    return Trace(window=window, device_ops=ops, host_spans=spans,
                 hlo_ops=hlo)


def read_dir(trace_dir) -> Trace:
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return read_xspace(files[0])


def save(trace: Trace, path: str) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(trace.to_json(), fh)


def load(path: str) -> Trace:
    with gzip.open(path, "rt") as fh:
        return Trace.from_json(json.load(fh))
