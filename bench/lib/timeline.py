"""The device timeline of a traced window, each operation named by its
scopes: where inside the program the device sat idle.

``bench/lib/trace.py`` reduces a profile to a compact :class:`Trace`:
the timeline's operations by HLO name, and xprof's ``hlo_stats`` rows
that give each HLO operation its framework op name (the
``jax.named_scope`` path). An HLO name is unique only within one program:
``fusion.3`` of ``jit(drift)`` and ``fusion.3`` of ``jit(_step_impl)``
are different operations. So :func:`read_run` reads the run's profile
once more for the program (XLA module) of every timeline operation and
of every ``hlo_stats`` row, and joins on (program, HLO name).

A :class:`Timeline` then answers one question, :meth:`scope_gaps`: how
long the device was idle between operations of given scopes, and which
scopes and operations bracket that idle time.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import sys
from pathlib import Path

from bench.lib import trace as trace_mod

# where bench/run.py writes the profile of a traced run
TRACE_DIR = Path(__file__).resolve().parents[2] / ".bench_trace"
MODULES_LINE = "XLA Modules"
PROGRAM_IN_NAME = re.compile(r"\((\d+)\)$")
SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]+")


@dataclasses.dataclass
class Timeline:
    window: tuple       # (start_s, end_s)
    ops: list           # [HLO name, start_s, end_s, device, program]
    names: list         # [program, HLO name, framework op name]

    def framework_names(self) -> list:
        """The framework op name of each operation, "" where it has none.
        Found by (program, HLO name); where an operation's program is
        unknown, by an HLO name that means one operation in every
        program, and not at all where it means several."""
        exact, by_name = {}, {}
        for prog, hlo, fw in self.names:
            if prog is not None:
                exact[(prog, hlo)] = fw
            by_name.setdefault(hlo, set()).add(fw)
        out = []
        for hlo, _a, _b, _dev, prog in self.ops:
            fw = exact.get((prog, hlo))
            if fw is None:
                means = by_name.get(hlo, ())
                fw = next(iter(means)) if len(means) == 1 else ""
            out.append(fw)
        return out

    def scope_gaps(self, patterns):
        """Idle time of the first device, inside the window, between
        operations of the scopes ``patterns``.

        A gap between two busy intervals counts where the nearest named
        operation on each side (one with a framework op name; an unnamed
        one, such as ``copy-done.4``, is passed over) holds one of the
        patterns. Returns ``(seconds, gaps)``, with one ``[scope before,
        scope after, seconds, HLO names of the operations that bracket
        it]`` per counted gap; ``(None, [])`` where no operation of that
        device holds a pattern."""
        devs = sorted({o[3] for o in self.ops})
        if not devs:
            return None, []
        lo, hi = self.window
        fws = self.framework_names()
        ops = sorted((max(o[1], lo), min(o[2], hi), o[0], fws[i])
                     for i, o in enumerate(self.ops)
                     if o[3] == devs[0] and o[2] > lo and o[1] < hi)

        def held(fw):
            return any(p in fw for p in patterns)

        if not any(held(op[3]) for op in ops):
            return None, []
        named = [op for op in ops if op[3]]           # sorted by start
        starts = [op[0] for op in named]
        by_end = sorted(named, key=lambda op: op[1])
        ends = [op[1] for op in by_end]
        ending, starting = {}, {}
        for op in ops:
            ending.setdefault(op[1], op)
            starting.setdefault(op[0], op)
        busy = trace_mod.merge([(a, b) for a, b, _n, _f in ops])
        total, gaps = 0.0, []
        for (_s, a), (b, _e) in zip(busy, busy[1:]):
            i = bisect.bisect_right(ends, a) - 1
            j = bisect.bisect_left(starts, b)
            if i < 0 or j >= len(named):
                continue
            before, after = by_end[i], named[j]
            if not (held(before[3]) and held(after[3])):
                continue
            total += b - a
            bracket = [hlo_name(op[2]) for op in
                       (before, ending[a], starting[b], after)]
            gaps.append([scope_label(before[3]), scope_label(after[3]),
                         b - a, list(dict.fromkeys(bracket))])
        return total, gaps


def hlo_name(event_name: str) -> str:
    """The HLO op name of a timeline event: ``fusion.3`` from
    ``fusion.3`` or from an expression ``%fusion.3 = f32[...] ...``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def scope_label(framework_name: str) -> str:
    """The program's own scopes in a framework op name, outermost first
    (``repro.execute_plan/repro.launch.level1_w3/repro.search.select``);
    the name itself where it holds none."""
    return "/".join(SCOPE.findall(framework_name)) or framework_name


def program_key(value) -> str | None:
    """One spelling of a program id, as a module event's name, an event
    stat or ``hlo_stats`` gives it (int, float or digits); None where
    there is none. ``hlo_stats`` gives the 64-bit id as a JSON number, a
    double, so every id is compared rounded to a double: two programs of
    one window whose ids round alike are not told apart."""
    if value is None or value == "":
        return None
    try:
        return str(int(float(value)))
    except (TypeError, ValueError):
        return str(value)


def from_trace(tr) -> Timeline:
    """A timeline of a compact trace alone: no programs, so an HLO name
    that several programs use finds no framework op name."""
    return Timeline(window=tuple(tr.window),
                    ops=[[hlo_name(o[0]), o[1], o[2], o[3], None]
                         for o in tr.device_ops],
                    names=[[None, o[0], o[1]] for o in tr.hlo_ops])


# ---------------------------------------------------------------------------
# xplane -> Timeline
# ---------------------------------------------------------------------------

def _stat_program(event) -> str | None:
    for name, value in event.stats:
        if name == "program_id":
            return program_key(value)
    return None


def _programs(ops_line, modules_line) -> list:
    """The program of each event of a device's ``XLA Ops`` line: its own
    ``program_id`` stat, else the run of a program on the ``XLA Modules``
    line that holds its start (the run's ``program_id`` stat, or the
    number that ends its name, ``jit_frame(12)``)."""
    runs = []
    if modules_line is not None:
        for e in modules_line.events:
            prog = _stat_program(e)
            if prog is None:
                m = PROGRAM_IN_NAME.search(e.name)
                prog = program_key(m.group(1)) if m else None
            runs.append((e.start_ns, e.start_ns + e.duration_ns, prog))
        runs.sort()
    starts = [r[0] for r in runs]
    progs = []
    for e in ops_line.events:
        prog = _stat_program(e)
        if prog is None:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns <= runs[i][1]:
                prog = runs[i][2]
        progs.append(prog)
    return progs


def hlo_names(path: str) -> list:
    """[program, HLO op name, framework op name] of every operation in
    the profile at ``path``, from xprof's ``hlo_stats`` tool."""
    from xprof.convert import raw_to_tool_data
    raw = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})[0]
    table = json.loads(raw)
    cols = [c["id"] for c in table["cols"]]
    out = []
    for row in table.get("rows", []):
        r = dict(zip(cols, (c.get("v") for c in row["c"])))
        out.append([program_key(r.get("program_id")), r["hlo_op_name"],
                    r.get("tf_op_name") or ""])
    return out


def read_xspace(path: str) -> Timeline:
    """The timeline of the profile at ``path``: the window that the
    ``bench.window`` span marks, every operation of each device's
    ``XLA Ops`` line with its program, and the ``hlo_stats`` names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, window = [], None
    for plane in pd.planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            line = lines.get(trace_mod.OPS_LINE)
            if line is None:
                continue
            progs = _programs(line, lines.get(MODULES_LINE))
            for e, prog in zip(line.events, progs):
                t0 = e.start_ns * 1e-9
                ops.append([hlo_name(e.name), t0,
                            t0 + e.duration_ns * 1e-9, int(m.group(1)),
                            prog])
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == trace_mod.WINDOW_SPAN:
                        t0 = e.start_ns * 1e-9
                        window = (t0, t0 + e.duration_ns * 1e-9)
    if window is None:
        raise ValueError(f"no {trace_mod.WINDOW_SPAN} span in {path}")
    return Timeline(window=window, ops=ops,
                    names=hlo_names(path) if ops else [])


def read_run(tr, trace_dir=TRACE_DIR) -> Timeline:
    """The timeline of a run whose compact trace is ``tr``: from the
    profile that ``bench/run.py`` left in ``trace_dir`` where that
    profile is the one ``tr`` was read from (the same window), else from
    ``tr`` alone."""
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) == 1:
        try:
            tl = read_xspace(files[0])
        except Exception as e:  # noqa: BLE001 - any unreadable profile
            print(f"bench: {files[0]} not read ({e!r}); HLO names that "
                  f"several programs use go unnamed", file=sys.stderr)
        else:
            if all(abs(x - y) < 1e-9 for x, y in zip(tl.window,
                                                       tr.window)):
                return tl
            print(f"bench: {files[0]} is another window's profile; HLO "
                  f"names that several programs use go unnamed",
                  file=sys.stderr)
    return from_trace(tr)
