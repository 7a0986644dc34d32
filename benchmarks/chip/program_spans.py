"""The program's own spans in a traced window, on the device trace's clock.

While a profiler records, the program's device paths open spans of their
own (`ckpt.*`, `serve.*`) on time.perf_counter, which
`repro.core.trace.profiled()` returns.  `read(ctx, trace)` keeps those that
lie inside the window that the harness's spans cover (`ctx["spans"].log`,
on the same clock), finds the offset from that clock to the trace's as the
median, over the harness's spans that both records hold (matched by name
and order: one `Spans.span` call writes both), of the trace's start less
the log's, and gives each device-idle stretch of chip 0 in the trace's
window to the innermost program span, or `bench/driver.wait_arrival`, open
over it.  A program without these spans reads None, and so does every
metric read from them.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import statistics
import sys
from typing import Dict, List, Optional, Tuple

import trace_reduce

WAIT = "bench/driver.wait_arrival"
UNCOVERED = ""              # idle under no program span and no WAIT


@dataclasses.dataclass
class ProgramSpans:
    spans: list                 # repro.core.trace.Span, on the host clock
    pairs: int                  # harness spans matched across the records
    offset: Optional[float]     # trace clock less host clock, seconds
    offset_spread: Optional[float]      # max less min of the pairs' offsets
    idle: Dict[str, float]      # chip 0's idle seconds by innermost span
    idle_s: float

    def named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    def durations(self, name: str) -> List[float]:
        return [s.t1 - s.t0 for s in self.named(name)]

    def per(self, total: float, of: str) -> float:
        """`total` over the number of `of` spans; 0 where there is none."""
        n = len(self.named(of))
        return total / n if n else 0.0

    def summary(self) -> dict:
        longest: Dict[str, float] = {}
        for s in self.spans:
            longest[s.name] = max(longest.get(s.name, 0.0), s.t1 - s.t0)
        return {"spans": len(self.spans), "pairs": self.pairs,
                "offset_spread_s": self.offset_spread, "idle_s": self.idle_s,
                "idle_uncovered_s": self.idle.get(UNCOVERED, 0.0),
                "idle_by_span_s": self.idle, "longest_s": longest}


def read(ctx: dict, trace) -> Optional[ProgramSpans]:
    """The window's program spans (computed once a run, kept in ctx), or
    None where the program records none."""
    if "program_spans" not in ctx:
        ctx["program_spans"] = _read(ctx, trace)
        if ctx["program_spans"] is not None:
            print("program_spans " + json.dumps(
                ctx["program_spans"].summary()), file=sys.stderr, flush=True)
    return ctx["program_spans"]


def _read(ctx: dict, trace) -> Optional[ProgramSpans]:
    try:
        from repro.core.trace import profiled
    except ImportError:
        return None
    tracer, log = profiled(), ctx["spans"].log
    if tracer is None or not log:
        return None
    w0, w1 = min(s for _, s, _ in log), max(e for _, _, e in log)
    spans = [s for s in tracer.spans
             if s.t1 is not None and s.t0 >= w0 and s.t1 <= w1]
    offsets = clock_offsets(log, trace) if trace is not None else []
    if not offsets:
        return ProgramSpans(spans, 0, None, None, {}, 0.0)
    offset = statistics.median(offsets)
    labelled = [(s.t0 + offset, s.t1 + offset, s.name) for s in spans
                if s.kind == "span"]
    labelled += [(e.start, e.end, e.name) for e in trace.span_events(WAIT)]
    gaps = trace_reduce.idle_gaps(((e.start, e.end) for e in trace.ops[0]),
                                  trace.t0, trace.t1)
    return ProgramSpans(spans, len(offsets), offset,
                        max(offsets) - min(offsets),
                        attribute_idle(gaps, labelled),
                        sum(b - a for a, b in gaps))


def clock_offsets(log: List[Tuple[str, float, float]], trace) -> List[float]:
    """Per harness span held by both records: its start in the trace less
    its start in the log, matched by name and order."""
    out = []
    for name in sorted({n for n, _, _ in log}):
        host = sorted(s for n, s, _ in log if n == name)
        dev = sorted(e.start for e in trace.span_events(name))
        out += [d - h for d, h in zip(dev, host)]
    return out


def innermost(labelled: List[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """The timeline cut where any span starts or ends, each piece with the
    name of the innermost (latest-starting) span open over it; pieces that
    no span covers are left out."""
    order = sorted(labelled, key=lambda x: (x[0], -x[1]))
    bounds = sorted({t for s, e, _ in labelled for t in (s, e)})
    pieces, open_, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(order) and order[i][0] <= a:
            open_.append(order[i])
            i += 1
        open_ = [x for x in open_ if x[1] > a]
        if open_:
            pieces.append((a, b, open_[-1][2]))
    return pieces


def attribute_idle(gaps: List[Tuple[float, float]],
                   labelled: List[Tuple[float, float, str]]
                   ) -> Dict[str, float]:
    """Seconds of the idle gaps under each innermost span; UNCOVERED holds
    the rest."""
    pieces = innermost(labelled)
    starts = [p[0] for p in pieces]
    idle: Dict[str, float] = {UNCOVERED: 0.0}
    for g0, g1 in gaps:
        covered = 0.0
        j = max(bisect.bisect_right(starts, g0) - 1, 0)
        while j < len(pieces) and pieces[j][0] < g1:
            a, b, name = pieces[j]
            part = min(b, g1) - max(a, g0)
            if part > 0:
                idle[name] = idle.get(name, 0.0) + part
                covered += part
            j += 1
        idle[UNCOVERED] += g1 - g0 - covered
    return idle
