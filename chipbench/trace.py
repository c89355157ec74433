"""From the profiler's trace to device busy time, collective exposure, the
costliest device operations and the idle gaps, labelled by the
benchmark's own host spans.

A traced window is reduced in two steps.  :func:`load_xplane` keeps, of
the JAX profiler's ``.xplane.pb``, the device operations (the ``XLA Ops``
line of every ``/device:`` plane, as ``[name, opcode, start_ns,
duration_ns]``) and the benchmark's host spans (``bench.*``, as ``[name,
start_ns, duration_ns]``); :func:`summarize` does all the arithmetic on
those lists, so that a small recorded trace can pin it in a test.

Host and device events of one trace share one clock.  A loop or a branch
(``while``, ``conditional``, ``call``) is an event that spans the
operations it runs; those containers are left out, so that busy time is
the time in which an operation of the loop's body ran.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

from . import stats

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = frozenset({"while", "conditional", "call"})
#: operations that move data between chips
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|all-to-all"
                        r"|collective-permute|collective-broadcast|send"
                        r"|recv)")
TOP = 10


def start(out_dir: str) -> None:
    """Start the JAX profiler, without the Python tracer (whose per-call
    cost would slow the host loop being measured)."""
    import jax

    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def op_parts(text: str) -> Tuple[str, str]:
    """(name, opcode) of an HLO instruction as the TPU trace names it,
    ``%fusion.3 = f32[8]{0} fusion(...)``; a plain name passes through
    with an empty opcode."""
    if " = " not in text:
        return text, ""
    name, rest = text.split(" = ", 1)
    name = name.lstrip("%")
    if rest.startswith("("):              # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = re.match(r"\s*([A-Za-z][\w-]*)\(", rest)
    return name, (m.group(1) if m else "")


def load_xplane(out_dir: str) -> dict:
    """``{"devices": {plane: [[op, opcode, start_ns, dur_ns], ...]},
    "host": [[span, start_ns, dur_ns], ...]}`` from the newest trace
    under ``out_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(out_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {out_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        ops.append([*op_parts(e.name), float(e.start_ns),
                                    float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


@dataclasses.dataclass
class TraceSummary:
    window_s: float                 # the bench.window span
    devices: int
    busy_s: float                   # union of op intervals, device mean
    collective_exposed_s: float     # collective, no compute: device mean
    top_ops: List[Tuple[str, float]]    # op -> seconds, device mean
    idle_gaps: List[Tuple[str, float]]  # longest gaps, by host span

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _collective(name: str, opcode: str) -> bool:
    return bool(COLLECTIVE.match(opcode) or COLLECTIVE.match(name))


def _window(host: list) -> Tuple[float, float]:
    spans = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span in the trace, "
                         f"found {len(spans)}")
    return spans[0]


def _label(host: list, lo: float, hi: float) -> str:
    """The shortest host span (other than the window) that covers the
    middle of [lo, hi]; ``bench.window`` where none does."""
    mid = 0.5 * (lo + hi)
    best, best_len = WINDOW_SPAN, float("inf")
    for name, s, d in host:
        if name != WINDOW_SPAN and s <= mid <= s + d and d < best_len:
            best, best_len = name, d
    return best


def summarize(trace: dict, device_ids) -> Optional[TraceSummary]:
    """The window's numbers on the devices ``device_ids`` (the ones the
    cell runs on); None where none of them ran anything in it."""
    host = trace["host"]
    lo, hi = _window(host)
    ids = {int(i) for i in device_ids}
    planes = sorted(p for p in trace["devices"]
                    if int(DEVICE_PLANE.match(p).group(2)) in ids)
    busy, exposed, op_ns, gap_list = [], [], {}, []
    for plane in planes:
        ops = [(name, code, max(s, lo), min(s + d, hi))
               for name, code, s, d in trace["devices"][plane]
               if code not in CONTAINERS and d > 0 and s < hi and s + d > lo]
        all_iv = stats.merge_intervals([(s, e) for *_, s, e in ops])
        coll = [_collective(n, c) for n, c, _, _ in ops]
        coll_iv = stats.merge_intervals(
            [(s, e) for (*_, s, e), k in zip(ops, coll) if k])
        comp_iv = stats.merge_intervals(
            [(s, e) for (*_, s, e), k in zip(ops, coll) if not k])
        busy.append(stats.total(all_iv))
        exposed.append(stats.total(coll_iv) - stats.total(
            stats.intersect_intervals(coll_iv, comp_iv)))
        for name, _, s, e in ops:
            op_ns[name] = op_ns.get(name, 0.0) + e - s
        gap_list += stats.gaps(all_iv, lo, hi)
    if not planes or sum(busy) <= 0:
        return None
    k = len(planes)
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:TOP]
    longest = sorted(gap_list, key=lambda g: g[0] - g[1])[:TOP]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, devices=k,
        busy_s=sum(busy) / k * 1e-9,
        collective_exposed_s=sum(exposed) / k * 1e-9,
        top_ops=[(n, v / k * 1e-9) for n, v in top],
        idle_gaps=[(_label(host, s, e), (e - s) * 1e-9) for s, e in longest])
