"""Runtime overlap profiler: device timelines -> per-phase breakdown.

`repro.analysis` proves the paper's overlap claim *structurally* (no
dependency edge from the fused reduction to the in-flight matvec in the
jaxpr/HLO); this module measures it *at runtime*.  A capture context
wraps execution in :func:`jax.profiler.trace`, the emitted perfetto
trace-event timeline is parsed with the stdlib (gzip + json — no
TensorFlow/xprof dependency), device op events are attributed to solver
phases, and the headline number is computed:

    overlap efficiency = |reduce ∩ matvec| / |reduce|

the fraction of reduction/collective device wall time hidden under the
in-flight matvec (interval-union intersection, so concurrent ops are not
double counted), plus the complementary *exposed* communication time per
iteration — exactly how Cools & Vanroose evaluate pipelined solvers.

Phase attribution works in two layers:

1. **HLO metadata map.**  The solver loop bodies wrap their three phases
   in ``jax.named_scope("repro.matvec" | "repro.reduce" | "repro.axpy")``
   (see ``core/pipelined_bicgsafe.py``); those scopes survive into the
   compiled module's per-instruction ``metadata={op_name=...}``.  When a
   capture knows which jitted programs ran (the session front door notes
   them — see :func:`active_capture`), it lowers each with the recorded
   abstract shapes and parses ``compiled.as_text()`` into an
   ``{hlo_module: {instruction: scope path}}`` map.
2. **Name heuristics.**  Ops absent from the map (compiler-inserted
   copies, collectives renamed by SPMD partitioning) fall back to name
   patterns: ``all-reduce``/``psum``/``fused_dots`` -> reduce,
   ``collective-permute``/``ppermute``/``halo``/``spmv`` -> matvec,
   ``fused_axpy`` -> axpy.

Fusions that cross a scope boundary carry one representative op_name, so
per-phase times are attribution-exact only up to XLA's fusion decisions;
the reduce/matvec phases fuse cleanly in practice (dots and stencil
fusions are distinct instructions) and those two are all the headline
number reads.

On a single CPU device XLA executes thunks serially, so measured overlap
is honestly ~0 there — the efficiency math itself is pinned by golden
timeline fixtures in ``tests/test_profile.py``, and the multi-device
bindings report the real number.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

SCHEMA_PROFILE = "repro.observe/profile/v1"

PHASES = ("matvec", "reduce", "axpy", "precond", "other")

# scope tag -> phase (layer 1); checked against the full op_name path
_SCOPE_TAGS = (("repro.reduce", "reduce"), ("repro.matvec", "matvec"),
               ("repro.axpy", "axpy"), ("repro.precond", "precond"))

# op-name pattern -> phase (layer 2 fallback); order matters
_NAME_RULES: Tuple[Tuple[str, str], ...] = (
    ("all-reduce", "reduce"), ("all_reduce", "reduce"),
    ("reduce-scatter", "reduce"), ("psum", "reduce"),
    ("fused_dots", "reduce"), ("bicgsafe_dots", "reduce"),
    ("collective-permute", "matvec"), ("ppermute", "matvec"),
    ("halo", "matvec"), ("spmv", "matvec"), ("stencil", "matvec"),
    ("fused_axpy", "axpy"), ("axpy_phase", "axpy"),
    ("precond", "precond"),
)


# ---------------------------------------------------------------------------
# timeline loading
# ---------------------------------------------------------------------------

def load_timeline(src: Any) -> Dict[str, Any]:
    """Load a Chrome trace-event document from a path (.json / .json.gz)
    or pass a dict through unchanged."""
    if isinstance(src, dict):
        return src
    opener = gzip.open if str(src).endswith(".gz") else open
    with opener(src, "rt") as fh:
        return json.load(fh)


def find_perfetto_trace(profile_dir: str) -> Optional[str]:
    """Newest ``perfetto_trace.json.gz`` under a jax.profiler dump dir."""
    hits = glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "perfetto_trace.json.gz"))
    return max(hits, key=os.path.getmtime) if hits else None


def _thread_names(events: Iterable[dict]) -> Dict[Tuple[Any, Any], str]:
    names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            names[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    return names


def device_events(doc: Dict[str, Any]) -> List[dict]:
    """Complete device op events: ``ph == "X"`` carrying ``args.hlo_op``."""
    return [e for e in doc.get("traceEvents", [])
            if e.get("ph") == "X" and "hlo_op" in (e.get("args") or {})]


def host_spans(doc: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Aggregate host-side ``TraceAnnotation`` spans (the SpanRecorder
    names: ``api.*`` / ``engine.*``) by name -> {count, total_us}."""
    out: Dict[str, Dict[str, float]] = {}
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X" or "hlo_op" in (e.get("args") or {}):
            continue
        name = e.get("name", "")
        if not re.match(r"^(api|engine|repro)\.", name):
            continue
        rec = out.setdefault(name, {"count": 0, "total_us": 0.0})
        rec["count"] += 1
        rec["total_us"] += float(e.get("dur", 0.0))
    return out


# ---------------------------------------------------------------------------
# HLO metadata map
# ---------------------------------------------------------------------------

_MODULE_RE = re.compile(r"HloModule ([^,\s]+)")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([A-Za-z0-9_.\-]+)\s*\(.*\{\s*$")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z0-9_.\-]+) = ")
_CALLS_RE = re.compile(r"calls=%?([A-Za-z0-9_.\-]+)")
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')

#: when a fusion's body spans scopes, the highest-priority tag wins —
#: reduce first, so boundary-crossing fusions bias the efficiency DOWN
#: (any reduction work they contain is counted as reduction time)
_TAG_PRIORITY = ("repro.reduce", "repro.matvec", "repro.axpy",
                 "repro.precond")


def hlo_op_map(compiled_text: str) -> Tuple[str, Dict[str, str]]:
    """Parse ``compiled.as_text()`` into (module name, {instruction:
    op_name scope path}).

    XLA fuses whole phases into single instructions whose own metadata
    names one representative op; the instructions *inside* the called
    ``%fused_computation`` keep their full scope paths.  A fusion is
    therefore attributed by the tagged scopes of its body (priority:
    reduce > matvec > axpy), falling back to its own metadata.
    """
    m = _MODULE_RE.search(compiled_text)
    module = m.group(1) if m else ""
    ops: Dict[str, str] = {}
    comp_tags: Dict[str, set] = {}
    fusion_calls: Dict[str, str] = {}
    current = ""
    for line in compiled_text.splitlines():
        cm = _COMP_RE.match(line.strip()) if line.rstrip().endswith("{") \
            else None
        if cm:
            current = cm.group(1)
            continue
        im = _INSTR_RE.match(line)
        if not im:
            continue
        name = im.group(1)
        om = _OPNAME_RE.search(line)
        scope = om.group(1) if om else ""
        if scope:
            ops[name] = scope
            for tag in _TAG_PRIORITY:
                if tag in scope:
                    comp_tags.setdefault(current, set()).add(tag)
                    break
        calls = _CALLS_RE.search(line)
        if calls:
            fusion_calls[name] = calls.group(1)
    for name, comp in fusion_calls.items():
        tags = comp_tags.get(comp)
        if not tags:
            continue
        own = ops.get(name, "")
        if any(t in own for t in _TAG_PRIORITY):
            continue                      # own metadata already tagged
        best = next(t for t in _TAG_PRIORITY if t in tags)
        ops[name] = f"{own}#{best}" if own else best
    return module, ops


def classify_op(name: str, scope: str = "") -> str:
    """Phase of one device op: scope tags first, then name patterns."""
    hay = f"{scope}/{name}".lower()
    for tag, phase in _SCOPE_TAGS:
        if tag in hay:
            return phase
    for pat, phase in _NAME_RULES:
        if pat in hay:
            return phase
    return "other"


# ---------------------------------------------------------------------------
# interval math
# ---------------------------------------------------------------------------

def merge_intervals(iv: Sequence[Tuple[float, float]]) \
        -> List[Tuple[float, float]]:
    """Union of half-open intervals, sorted and coalesced."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(i for i in iv if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def intersect_intervals(a: Sequence[Tuple[float, float]],
                        b: Sequence[Tuple[float, float]]) \
        -> List[Tuple[float, float]]:
    """Intersection of two merged interval lists (two-pointer sweep)."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def total(iv: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in iv)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ProfileReport:
    """Per-phase device-time breakdown + the headline overlap numbers.

    Times are microseconds of *device op wall time* (interval union per
    phase, so concurrent ops on different device lanes are not double
    counted).  ``overlap_efficiency`` is None when no reduce-phase device
    time was observed.
    """
    phase_us: Dict[str, float]
    phase_ops: Dict[str, int]
    device_wall_us: float
    reduce_us: float
    matvec_us: float
    hidden_us: float
    exposed_us: float
    overlap_efficiency: Optional[float]
    iterations: Optional[int]
    exposed_per_iter_us: Optional[float]
    n_device_events: int
    unmapped_ops: int
    host_spans: Dict[str, Dict[str, float]]
    label: str = ""
    timeline_path: Optional[str] = None

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["schema"] = SCHEMA_PROFILE
        return d

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "ProfileReport":
        d = {k: v for k, v in d.items() if k != "schema"}
        return cls(**d)

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            json.dump(self.to_json(), fh, indent=1, sort_keys=True)
        return path

    @classmethod
    def load(cls, path: str) -> "ProfileReport":
        with open(path) as fh:
            return cls.from_json(json.load(fh))

    def render(self, width: int = 46) -> str:
        lines = [f"== phase breakdown{f' ({self.label})' if self.label else ''} =="]
        denom = max(self.device_wall_us, 1e-9)
        for ph in PHASES:
            us = self.phase_us.get(ph, 0.0)
            if not us and ph not in ("matvec", "reduce"):
                continue
            frac = us / denom
            bar = "█" * int(round(width * min(frac, 1.0)))
            lines.append(f"  {ph:<8} {us / 1e3:9.3f} ms "
                         f"|{bar:<{width}}| {100 * frac:5.1f}%  "
                         f"({self.phase_ops.get(ph, 0)} ops)")
        lines.append(f"  device wall {self.device_wall_us / 1e3:.3f} ms, "
                     f"{self.n_device_events} device events"
                     + (f", {self.unmapped_ops} unmapped"
                        if self.unmapped_ops else ""))
        if self.overlap_efficiency is None:
            lines.append("  overlap: no reduce-phase device time observed")
        else:
            lines.append(
                f"  reduce {self.reduce_us / 1e3:.3f} ms: "
                f"{self.hidden_us / 1e3:.3f} ms hidden under matvec, "
                f"{self.exposed_us / 1e3:.3f} ms exposed "
                f"-> overlap efficiency {self.overlap_efficiency:.3f}")
            if self.exposed_per_iter_us is not None:
                lines.append(
                    f"  exposed communication per iteration: "
                    f"{self.exposed_per_iter_us:.2f} us"
                    + (f" ({self.iterations} iterations)"
                       if self.iterations else ""))
        return "\n".join(lines)


def analyze_timeline(src: Any,
                     hlo_maps: Optional[Dict[str, Dict[str, str]]] = None,
                     iterations: Optional[int] = None,
                     label: str = "") -> ProfileReport:
    """Parse one trace-event timeline into a :class:`ProfileReport`.

    ``src`` is a path (.json/.json.gz) or a loaded trace dict;
    ``hlo_maps`` is ``{hlo_module: {instruction: op_name scope}}`` from
    :func:`hlo_op_map`.  ``iterations`` (solver iterations inside the
    capture window) enables the per-iteration exposed time; when omitted
    it is estimated as the execution count of the most-run reduce op.
    """
    doc = load_timeline(src)
    hlo_maps = hlo_maps or {}
    events = device_events(doc)

    phase_iv: Dict[str, List[Tuple[float, float]]] = {p: [] for p in PHASES}
    phase_ops: Dict[str, set] = {p: set() for p in PHASES}
    op_counts: Dict[Tuple[str, str, str], int] = {}
    unmapped = 0
    for e in events:
        args = e["args"]
        op = str(args.get("hlo_op", e.get("name", "")))
        module = str(args.get("hlo_module", ""))
        scope = hlo_maps.get(module, {}).get(op, "")
        if not scope:
            # SPMD partitioning renames modules (e.g. ".spmd"); retry on
            # prefix match before falling back to name heuristics only.
            for mod, ops in hlo_maps.items():
                if module.startswith(mod) or mod.startswith(module):
                    scope = ops.get(op, "")
                    if scope:
                        break
        if not scope:
            unmapped += 1
        phase = classify_op(op, scope)
        ts = float(e.get("ts", 0.0))
        dur = float(e.get("dur", 0.0))
        phase_iv[phase].append((ts, ts + dur))
        phase_ops[phase].add((module, op))
        key = (module, op, phase)
        op_counts[key] = op_counts.get(key, 0) + 1

    merged = {p: merge_intervals(iv) for p, iv in phase_iv.items()}
    phase_us = {p: total(iv) for p, iv in merged.items()}
    all_iv = merge_intervals([i for iv in phase_iv.values() for i in iv])

    R, V = merged["reduce"], merged["matvec"]
    reduce_us = total(R)
    hidden_us = total(intersect_intervals(R, V))
    exposed_us = reduce_us - hidden_us
    eff = (hidden_us / reduce_us) if reduce_us > 0 else None

    if iterations is None:
        reduce_counts = [n for (_, _, p), n in op_counts.items()
                         if p == "reduce"]
        iterations = max(reduce_counts) if reduce_counts else None
    exposed_per_iter = (exposed_us / iterations
                        if eff is not None and iterations else None)

    return ProfileReport(
        phase_us=phase_us,
        phase_ops={p: len(s) for p, s in phase_ops.items()},
        device_wall_us=total(all_iv),
        reduce_us=reduce_us,
        matvec_us=phase_us["matvec"],
        hidden_us=hidden_us,
        exposed_us=exposed_us,
        overlap_efficiency=eff,
        iterations=int(iterations) if iterations is not None else None,
        exposed_per_iter_us=exposed_per_iter,
        n_device_events=len(events),
        unmapped_ops=unmapped,
        host_spans=host_spans(doc),
        label=label,
        timeline_path=src if isinstance(src, str) else None,
    )


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------

class Capture:
    """One profiling window: owns the jax.profiler dump dir, collects the
    jitted programs that executed inside it (noted by the session front
    door via :func:`active_capture`), and produces the HLO metadata maps.
    """

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.perfetto_path: Optional[str] = None
        self._programs: List[Tuple[Any, Any, Dict[str, Any]]] = []
        self._seen: set = set()
        self.hlo_maps: Dict[str, Dict[str, str]] = {}

    def note_program(self, fn: Any, args: Sequence[Any],
                     kwargs: Optional[Dict[str, Any]] = None) -> None:
        """Record a jitted program + abstract arg shapes for post-hoc
        HLO-map extraction (costs one re-lower per distinct program)."""
        if not hasattr(fn, "lower"):
            return
        import jax
        import jax.numpy as jnp

        def struct(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))

        structs = jax.tree_util.tree_map(struct, tuple(args))
        kwargs = dict(kwargs or {})
        key = (id(fn), str(structs), str(sorted(kwargs.items())))
        if key in self._seen:
            return
        self._seen.add(key)
        self._programs.append((fn, structs, kwargs))

    def finalize(self) -> Dict[str, Dict[str, str]]:
        """Lower + compile every noted program and merge the op maps.
        These programs just ran, so a lowering or compile error here is
        a defect and propagates."""
        for fn, structs, kwargs in self._programs:
            txt = fn.lower(*structs, **kwargs).compile().as_text()
            module, ops = hlo_op_map(txt)
            if module:
                self.hlo_maps.setdefault(module, {}).update(ops)
        self._programs.clear()
        return self.hlo_maps

    def analyze(self, iterations: Optional[int] = None,
                label: str = "") -> ProfileReport:
        self.finalize()
        if self.perfetto_path is None:
            self.perfetto_path = find_perfetto_trace(self.out_dir)
        if self.perfetto_path is None:
            raise FileNotFoundError(
                f"no perfetto_trace.json.gz under {self.out_dir!r} — did "
                "the capture context exit cleanly?")
        return analyze_timeline(self.perfetto_path, self.hlo_maps,
                                iterations=iterations, label=label)

    def save_hlo_map(self, path: Optional[str] = None) -> str:
        path = path or os.path.join(self.out_dir, "hlo_map.json")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            json.dump({"schema": "repro.observe/hlo-map/v1",
                       "modules": self.hlo_maps}, fh)
        return path


_ACTIVE: List[Capture] = []


def active_capture() -> Optional[Capture]:
    """The innermost open capture, if any (the api/service layers call
    this on every program invocation; None check is the fast path)."""
    return _ACTIVE[-1] if _ACTIVE else None


class capture:
    """Context manager: ``with capture(out_dir) as cap: ...`` wraps the
    body in ``jax.profiler.trace`` and locates the emitted perfetto
    timeline on exit.  Programs run through the session front door inside
    the window are noted on ``cap`` for HLO-map extraction.

    Warm (compile + run once) before entering the window, or compilation
    events will dominate the timeline.
    """

    def __init__(self, out_dir: str):
        self.cap = Capture(out_dir)
        self._ctx = None

    def __enter__(self) -> Capture:
        import jax

        os.makedirs(self.cap.out_dir, exist_ok=True)
        self._before = set(glob.glob(os.path.join(
            self.cap.out_dir, "plugins", "profile", "*")))
        self._ctx = jax.profiler.trace(self.cap.out_dir,
                                       create_perfetto_trace=True)
        self._ctx.__enter__()
        _ACTIVE.append(self.cap)
        return self.cap

    def __exit__(self, *exc) -> None:
        _ACTIVE.remove(self.cap)
        self._ctx.__exit__(*exc)
        runs = sorted(set(glob.glob(os.path.join(
            self.cap.out_dir, "plugins", "profile", "*"))) - self._before)
        for run in reversed(runs or []):
            hit = glob.glob(os.path.join(run, "perfetto_trace.json.gz"))
            if hit:
                self.cap.perfetto_path = hit[0]
                break
        if self.cap.perfetto_path is None:
            self.cap.perfetto_path = find_perfetto_trace(self.cap.out_dir)
