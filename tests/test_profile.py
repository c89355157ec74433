"""repro.observe.profile: trace parsing + overlap math on golden
timelines (device-free, deterministic).

The committed fixtures under tests/data/ are synthetic Chrome
trace-event timelines in exactly the shape ``jax.profiler.trace``'s
perfetto export produces (``ph: "X"`` device ops carrying
``args.hlo_op`` / ``args.hlo_module``):

* ``timeline_exposed.json`` — every all-reduce runs strictly AFTER the
  matvec's collective-permute finished: fully exposed communication,
  overlap efficiency 0.
* ``timeline_hidden.json`` — every all-reduce runs on a second device
  lane entirely inside the matvec's window: fully hidden, efficiency 1.

These pin the headline math the runtime captures feed
(``bench_overlap``'s measured section, ``session.solve(profile=)``).
"""
import json
import os

import pytest

from repro.observe import profile as P

DATA = os.path.join(os.path.dirname(__file__), "data")


def _load(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# interval math
# ---------------------------------------------------------------------------

def test_merge_intervals_coalesces_and_sorts():
    assert P.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 7)]) == \
        [(0, 3), (5, 7)]


def test_merge_intervals_drops_empty():
    assert P.merge_intervals([(3, 3), (4, 2)]) == []


def test_intersect_intervals_two_pointer():
    a = [(0, 10), (20, 30)]
    b = [(5, 25), (28, 40)]
    assert P.intersect_intervals(a, b) == [(5, 10), (20, 25), (28, 30)]


def test_total():
    assert P.total([(0, 3), (5, 7)]) == 5


# ---------------------------------------------------------------------------
# golden timelines: the two extremes of the headline number
# ---------------------------------------------------------------------------

def test_fully_exposed_timeline():
    rep = P.analyze_timeline(_load("timeline_exposed.json"))
    assert rep.overlap_efficiency == 0.0
    assert rep.hidden_us == 0.0
    assert rep.reduce_us == pytest.approx(100.0)
    assert rep.exposed_us == pytest.approx(100.0)
    assert rep.matvec_us == pytest.approx(200.0)
    # iterations estimated from the most-run reduce op (2 all-reduces)
    assert rep.iterations == 2
    assert rep.exposed_per_iter_us == pytest.approx(50.0)
    # the unmapped fusion.9 falls into "other" via name heuristics
    assert rep.phase_us["other"] == pytest.approx(60.0)
    assert rep.n_device_events == 6
    # device wall is the union of all op intervals: [0,180] + [200,380]
    assert rep.device_wall_us == pytest.approx(360.0)
    # the host-side TraceAnnotation span is aggregated, not a device op
    assert rep.host_spans["api.solve"]["count"] == 1
    assert rep.host_spans["api.solve"]["total_us"] == pytest.approx(400.0)


def test_fully_hidden_timeline():
    rep = P.analyze_timeline(_load("timeline_hidden.json"))
    assert rep.overlap_efficiency == pytest.approx(1.0)
    assert rep.exposed_us == pytest.approx(0.0)
    assert rep.hidden_us == pytest.approx(80.0)
    assert rep.exposed_per_iter_us == pytest.approx(0.0)


def test_partial_overlap_half_hidden():
    doc = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 100.0,
         "args": {"hlo_op": "collective-permute.1", "hlo_module": "m"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 50.0, "dur": 100.0,
         "args": {"hlo_op": "all-reduce.1", "hlo_module": "m"}},
    ]}
    rep = P.analyze_timeline(doc)
    assert rep.overlap_efficiency == pytest.approx(0.5)
    assert rep.hidden_us == pytest.approx(50.0)
    assert rep.exposed_us == pytest.approx(50.0)


def test_no_reduce_time_means_no_efficiency():
    doc = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0,
         "args": {"hlo_op": "fusion.1", "hlo_module": "m"}},
    ]}
    rep = P.analyze_timeline(doc)
    assert rep.overlap_efficiency is None
    assert rep.exposed_per_iter_us is None


def test_concurrent_reduce_ops_not_double_counted():
    # two overlapping all-reduces on different lanes: union, not sum
    doc = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 100.0,
         "args": {"hlo_op": "all-reduce.1", "hlo_module": "m"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 50.0, "dur": 100.0,
         "args": {"hlo_op": "all-reduce.2", "hlo_module": "m"}},
    ]}
    rep = P.analyze_timeline(doc)
    assert rep.reduce_us == pytest.approx(150.0)


def test_explicit_iterations_override():
    rep = P.analyze_timeline(_load("timeline_exposed.json"), iterations=4)
    assert rep.iterations == 4
    assert rep.exposed_per_iter_us == pytest.approx(25.0)


# ---------------------------------------------------------------------------
# HLO metadata map
# ---------------------------------------------------------------------------

_HLO_TEXT = """\
HloModule jit_solve_program, entry_computation_layout={(f64[64]{0})->f64[64]{0}}

%fused_computation.1 (param_0.1: f64[64]) -> f64[9] {
  %param_0.1 = f64[64]{0} parameter(0)
  ROOT %dot.1 = f64[9]{0} dot(%param_0.1, %param_0.1), metadata={op_name="jit(solve_program)/jit(main)/while/body/repro.reduce/dot_general"}
}

%fused_computation.2 (param_0.2: f64[64]) -> f64[64] {
  %param_0.2 = f64[64]{0} parameter(0)
  ROOT %mul.3 = f64[64]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(solve_program)/jit(main)/while/body/repro.axpy/mul"}
}

ENTRY %main.1 (Arg_0.1: f64[64]) -> f64[64] {
  %Arg_0.1 = f64[64]{0} parameter(0)
  %fusion.1 = f64[9]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(solve_program)/jit(main)/while/body/reduce_sum"}
  %fusion.2 = f64[64]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(solve_program)/jit(main)/while/body/add"}
  ROOT %add.5 = f64[64]{0} add(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(solve_program)/jit(main)/while/body/repro.matvec/add"}
}
"""


def test_hlo_op_map_module_and_direct_scopes():
    module, ops = P.hlo_op_map(_HLO_TEXT)
    assert module == "jit_solve_program"
    assert "repro.matvec" in ops["add.5"]


def test_hlo_op_map_attributes_fusions_by_body():
    # the fusion instruction's own metadata has no repro.* tag; the tag
    # comes from the instructions inside its called computation
    _, ops = P.hlo_op_map(_HLO_TEXT)
    assert "repro.reduce" in ops["fusion.1"]
    assert "repro.axpy" in ops["fusion.2"]
    assert P.classify_op("fusion.1", ops["fusion.1"]) == "reduce"
    assert P.classify_op("fusion.2", ops["fusion.2"]) == "axpy"


def test_classify_op_name_fallbacks():
    assert P.classify_op("all-reduce.17") == "reduce"
    assert P.classify_op("collective-permute.3") == "matvec"
    assert P.classify_op("copy.2") == "other"


def test_analyze_with_hlo_map_and_spmd_prefix_fallback():
    _, ops = P.hlo_op_map(_HLO_TEXT)
    maps = {"jit_solve_program": ops}
    doc = {"traceEvents": [
        # exact module match
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0,
         "args": {"hlo_op": "fusion.1",
                  "hlo_module": "jit_solve_program"}},
        # SPMD-renamed module: matched by prefix
        {"ph": "X", "pid": 1, "tid": 1, "ts": 20.0, "dur": 10.0,
         "args": {"hlo_op": "fusion.2",
                  "hlo_module": "jit_solve_program.spmd"}},
    ]}
    rep = P.analyze_timeline(doc, hlo_maps=maps)
    assert rep.phase_us["reduce"] == pytest.approx(10.0)
    assert rep.phase_us["axpy"] == pytest.approx(10.0)
    assert rep.unmapped_ops == 0


def test_halo_time_is_reported_inside_matvec():
    """The exchange (scope repro.halo inside repro.matvec, or a bare
    collective-permute) counts in matvec and is reported as halo_us."""
    maps = {"m": {"fusion.7": "jit(p)/while/body/repro.matvec/repro.halo/"
                              "ppermute"}}
    doc = {"traceEvents": [
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0.0, "dur": 10.0,
         "args": {"hlo_op": "fusion.7", "hlo_module": "m"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 5.0, "dur": 10.0,
         "args": {"hlo_op": "collective-permute-done.3", "hlo_module": "m"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 20.0, "dur": 30.0,
         "args": {"hlo_op": "stencil_fusion.1", "hlo_module": "m"}},
    ]}
    rep = P.analyze_timeline(doc, hlo_maps=maps)
    assert rep.halo_us == pytest.approx(15.0)
    assert rep.matvec_us == pytest.approx(45.0)
    assert set(rep.phase_us) == set(P.PHASES)
    assert "halo" in rep.render()
    assert P.ProfileReport.from_json(rep.to_json()).halo_us == rep.halo_us


# ---------------------------------------------------------------------------
# report round-trip
# ---------------------------------------------------------------------------

def test_report_save_load_roundtrip(tmp_path):
    rep = P.analyze_timeline(_load("timeline_exposed.json"),
                             label="golden/exposed")
    p = rep.save(str(tmp_path / "profile.json"))
    back = P.ProfileReport.load(p)
    assert back == rep
    with open(p) as fh:
        assert json.load(fh)["schema"] == P.SCHEMA_PROFILE


def test_render_mentions_headline(capsys=None):
    rep = P.analyze_timeline(_load("timeline_hidden.json"))
    text = rep.render()
    assert "overlap efficiency 1.000" in text


# ---------------------------------------------------------------------------
# noting: the capture's program notes without a profiler of its own
# ---------------------------------------------------------------------------

def test_noting_gives_the_capture_map_without_a_profiler(tmp_path):
    """A caller with its own profiler enters ``noting``: the session
    notes its programs there exactly as under ``capture``, so
    ``finalize`` gives the same HLO map."""
    import numpy as np

    import repro
    from repro.core import matrices as M

    op, b, _ = M.poisson3d(5)
    session = repro.make_solver("p-bicgsafe", op)
    b = np.asarray(b, np.float32)
    session.solve(b)                                  # warm
    with P.noting() as cap:
        assert P.active_capture() is cap
        session.solve(b)
    assert P.active_capture() is None
    noted = cap.finalize()
    with P.capture(str(tmp_path / "traced")) as cap2:
        session.solve(b)
    assert cap2.perfetto_path is not None
    assert noted == cap2.finalize()
    phases = {P.classify_op(op_, scope) for ops in noted.values()
              for op_, scope in ops.items()}
    assert {"matvec", "reduce", "axpy"} <= phases
