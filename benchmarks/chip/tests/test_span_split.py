"""CPU tests of ``span_split``: the innermost-span label at any depth of
nesting, span names with encoded arguments, self times, idle time
between and inside a program's runs, agreement with ``xplane.reduce`` on
a recorded trace, and one traced window of each loop kind at a tiny
size."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import span_split  # noqa: E402
import xplane  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "fleet256.xplane.pb")
MS = 1e6


def test_label_finds_the_innermost_span_at_any_depth():
    """Twelve nested spans, the innermost with 200 children: a gap in a
    child is the child's, a gap between children the innermost
    parent's, and one at each depth's own edge that depth's."""
    depth, kids = 12, 200
    spans = [(f"d{i}", float(i), 10_000.0 - 2 * i, "t") for i in range(depth)]
    base = float(depth)
    spans += [("child", base + 10 * k, 5.0, "t") for k in range(kids)]
    times = sorted([base + 10 * k + 1 for k in range(kids)]
                   + [base + 10 * k + 7 for k in range(kids)]
                   + [i + 0.5 for i in range(depth)] + [10_500.0])
    got = dict(zip(times, span_split.label(spans, times)))
    for k in range(kids):
        assert got[base + 10 * k + 1] == "child"
        assert got[base + 10 * k + 7] == f"d{depth - 1}"
    for i in range(depth):
        assert got[i + 0.5] == f"d{i}"
    assert got[10_500.0] == "outside_spans"


def test_label_matches_xplane_on_shallow_spans():
    spans = [("a", 0.0, 10.0), ("b", 2.0, 3.0), ("a", 20.0, 5.0)]
    inner = sorted((s, s + d, n) for n, s, d in spans)
    starts = [s for s, _, _ in inner]
    times = [0.0, 1.0, 2.0, 4.9, 5.0, 12.0, 20.0, 24.9, 25.0]
    assert span_split.label([s + ("t",) for s in spans], times) == [
        xplane._label(inner, starts, t) for t in times]


@pytest.mark.parametrize("event,name", [
    ("exchange.round#step=3#", "exchange.round"),
    ("trainer.dispatch#step=17,x=y#", "trainer.dispatch"),
    ("engine.wait", "engine.wait"),
])
def test_span_name_drops_encoded_arguments(event, name):
    assert span_split.span_name(event) == name


def test_program_name_drops_the_fingerprint():
    assert span_split.program_name(
        "jit_engine_step_score(13619069563863208909)") \
        == "jit_engine_step_score"


def test_self_times_per_host_line():
    spans = [("round", 0.0, 100.0, "main"), ("wait", 10.0, 30.0, "main"),
             ("fetch", 50.0, 20.0, "main"), ("leaf", 55.0, 5.0, "main"),
             # another thread's span overlapping the round is nobody's child
             ("other", 20.0, 10.0, "worker")]
    assert span_split.self_times(spans) == [50.0, 30.0, 15.0, 5.0, 10.0]


def test_module_gaps_and_idle_inside_runs():
    """Two runs of ``jit_step`` with a slice program between them: the
    idle between the runs is the gap less the slice; the idle inside a
    run is the run less its operations; the parts add up to the idle
    total."""
    devices = {"/device:TPU:0": [
        ("op", 1 * MS, 3 * MS),          # run 1 [1, 5): idle [4, 5)
        ("slice", 6 * MS, 1 * MS),       # between runs [5, 8): idle 2 ms
        ("op", 8 * MS, 4 * MS)]}         # run 2 [8, 12): busy whole
    modules = {"/device:TPU:0": [
        ("jit_step", 1 * MS, 4 * MS), ("jit_slice", 6 * MS, 1 * MS),
        ("jit_step", 8 * MS, 4 * MS)]}
    spans = [("window", 0.0, 14 * MS, "main"),
             ("exchange.round", 0.5 * MS, 6.9 * MS, "main"),
             ("engine.fetch_selected", 5 * MS, 2.2 * MS, "main"),
             ("exchange.round", 7.5 * MS, 6 * MS, "main")]
    s = span_split.reduce(devices, modules, spans)
    assert s["busy_s"] == pytest.approx(8e-3)
    assert s["idle_s"] == pytest.approx(6e-3)
    assert s["module_runs"] == {"jit_step": 2, "jit_slice": 1}
    assert s["module_s"]["jit_step"] == pytest.approx(8e-3)
    assert s["module_gap_s"]["jit_step"] == pytest.approx(2e-3)
    assert s["module_gap_s"]["jit_slice"] == 0.0
    assert s["module_idle_in_s"]["jit_step"] == pytest.approx(1e-3)
    gaps = dict(s["idle_gaps"])
    # idle [0, 1) began before any round, [4, 6) in round 1, [7, 8) while
    # fetching, [12, 14) in round 2
    assert gaps["outside_spans"] == pytest.approx(1e-3)
    assert gaps["exchange.round"] == pytest.approx(4e-3)
    assert gaps["engine.fetch_selected"] == pytest.approx(1e-3)
    assert sum(gaps.values()) == pytest.approx(s["idle_s"])
    assert s["span_n"] == {"exchange.round": 2, "engine.fetch_selected": 1}
    assert s["span_self_s"]["exchange.round"] == pytest.approx(10.7e-3)
    lay = span_split.layers(s, "exchange", 2, ("exchange.round",
                                               "engine.fetch_selected"))
    assert lay["exchange_host_ms.fleet"] == pytest.approx(6.45)
    assert lay["fleet_launch_gap_ms.fleet"] is None      # no jit_engine_*
    s["module_gap_s"]["jit_engine_step_score"] = 2e-3
    s["module_idle_in_s"]["jit_engine_step_score"] = 1e-3
    lay = span_split.layers(s, "exchange", 2, ("exchange.round",
                                               "engine.fetch_selected"))
    assert lay["fleet_launch_gap_ms.fleet"] == pytest.approx(1.0)
    split = lay["idle_split_s"]
    assert split["between_runs"] + split["inside_runs"] \
        + split["before_first_or_after_last_run"] == pytest.approx(6e-3)
    assert lay["idle_share_under_program_spans"] == pytest.approx(5 / 6)


def test_recorded_trace_agrees_with_xplane():
    """On a recorded fleet trace of one TPU v5 lite, with the benchmark's
    spans only, the busy time and the idle gaps are ``xplane.reduce``'s;
    its one program, ``jit_fused``, ran three times."""
    old = xplane.reduce(*xplane.load(FIXTURE, harness.SPAN_NAMES))
    devices, modules, spans = span_split.load(FIXTURE, harness.SPAN_NAMES)
    new = span_split.reduce(devices, modules, spans)
    assert new["busy_s"] == pytest.approx(old["busy_s"], rel=1e-12)
    assert new["window_s"] == old["window_s"]
    assert dict(new["idle_gaps"]) == pytest.approx(dict(old["idle_gaps"]))
    assert new["module_runs"] == {"jit_fused": 3}
    assert new["span_n"]["exchange.step"] == 3
    main = new["module_gap_s"]["jit_fused"] \
        + new["module_idle_in_s"]["jit_fused"]
    assert 0 < main <= new["idle_s"]


@pytest.mark.parametrize("loop", ["exchange", "train"])
def test_traced_window_finds_the_program_spans(loop):
    """A tiny traced window on the CPU (which has no device plane): the
    program's spans are found, one round or dispatch per step, and the
    host-side per-layer number reads; the device ones do not."""
    import copy

    from test_chip_loops import CELLS, TINY_CFG, TINY_TRAFFIC

    spec = copy.deepcopy(harness.cell_spec(CELLS[loop]))
    spec["cfg"].update(TINY_CFG)
    spec["traffic"].update(TINY_TRAFFIC[loop])
    out = span_split.run(spec, 7, 0.3, impl="xla", require_tpu=False,
                         compile_cache=False)
    steps = out["window"]["steps"]
    n = out["split"]["span_n"]
    lay = out["layers"]
    if loop == "exchange":
        assert n["exchange.round"] == n["engine.wait"] == steps
        assert n["exchange.step"] == steps
        assert lay["exchange_host_ms.fleet"] > 0
        assert lay["fleet_launch_gap_ms.fleet"] is None
    else:
        assert n["trainer.dispatch"] == steps
        assert n["trainer.round"] == n["trainer.sync"] \
            == out["window"]["rounds"]
        assert lay["trainer_host_ms.train"] > 0
        assert lay["train_launch_gap_ms.train"] is None
    assert "idle_split_s" not in lay
