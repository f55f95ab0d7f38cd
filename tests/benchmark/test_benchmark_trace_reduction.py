"""``reduce/trace.py`` on the small recorded trace: three score calls of the
fused kernel on one TPU v5 lite (7, 100 and 4096 rows). Every expected
number below is worked out by hand from the events in
``benchmark/reduce/fixtures/three_dispatches.textproto``."""

import os

import pytest

from benchmark.reduce import costs, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(ROOT, "benchmark", "reduce", "fixtures",
                       "three_dispatches.textproto")
MLP = dict(in_bytes_per_value=2, weight_bytes_per_value=2,
           out_bytes_per_row=4)

WINDOW_NS = 12_568_208 - 1_000  # first event's start to the last span's end
BUSY_NS = 14_568  # 38 operations, back to back inside 3 programs
KERNEL_NS = 1_008 + 1_253 + 5_856  # the three custom-calls
OPEN_NS = 1_682_030 + 1_958_360 + 3_375_110  # the three score spans


@pytest.fixture(scope="module")
def planes():
    return trace.load(FIXTURE, "bench.score")


@pytest.fixture(scope="module")
def summary(planes):
    return trace.reduce(planes, op_line="XLA Ops",
                        kernel_patterns=["custom-call"])


@pytest.mark.parametrize("field,want", [
    ("window_s", WINDOW_NS / 1e9),
    ("busy_s", BUSY_NS / 1e9),
    ("kernel_s", KERNEL_NS / 1e9),
    ("kernel_events", 3),
    ("span_rows", 7 + 100 + 4096),
    ("span_count", 3),
    ("n_devices", 1),
    ("idle_share_pct", 100.0 * (1.0 - BUSY_NS / WINDOW_NS)),
])
def test_the_recorded_trace_gives_the_known_numbers(summary, field, want):
    assert getattr(summary, field) == pytest.approx(want, rel=1e-9)


def test_idle_time_splits_into_inside_and_between_dispatches(summary):
    gaps = summary.gap_seconds
    assert gaps["inside a dispatch"] == pytest.approx(
        (OPEN_NS - BUSY_NS) / 1e9, rel=1e-9)
    assert gaps["between dispatches"] == pytest.approx(
        (WINDOW_NS - OPEN_NS) / 1e9, rel=1e-9)
    assert sum(gaps.values()) + summary.busy_s == pytest.approx(
        summary.window_s, rel=1e-9)


def test_breakdown_names_the_operations_that_took_most_time(summary):
    b = summary.breakdown()
    assert b["device_ops"][0] == [
        "%fused_mlp_score.1 custom-call f32[4096,1]", pytest.approx(5.856e-6)]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 2
    assert b["idle_gaps"][0][0] == "inside a dispatch"
    seconds = [s for _, s in b["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)


SCOPED = os.path.join(ROOT, "benchmark", "reduce", "fixtures",
                      "scoped_dispatches.textproto")
WHILE = ("%while.2 = (s32[], bf16[8,8]{1,0}) while((s32[], bf16[8,8]{1,0}) "
         "%tuple.9), condition=%cond, body=%body")
FUSION = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop"


def test_breakdown_leaves_out_a_container_whose_children_are_listed():
    """``scoped_dispatches.textproto``: in each of two programs a
    ``%while.2`` of 100 us around two ``%fusion.3`` of 60 and 40 us. The
    list names operations: the loop's time is its children's."""
    s = trace.reduce(trace.load(SCOPED, "bench.score"), op_line="XLA Ops",
                     kernel_patterns=["custom-call"])
    assert not any("while" in name for name in s.op_seconds)
    assert not any("while" in name
                   for name, _ in s.breakdown()["device_ops"])
    (name, seconds), = s.container_seconds.items()
    assert name.startswith("%while.2 = ") and seconds == pytest.approx(200e-6)
    assert s.op_seconds["%fusion.3 fusion f32[256,2560]"] == pytest.approx(
        200e-6)
    # 2 x 350 us of operations and the 50 us before the first program
    assert sum(s.op_seconds.values()) == pytest.approx(s.busy_s) == \
        pytest.approx(750e-6)


def test_a_container_without_children_in_the_capture_is_kept_and_marked():
    ops = [trace.Event(FUSION, 0.0, 10.0, {}),
           trace.Event(WHILE, 10.0, 100.0, {}),
           trace.Event(FUSION, 110.0, 10.0, {})]
    planes = {"/device:TPU:0": {"XLA Ops": ops}, "/host:CPU": {"python3": [
        trace.Event("bench.score", 0.0, 200.0, {"rows": 1})]}}
    s = trace.reduce(planes, op_line="XLA Ops", kernel_patterns=[".*"])
    assert s.container_seconds == {}
    marked = trace.CHILDLESS + trace.short_name(WHILE)
    assert s.op_seconds[marked] == pytest.approx(100e-9)
    assert s.breakdown()["device_ops"][0] == [marked, pytest.approx(100e-9)]
    # with a child inside it, the same loop leaves the list
    inside = [*ops, trace.Event(FUSION, 20.0, 30.0, {})]
    planes["/device:TPU:0"]["XLA Ops"] = inside
    s = trace.reduce(planes, op_line="XLA Ops", kernel_patterns=[".*"])
    assert list(s.container_seconds) == [trace.short_name(WHILE)]
    assert list(s.op_seconds) == [trace.short_name(FUSION)]


@pytest.mark.parametrize("patterns,kernel_ns,want_pct", [
    # 4203 rows x 147,004 operations at 197e12/s = 3.1363 us: compute-bound
    # (the 715,980 bytes take 0.874 us at 819e9/s)
    (["custom-call"], KERNEL_NS, 100 * 3.136334e-6 / 8.117e-6),
    ([".*"], BUSY_NS, 100 * 3.136334e-6 / 14.568e-6),
])
def test_roofline_share_of_the_recorded_kernel(planes, patterns, kernel_ns,
                                               want_pct):
    s = trace.reduce(planes, op_line="XLA Ops", kernel_patterns=patterns)
    assert s.kernel_s == pytest.approx(kernel_ns / 1e9)
    flop, moved = costs.mlp_costs([30, 256, 256, 1], s.span_rows,
                                  s.span_count, **MLP)
    assert flop == 4203 * 147_004 and moved == 4203 * 64 + 3 * 148_996
    share, bound = trace.roofline_share(flop, moved, s.kernel_s,
                                        "TPU v5 lite")
    assert bound == "compute"
    assert share == pytest.approx(want_pct, rel=1e-5)


def test_a_small_batch_is_bound_by_bandwidth():
    flop, moved = costs.mlp_costs([30, 256, 256, 1], 7, 1, **MLP)
    share, bound = trace.roofline_share(flop, moved, 1.008e-6, "TPU v5 lite")
    assert bound == "bandwidth"  # the weights, once per call
    assert share == pytest.approx(100 * (moved / 819e9) / 1.008e-6)


@pytest.mark.parametrize("kernel_s,kind,error", [
    (1e-6, "TPU v5 lite", ValueError),  # 313%: time leaves out work
    (3.0e-6, "TPU v5 lite", ValueError),  # 104.5% passes, see below; 3.0
    (0.0, "TPU v5 lite", ValueError),
    (8e-6, "TPU v9 imaginary", KeyError),  # no peaks: an error, no default
])
def test_a_share_over_105_or_an_unknown_device_raises(kernel_s, kind, error):
    flop = 4203 * 147_004  # 3.1363 us at the bf16 peak
    if kernel_s == 3.0e-6:  # just under the line does not raise
        share, _ = trace.roofline_share(flop, 0.0, kernel_s, kind)
        assert 104.0 < share <= 105.0
        kernel_s = 2.9e-6  # 108%: does
    with pytest.raises(error):
        trace.roofline_share(flop, 0.0, kernel_s, kind)


def test_a_trace_without_device_operations_is_refused(planes):
    host_only = {k: v for k, v in planes.items() if k == "/host:CPU"}
    with pytest.raises(ValueError):
        trace.reduce(host_only, op_line="XLA Ops", kernel_patterns=[".*"])
    with pytest.raises(ValueError):
        trace.reduce(planes, op_line="No Such Line", kernel_patterns=[".*"])


def test_overlapping_intervals_count_once():
    assert trace.union_s([(0, 10), (5, 20), (30, 40)]) == pytest.approx(30e-9)
    assert trace.union_s([]) == 0.0


SEQ = {"kind": "seq", "length": 2, "num_features": 3, "d_model": 4,
       "n_blocks": 2, "mlp_mult": 4, "in_bytes_per_value": 4,
       "weight_bytes_per_value": 4, "out_bytes_per_row": 4}


@pytest.mark.parametrize("rows,dispatches,flop,moved", [
    # by hand: embedding 48, the full block 768 + 64, of the last block
    # keys and values 128, one position's projections and feed-forward 320,
    # its attention 32, the head 8; a history is 24 bytes in and 4 out, the
    # weights 12 + 2 x 192 + 4 values of 4 bytes, once a call
    (1, 1, 1368, 28 + 1600),
    (10, 2, 13680, 280 + 3200),
])
def test_seq_costs_by_hand(rows, dispatches, flop, moved):
    assert costs.of(SEQ, rows, dispatches) == (flop, moved)


def test_costs_are_found_by_the_kind_the_configuration_names():
    mlp = {"dims": [30, 256, 256, 1], **MLP}
    assert costs.of(mlp, 7, 1) == costs.mlp_costs([30, 256, 256, 1], 7, 1,
                                                  **MLP)
    with pytest.raises(ValueError):
        costs.of({"kind": "no_such_model"}, 1, 1)
