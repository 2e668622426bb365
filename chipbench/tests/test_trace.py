"""The trace reduction on a synthetic trace, and the refusals of a run
without a chip or with an unknown device."""
import json
import types

import pytest

from chipbench import run, trace as tr
from chipbench.trace import Op, Span, Trace

MS = 1e6   # ns


def _costs():
    return {"distance_argmin": types.SimpleNamespace(
        PATTERN=r"^distance_argmin\b",
        cost=lambda cell: (2.0e9, 1.0e6))}


def _trace():
    # window 0..100 ms; fit spans 0..60 and 60..100; ops leave gaps
    ops = [Op("fusion.1", 0 * MS, 10 * MS),
           Op("fusion.2", 5 * MS, 15 * MS),                # overlaps
           Op("distance_argmin.3", 20 * MS, 40 * MS,
              "long_name=distance_argmin kernel"),
           Op("fusion.1", 45 * MS, 50 * MS),
           Op("distance_argmin.3", 70 * MS, 90 * MS,
              "long_name=distance_argmin kernel"),
           Op("fusion.9", 95 * MS, 130 * MS)]              # runs past end
    spans = [Span("window", 0, 100 * MS), Span("fit", 0, 60 * MS),
             Span("fit", 60 * MS, 100 * MS), Span("witness", 100 * MS,
                                                  200 * MS)]
    return Trace([ops], spans)


def test_busy_is_the_union_of_op_intervals():
    ops = tr.clip(_trace().devices[0], 0, 100 * MS)
    assert tr.merged(ops) == [(0, 15 * MS), (20 * MS, 40 * MS),
                              (45 * MS, 50 * MS), (70 * MS, 90 * MS),
                              (95 * MS, 100 * MS)]
    assert tr.busy_ns(ops) == pytest.approx(65 * MS)


def test_idle_gaps_cover_the_rest_of_the_window():
    ops = tr.clip(_trace().devices[0], 0, 100 * MS)
    gaps = tr.idle_gaps(ops, 0, 100 * MS)
    assert gaps == [(15 * MS, 20 * MS), (40 * MS, 45 * MS),
                    (50 * MS, 70 * MS), (90 * MS, 95 * MS)]
    assert sum(b - a for a, b in gaps) + tr.busy_ns(ops) == 100 * MS


def test_gap_is_labelled_by_the_innermost_harness_span():
    spans = _trace().spans
    assert tr.label_of((50 * MS, 58 * MS), spans) == "fit"
    assert tr.label_of((150 * MS, 160 * MS), spans) == "witness"
    assert tr.label_of((300 * MS, 310 * MS), spans) == "none"


def test_kernels_are_matched_through_their_cost_models():
    ops = tr.clip(_trace().devices[0], 0, 100 * MS)
    per, other = tr.match_kernels(ops, _costs())
    assert per["distance_argmin"]["launches"] == 2
    assert per["distance_argmin"]["seconds"] == pytest.approx(0.040)
    assert other == pytest.approx(0.025)      # 65 ms busy - 40 ms kernel


def test_reduce_and_breakdown():
    red = tr.reduce(_trace(), _costs())
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.065)
    assert red["other_s"] == pytest.approx(0.025)
    bd = red["breakdown"]
    assert set(bd) == {"device_ops", "idle_gaps"}
    assert bd["device_ops"][0][0] == "distance_argmin.3"
    assert bd["device_ops"][0][1] == pytest.approx(0.040)
    assert bd["idle_gaps"][0] == ["fit", pytest.approx(0.020)]
    assert len(bd["device_ops"]) <= tr.TOP
    json.dumps(red)


def test_reduce_averages_over_devices():
    t = _trace()
    t.devices.append([Op("distance_argmin.3", 0, 100 * MS,
                         "long_name=distance_argmin kernel")])
    red = tr.reduce(t, _costs())
    assert red["busy_s"] == pytest.approx((0.065 + 0.1) / 2)
    assert red["kernels"]["distance_argmin"]["launches"] == 1.5


def test_a_kernel_without_launches_is_an_error():
    costs = dict(_costs(), lloyd_step_ft=types.SimpleNamespace(
        PATTERN=r"^lloyd_step_ft\b", cost=lambda cell: (1.0, 1.0)))
    red = tr.reduce(_trace(), costs)
    assert red["kernels"]["lloyd_step_ft"]["launches"] == 0
    with pytest.raises(run.BenchError, match="lloyd_step_ft"):
        run.require_launches(red, "ops.json")
    run.require_launches(tr.reduce(_trace(), _costs()), "ops.json")


def test_an_op_that_holds_others_is_not_counted():
    t = _trace()
    # a scan's while loop spans the ops it runs, gap at 15..20 included
    t.devices[0].append(Op("while.7", 0, 60 * MS))
    red = tr.reduce(t, _costs())
    assert red["busy_s"] == pytest.approx(0.065)
    assert red["other_s"] == pytest.approx(0.025)
    assert all(n != "while.7" for n, _ in red["breakdown"]["device_ops"])
    table = {r[0]: r for r in tr.op_table(t)}
    assert table["*while.7"][1] == 1 and "while.7" not in table


def test_window_span_must_be_unique():
    t = _trace()
    t.spans.append(Span("window", 0, 1))
    with pytest.raises(ValueError):
        tr.window_of(t)


def test_unknown_device_kind_is_an_error():
    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(run.BenchError):
        run.peaks_for("TPU v99")


def test_without_a_tpu_the_command_exits_nonzero(capsys):
    rc = run.main(["--workload", "ivf4096-fit", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "needs a TPU" in err


def test_unknown_workload_is_an_error(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1",
                     "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("text,name", [
    ("%distance_argmin.2 = (f32[1000448,1]{1,0:T(8,128)}, s32[1000448,1]) "
     "custom-call(f32[1000448,128] %p0), custom_call_target=\"tpu_custom_call\"",
     "distance_argmin.2"),
    ("%while = (s32[]{:T(128)}, f32[4096,128]) while(%tuple.17)", "while"),
    ("fusion.3", "fusion.3"),
])
def test_op_name_is_the_hlo_instruction(text, name):
    """TPU events carry their whole HLO line; kernels are matched, and the
    breakdown is keyed, by the instruction's name."""
    assert tr.op_name(text) == name
