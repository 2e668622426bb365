"""The program's scopes and spans read on a synthetic trace: scope paths
from the XSpace proto's event metadata, device time per scope, idle time
by program span and by program, and the one-clock check."""
import json
import types

import pytest

from chipbench import probe, scopes, trace as tr
from chipbench.trace import Op, Span, Trace

MS = 1e6   # ns
PATH = "jit(chunk)/while/body/closed_call/cond/branch_0_fun/kmeans.step"


def _costs():
    return {"distance_argmin": types.SimpleNamespace(
        PATTERN=r"^distance_argmin\b", cost=lambda cell: (1.0, 1.0))}


def _trace():
    # window 0..100 ms; one fit 0..90; two chunk programs, each opened by
    # a dispatch and read back by a sync
    ops = [Op("distance_argmin.2", 10 * MS, 30 * MS),
           Op("fusion.4", 30 * MS, 35 * MS),
           Op("sort.2", 40 * MS, 42 * MS),
           Op("slice_add_fusion", 60 * MS, 70 * MS),
           Op("copy.3", 70 * MS, 71 * MS)]
    spans = [Span("window", 0, 100 * MS), Span("fit", 0, 90 * MS)]
    return Trace([ops], spans)


SCOPES = {"distance_argmin.2": "kmeans.assign", "fusion.4": "kmeans.update",
          "sort.2": "kmeans.reseed", "slice_add_fusion": "kmeans.partials",
          "copy.3": None}
MODULES = [Op("jit_chunk(1)", 8 * MS, 45 * MS),
           Op("jit_chunk(1)", 58 * MS, 72 * MS)]
PSPANS = [Span("kmeans.fit", 1 * MS, 89 * MS),
          Span("kmeans.dispatch", 5 * MS, 7 * MS),
          Span("kmeans.sync", 7 * MS, 46 * MS),
          Span("kmeans.dispatch", 50 * MS, 52 * MS),
          Span("kmeans.sync", 52 * MS, 80 * MS)]


@pytest.mark.parametrize("path,scope", [
    (f"{PATH}/kmeans.update/reduce_sum:", "kmeans.update"),
    (f"{PATH}/kmeans.assign/kmeans.partials/add:", "kmeans.partials"),
    ("jit(chunk)/while:", None),
    ("/src/repro/core/kmeans.py:125", None),
    ("", None),
])
def test_the_innermost_scope_names_the_op(path, scope):
    assert scopes.scope_of(path) == scope


# -- a hand-encoded XSpace (xplane.proto field numbers) ---------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _event_md(id_, name, stats):
    return _field(4, _msg((1, id_), (2, _msg(
        (1, id_), (2, name), *[(5, s) for s in stats]))))


def _stat_md(id_, name):
    return _field(5, _msg((1, id_), (2, _msg((1, id_), (2, name)))))


def _xspace():
    device = b"".join([
        _field(1, 3), _field(2, "/device:TPU:0"),
        _field(3, _msg((1, 9), (2, "XLA Ops"))),         # a line, skipped
        _stat_md(1, "hlo_category"), _stat_md(2, "tf_op"),
        _stat_md(3, f"{PATH}/kmeans.reseed/jit(argsort)/sort:"),
        _event_md(1, "%fusion.4 = f32[4096] fusion(s32[8] %p), kind=kLoop",
                  [_msg((1, 1), (5, "loop fusion")),
                   _msg((1, 2), (5, f"{PATH}/kmeans.update/reduce_sum:"))]),
        _event_md(2, "%sort.2 = (f32[8], s32[8]) sort(f32[8] %a)",
                  [_msg((1, 2), (7, 3))]),               # interned path
        _event_md(3, "%copy-start.4 = f32[10] copy-start(f32[10] %h)", []),
        _event_md(4, "%fusion.9 = f32[8] fusion(f32[8] %a)",
                  [_msg((1, 2), (5, f"{PATH}/kmeans.update/mul:"))]),
        _event_md(5, "%fusion.9 = f32[8] fusion(f32[8] %b)",
                  [_msg((1, 2), (5, f"{PATH}/kmeans.reseed/neg:"))]),
    ])
    host = _msg((2, "/host:CPU")) + _event_md(
        1, "kmeans.fit", [_msg((1, 2), (5, f"{PATH}/kmeans.assign/x:"))])
    return _msg((1, device), (1, host), (2, "an error"))


def test_scopes_are_read_from_the_event_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    assert scopes.op_scopes(str(path)) == {
        "fusion.4": "kmeans.update", "sort.2": "kmeans.reseed",
        "copy-start.4": None,
        "fusion.9": None}        # two programs disagree: left unscoped


def test_scope_seconds_leave_out_the_costed_kernels():
    ops = _trace().devices[0]
    secs = scopes.scope_seconds(ops, SCOPES, _costs())
    assert "kmeans.assign" not in secs
    assert secs == pytest.approx({
        "kmeans.update": 0.005, "kmeans.reseed": 0.002,
        "kmeans.partials": 0.010, "unscoped": 0.001})


def test_without_scopes_there_is_nothing_to_read():
    ops = _trace().devices[0]
    assert scopes.scope_seconds(ops, {}, _costs()) is None
    assert scopes.scope_seconds([], SCOPES, _costs()) is None


def test_a_program_span_nested_in_fit_labels_the_gap():
    spans = _trace().spans + PSPANS
    # the gap 45..58 ms lies under fit, sync (to 46) and dispatch (50..52)
    assert tr.label_of((47 * MS, 49 * MS), spans) == "kmeans.fit"
    assert tr.label_of((20 * MS, 22 * MS), spans) == "kmeans.sync"
    idle = scopes.idle_by_program_span([(45 * MS, 58 * MS)], PSPANS)
    assert idle == pytest.approx({"kmeans.sync": 0.001 + 0.006,
                                  "kmeans.dispatch": 0.002,
                                  "kmeans.fit": 0.004})
    assert scopes.idle_by_program_span([(95 * MS, 99 * MS)], PSPANS) == \
        pytest.approx({"none": 0.004})


def test_idle_splits_into_in_and_between_programs():
    red = scopes.reduce_program(_trace(), [MODULES], PSPANS, SCOPES,
                                _costs())
    # gaps: 0-10, 35-40, 42-60, 71-100 = 62 ms; inside programs:
    # 8-10, 35-40, 42-45, 58-60, 71-72 = 13 ms
    assert red["idle_in_programs_s"] == pytest.approx(0.013)
    assert red["idle_between_programs_s"] == pytest.approx(0.049)
    assert red["idle_in_programs_s"] + red["idle_between_programs_s"] == \
        pytest.approx(0.1 - tr.busy_ns(_trace().devices[0]) * 1e-9)
    # between programs, inside the program's fit: 1-8, 45-58, 72-89
    assert red["fit_host_s"] == pytest.approx(0.037)
    assert sum(red["idle_by_program_span"].values()) == pytest.approx(0.062)
    assert red["scopes"]["kmeans.partials"] == pytest.approx(0.010)
    assert red["one_clock"]["held"] == 2
    json.dumps(red)


def test_one_clock_holds_chunk_by_chunk():
    assert scopes.one_clock(MODULES, PSPANS) == {
        "chunks": 2, "dispatches": 2, "held": 2, "faults": []}
    early = [Op("jit_chunk(1)", 4 * MS, 45 * MS)] + MODULES[1:]
    assert scopes.one_clock(early, PSPANS)["held"] == 1
    late = MODULES[:1] + [Op("jit_chunk(1)", 58 * MS, 81 * MS)]
    assert scopes.one_clock(late, PSPANS)["held"] == 1


def test_the_probe_without_a_tpu_exits_nonzero(capsys):
    rc = probe.main(["--workload", "ivf4096-fit", "--seeds", "1",
                     "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == "" and "needs a TPU" in err
