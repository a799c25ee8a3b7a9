"""The program-trace reduction on hand-built events: scope and pass from a
name stack, device-idle time split over the training loop's spans by
overlap (the innermost span winning, other threads' spans apart), the new
per-layer readers, and the xplane's event metadata and HLO read from
protobuf bytes built here."""
import _paths  # noqa: F401
import pytest

from benchlib import program_trace as pt_

MS = 1_000_000
Span, Op = pt_.Span, pt_.DeviceOp


@pytest.mark.parametrize("name, want", [
    ("jit(loss_fn)/jvp(jvp(symcon))/mul", ("symcon", 0)),
    ("jit(loss_fn)/jvp(transpose(jvp(jvp(interaction))))/interaction_bwd/"
     "pallas_call", ("interaction", 1)),
    ("jit(loss_fn)/transpose(jvp(jvp(linear)))/nkd,kq->nqd/dot_general",
     ("linear", 1)),
    # a kernel's backward rule adds transforms in a later component
    ("jit(loss_fn)/transpose(jvp(transpose(jvp(jvp(symcon)))))/"
     "transpose(jvp(symcon_xla))/mul", ("symcon", 2)),
    ("jit(rank_step)/shard_map/allreduce/psum", ("allreduce", 0)),
    ("jit(loss_fn)/transpose(jvp(jvp()))/mul", (None, 1)),
    ("jit(multiply)/mul", (None, 0)),
    ("", (None, 0)),
])
def test_scope_and_pass_of_a_name_stack(name, want):
    assert pt_.classify(name) == want


def _trace():
    spans = [
        Span("train.step", 0, 60 * MS, {"step_num": 1, "atoms": [10, 30],
                                        "compiles": 0}),
        Span("train.wait_batch", 0, 10 * MS, {}),
        Span("data.fetch", 1 * MS, 9 * MS, {}),
        Span("engine.step", 10 * MS, 40 * MS, {}),
        Span("engine.grad_wait", 20 * MS, 30 * MS, {}),
        Span("train.ema_update", 40 * MS, 55 * MS, {}),
        Span("train.step", 60 * MS, 100 * MS, {"step_num": 2,
                                               "atoms": [20, 20],
                                               "compiles": 0}),
        Span("train.wait_batch", 60 * MS, 70 * MS, {}),
        # a step that closed without counters (its metrics raised)
        Span("train.step", 100 * MS, 110 * MS, {"step_num": 3}),
    ]
    dev = [Op(10 * MS, 20 * MS, "interaction", 0),
           Op(30 * MS, 40 * MS, "symcon", 2),
           Op(70 * MS, 80 * MS, "interaction", 2),
           Op(80 * MS, 85 * MS, "allreduce", 0),
           Op(85 * MS, 90 * MS, None, 1)]
    return pt_.from_events(spans, {"/device:TPU:0": dev}, (0, 120 * MS))


def test_labels_take_the_innermost_open_span():
    pieces = pt_.labels(_trace())
    assert pieces[:4] == [(0, 1 * MS, "train.wait_batch"),
                          (1 * MS, 9 * MS, "data.fetch"),
                          (9 * MS, 10 * MS, "train.wait_batch"),
                          (10 * MS, 20 * MS, "engine.step")]
    assert pieces[-1] == (110 * MS, 120 * MS, pt_.UNATTRIBUTED)


def test_idle_is_split_by_overlap():
    idle = pt_.idle_by_span(_trace())
    # idle: [0,10] [20,30] [40,70] [90,120]; the last step closes at 110
    assert idle == pytest.approx({
        "train.wait_batch": 2 + 10, "data.fetch": 8, "engine.grad_wait": 10,
        "train.ema_update": 15, "train.step": 5 + 20, "unattributed": 10})


def test_idle_goes_to_the_loops_thread_and_the_producer_is_apart():
    # prefetch: the producer thread fetches the next batch while the loop
    # waits for its gradient and updates the EMA
    loop, producer = "/host:CPU#0", "/host:CPU#1"
    spans = [
        Span("train.step", 0, 50 * MS, {"step_num": 1, "compiles": 0}, loop),
        Span("train.wait_batch", 0, 5 * MS, {}, loop),
        Span("engine.grad_wait", 5 * MS, 30 * MS, {}, loop),
        Span("train.ema_update", 30 * MS, 45 * MS, {}, loop),
        Span("data.fetch", 10 * MS, 40 * MS, {}, producer),
        Span("data.collate", 12 * MS, 38 * MS, {}, producer),
    ]
    pt = pt_.from_events(spans, {"/device:TPU:0": [Op(5 * MS, 15 * MS,
                                                      "interaction", 2)]},
                         (0, 50 * MS))
    # idle: [0,5] [15,50]
    assert pt_.idle_by_span(pt) == pytest.approx({
        "train.wait_batch": 5, "engine.grad_wait": 15,
        "train.ema_update": 15, "train.step": 5})
    assert pt_.other_thread_ms(pt) == pytest.approx({"data.fetch": 30,
                                                     "data.collate": 26})
    assert any("data.fetch 30.0" in line for line in pt_.notes(pt))


def test_window_steps_are_the_spans_with_counters():
    steps = pt_.step_spans(_trace(), 5)
    assert [s.args["step_num"] for s in steps] == [1, 2]
    assert pt_.host_ms(_trace(), "train.wait_batch") == pytest.approx(20.0)


def test_device_time_is_averaged_over_devices():
    pt = pt_.from_events(
        [Span("engine.step", 0, 40 * MS, {})],
        {"/device:TPU:0": [Op(0, 10 * MS, "allreduce", 0)],
         "/device:TPU:1": [Op(0, 30 * MS, "allreduce", 0)]}, (0, 40 * MS))
    assert pt_.device_ms(pt, lambda o: o.scope == "allreduce") == 20.0
    assert pt_.idle_by_span(pt) == {"engine.step": 20.0}


@pytest.fixture
def ctx(monkeypatch):
    pt = _trace()
    monkeypatch.setattr(pt_, "of_run", lambda ctx: pt)
    return {"steps": [{}, {}]}


@pytest.mark.parametrize("metric, want", [
    ("interaction_ms", 10.0), ("symcon_ms", 5.0), ("second_order_ms", 10.0),
    ("engine_idle_ms", 12.5), ("input_wait_ms", 10.0),
])
def test_readers(ctx, metric, want):
    reader = _paths.bench_run().load_module(
        _paths.BENCH / "metrics" / f"{metric}.py", f"test_metric_{metric}")
    assert reader.read(ctx) == pytest.approx(want)


def test_readers_are_silent_without_program_spans(monkeypatch):
    monkeypatch.setattr(pt_, "of_run", lambda ctx: None)
    for metric in ("interaction_ms", "symcon_ms", "second_order_ms",
                   "engine_idle_ms", "input_wait_ms"):
        reader = _paths.bench_run().load_module(
            _paths.BENCH / "metrics" / f"{metric}.py", f"test_none_{metric}")
        assert reader.read({"steps": [{}]}) is None


# ----------------------------- protobuf bytes --------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _stat_md(sid, name):
    return (5, _msg((1, sid), (2, _msg((1, sid), (2, name)))))


def _event_md(eid, name, display, *stats):
    """Event metadata ``eid`` with stats, each ((1, stat id), (n, value))."""
    return (4, _msg((1, eid), (2, _msg((1, eid), (2, name), (4, display),
                                       *[(5, _msg(*s)) for s in stats]))))


def test_op_names_from_event_metadata_and_hlo(tmp_path):
    # HloProto > module > computations > instructions (name, metadata
    # op_name, called computations): the fusion has no op_name of its own
    inst = lambda name, op, *called: (2, _msg(
        (1, name), *([(7, _msg((2, op)))] if op else []),
        *[(38, c) for c in called]))
    hlo = _msg((1, _msg(
        (3, _msg((1, "fused"), (5, 2),
                 inst("mul.1", "jit(f)/transpose(jvp(symcon))/mul"),
                 inst("add.1", "jit(f)/transpose(jvp(symcon))/add"))),
        (3, _msg((1, "main"), (5, 1), inst("fusion.3", "", 2))))))
    metadata = _msg((2, "/host:metadata"), _stat_md(1, "Hlo Proto"),
                    _event_md(77, "jit_f(77)", "", ((1, 1), (6, hlo))))
    device = _msg((2, "/device:TPU:0"), _stat_md(4, "tf_op"),
                  _stat_md(5, "program_id"),
                  _event_md(1, "%dot.1 = f32[8] dot()", "dot.1",
                            ((1, 4), (5, "jit(f)/jvp(jvp(linear))/dot")),
                            ((1, 5), (3, 77))),
                  _event_md(2, "%fusion.3 = f32[8] fusion()", "fusion.3",
                            ((1, 5), (3, 77))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, metadata), (1, device)))
    names = pt_._op_names(str(path))["/device:TPU:0"]
    assert names == {
        "%dot.1 = f32[8] dot()": "jit(f)/jvp(jvp(linear))/dot",
        "%fusion.3 = f32[8] fusion()": "jit(f)/transpose(jvp(symcon))/mul"}
    assert pt_.classify(names["%fusion.3 = f32[8] fusion()"]) == ("symcon", 1)
