"""``benchmark/trace_names.py``: the host plane's program spans and the
device ops' scopes, on the hand-made trace beside ``trace_sample/`` and
on a raw ``.xplane.pb`` made from it."""

import json
import os

import pytest

from benchmark import harness, scope_shares, trace_names, trace_reduce

SAMPLE = os.path.join(harness.HERE, "trace_sample", "names_sample.json")
DEVICE, HOST = "/device:TPU:0", trace_names.HOST_PLANE


def sample():
    with open(SAMPLE) as f:
        raw = json.load(f)
    dev = raw[DEVICE]
    return {DEVICE: {"modules": [tuple(e) for e in dev["modules"]],
                     "ops": [tuple(e) for e in dev["ops"]],
                     "op_names": dev["op_names"]},
            HOST: {"spans": [tuple(e) for e in raw[HOST]["spans"]]}}


def rounded(x):
    """``x`` with every float to 9 places: the protobuf holds times in
    whole picoseconds."""
    if isinstance(x, float):
        return round(x, 9)
    if isinstance(x, dict):
        return {k: rounded(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [rounded(v) for v in x]
    return x


def ms(d):
    return {k: pytest.approx(1e3 * v, abs=1e-9) for k, v in d.items()}


def test_idle_splits_by_the_innermost_span_of_the_dispatching_thread():
    got = trace_names.reduce_names(sample())
    # four gaps of 10, 9, 10 and 9 ms; ``submit`` (another thread, open
    # over all of them) takes nothing; ``epoch`` only where none of its
    # children is open
    assert {k: 1e3 * v for k, v in got["idle_by_span"].items()} == {
        "deviceWait": pytest.approx(10.0), "totalsRead": pytest.approx(14.0),
        "recordWrite": pytest.approx(2.0), "epochEnd": pytest.approx(1.0),
        "none": pytest.approx(1.0), "dispatch": pytest.approx(1.5),
        "epoch": pytest.approx(8.5)}
    first, second = got["epoch_gaps"]
    assert first["between"] == second["between"] == ["convert_element_type"]
    assert first["idle_s"] == pytest.approx(0.019)
    assert ms(first["by_span"]) == {
        "deviceWait": 9.5, "totalsRead": 6.0, "recordWrite": 1.0,
        "epochEnd": 0.5, "none": 1.0, "dispatch": 1.0}
    assert 1e3 * first["under"]["epochEnd"] == pytest.approx(7.5)
    assert 1e3 * second["under"]["epochEnd"] == pytest.approx(9.5)
    assert "submit" not in first["under"]


def test_a_stretch_the_capture_cuts_is_left_out():
    # the first epoch's spans opened before the capture: its gap after
    # the first program is read from the second epoch's start on
    planes = sample()
    planes[HOST] = {"spans": [s for s in planes[HOST]["spans"]
                              if s[1] >= 1.0]}
    got = trace_names.reduce_names(planes)
    assert 1e3 * sum(got["idle_by_span"].values()) == pytest.approx(28.0)
    (gap,) = got["epoch_gaps"]
    assert 1e3 * gap["under"]["epochEnd"] == pytest.approx(9.5)


def test_what_launched_each_program_and_the_clock_check():
    got = trace_names.reduce_names(sample())
    assert got["program_spans"] == {
        "epoch_fn": {"dispatch": 1, "deviceWait": 1, "epoch": 1},
        "convert_element_type": {"dispatch": 1, "epochEnd": 1}}
    assert got["wait_lag_ms"] == [pytest.approx(0.5), pytest.approx(0.5)]


def test_idle_under_epoch_end_is_the_lower_median_of_the_gaps():
    names = trace_names.reduce_names(sample())
    assert trace_names.idle_under_ms(names, "epochEnd") == \
        pytest.approx(7.5)
    assert trace_names.idle_under_ms(names, "checkpointCommit") == 0.0
    assert trace_names.idle_under_ms({}, "epochEnd") is None


def test_no_program_span_is_no_reduction():
    planes = sample()
    assert trace_names.reduce_names({DEVICE: planes[DEVICE]}) == {}
    jax_only = {HOST: {"spans": [("epoch", 0.0, 1.0, 1)]}}
    assert trace_names.reduce_names(dict(planes, **jax_only)) == {}


def test_scopes_sum_over_whole_epoch_executions_a_step():
    # only the execution at 1.020 s is whole; four steps each
    got = trace_names.per_step_ms(sample(), 4, ["moe/", "head_loss"],
                                  ["moe_gmm_", "moe_rows_", "flash_"])
    assert got["executions"] == 1
    assert got["kernels"] == {"moe_gmm_fwd": pytest.approx(10.0),
                              "moe_rows_sum": pytest.approx(2.5),
                              "flash_fwd": pytest.approx(12.5)}
    assert got["scopes"] == {"moe/": pytest.approx(5.0),
                             "head_loss": pytest.approx(8.0)}
    assert got["rest"] == {"fusion": pytest.approx(4.0),
                           "copy": pytest.approx(1.0)}
    assert got["total"] == pytest.approx(43.0)


@pytest.mark.parametrize("name,want", [
    ("moe_layer_ms", (40 + 20 + 10) / 4), ("head_loss_ms", 32 / 4),
    ("ssm_conv_ms", 16 / 4)])
def test_each_reading_gives_the_hand_computed_value(name, want):
    assert trace_names.reading_ms(sample(), 4, name) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(trace_names.READINGS))
def test_nothing_to_read_is_none(name):
    planes = sample()
    unnamed = dict(planes[DEVICE], op_names=[""] * len(planes[DEVICE]["ops"]))
    assert trace_names.reading_ms({DEVICE: unnamed}, 4, name) is None
    one = dict(planes[DEVICE], modules=planes[DEVICE]["modules"][:2])
    assert trace_names.reading_ms({DEVICE: one}, 4, name) is None
    dense = dict(planes[DEVICE], op_names=["jit(f)/mlp/dot"]
                 * len(planes[DEVICE]["ops"]),
                 ops=[("%fusion.1 fusion", s, d)
                      for _, s, d in planes[DEVICE]["ops"]])
    assert trace_names.reading_ms({DEVICE: dense}, 4, name) is None


def xspace_of(planes):
    """The raw protobuf a profiler would write for ``planes``: an op's
    event named by an HLO line, its scope in its metadata's ``tf_op``;
    each program span marked with its id, beside one of jax's own."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    dev = space.planes.add(name=DEVICE)
    dev.stat_metadata[1].name = "tf_op"
    ops = dev.lines.add(name=trace_reduce.OPS_LINE, timestamp_ns=0)
    mods = dev.lines.add(name=trace_reduce.MODULES_LINE, timestamp_ns=0)
    for i, ((name, start, dur), op_name) in enumerate(
            zip(planes[DEVICE]["ops"], planes[DEVICE]["op_names"]), 1):
        lhs, _, opcode = name.partition(" ")
        meta = dev.event_metadata[i]
        meta.name = f"{lhs} = f32[8]{{0}} {opcode}(%p)"
        meta.stats.add(metadata_id=1, str_value=op_name)
        ops.events.add(metadata_id=i, offset_ps=round(start * 1e12),
                       duration_ps=round(dur * 1e12))
    for i, (name, start, dur) in enumerate(planes[DEVICE]["modules"], 100):
        dev.event_metadata[i].name = name
        mods.events.add(metadata_id=i, offset_ps=round(start * 1e12),
                        duration_ps=round(dur * 1e12))
    host = space.planes.add(name=HOST)
    host.stat_metadata[1].name = trace_names.SPAN_ARG
    host.stat_metadata[2].name = "epoch"
    lines = {}
    for i, (name, start, dur, thread) in enumerate(
            planes[HOST]["spans"], 1):
        line = lines.get(thread) or host.lines.add(id=thread, timestamp_ns=0)
        lines[thread] = line
        host.event_metadata[i].name = name
        event = line.events.add(metadata_id=i, offset_ps=round(start * 1e12),
                                duration_ps=round(dur * 1e12))
        event.stats.add(metadata_id=1, int64_value=i)
        event.stats.add(metadata_id=2, int64_value=0)
    host.event_metadata[999].name = "PjitFunction(epoch_fn)"
    lines[1].events.add(metadata_id=999, offset_ps=0, duration_ps=10**9)
    return space


def test_the_raw_reader_gives_the_planes_and_what_trace_reduce_reads(
        tmp_path):
    planes = sample()
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace_of(planes).SerializeToString())
    got = trace_names.read_space(str(path))
    assert got[DEVICE]["op_names"] == planes[DEVICE]["op_names"]
    for key in ("ops", "modules"):
        assert rounded(got[DEVICE][key]) == rounded(planes[DEVICE][key])
    # jax's own event carries no span id: left out
    assert [s[0] for s in got[HOST]["spans"]] == \
        [s[0] for s in planes[HOST]["spans"]]
    # trace_reduce takes these planes as they are, and reads them as it
    # reads its own reader's
    assert rounded(trace_reduce.reduce_events(got)) == rounded(
        trace_reduce.reduce_events(trace_reduce.read_planes(str(path))))
    assert trace_names.read_ops(str(path)) == [
        (name.partition(" ")[0], name.partition(" ")[2], op_name,
         pytest.approx(dur))
        for (name, _, dur), op_name in zip(planes[DEVICE]["ops"],
                                           planes[DEVICE]["op_names"])
        if not name.endswith(" while")]
    # the rows ``scope_shares`` reads, so that it can take them from here
    assert trace_names.read_ops(str(path)) == \
        scope_shares.read_ops(str(path))
    assert trace_names.main([str(tmp_path), "--steps", "4"]) == 0
    assert trace_names.main([str(tmp_path / "none"), "--steps", "4"]) == 1
