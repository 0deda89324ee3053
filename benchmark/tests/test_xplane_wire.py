"""The wire-format reader against ``jax.profiler.ProfileData`` on the small
trace recorded on a TPU v5e by PR 24 (``data/tiny.xplane.pb``): the same
planes, lines and events, and besides what ``ProfileData`` does not show, the
stats of an event's metadata."""

import os

import pytest

from benchmark.harness import xplane_wire

TRACE = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return xplane_wire.read(TRACE)


def test_agrees_with_profile_data_on_every_event(planes):
    from jax.profiler import ProfileData
    theirs = list(ProfileData.from_file(TRACE).planes)
    assert [p.name for p in theirs] == [p["name"] for p in planes]
    n = 0
    for plane, mine in zip(theirs, planes):
        lines = list(plane.lines)
        assert [ln.name for ln in lines] == [ln["name"]
                                             for ln in mine["lines"]]
        for line, my_line in zip(lines, mine["lines"]):
            events = list(line.events)
            assert len(events) == len(my_line["events"])
            for ev, my in zip(events, my_line["events"]):
                assert (ev.name, ev.start_ns, ev.duration_ns) == (
                    my["name"], my["start_ns"], my["duration_ns"])
                for key, value in dict(ev.stats).items():
                    assert str(my["stats"][key]) == str(value)
                n += 1
    assert n == 234


def test_metadata_stats_carry_the_op_name_path(planes):
    device = next(p for p in planes if p["name"] == "/device:TPU:0")
    ops = next(ln for ln in device["lines"] if ln["name"] == "XLA Ops")
    by_tf_op = {}
    for ev in ops["events"]:
        by_tf_op.setdefault(ev["meta_stats"].get("tf_op"), []).append(ev)
    # "<op_name path>:<op type>", the type left empty by JAX
    assert "jit(<lambda>)/jit(sort)/sort:" in by_tf_op
    assert "jit(<lambda>)/dot_general:" in by_tf_op
    sort = by_tf_op["jit(<lambda>)/jit(sort)/sort:"][0]
    # a reference into the stat table is resolved to its text
    assert isinstance(sort["meta_stats"]["hlo_category"], str)
    assert sort["meta_stats"]["bytes_accessed"] > 0
    # compiler-inserted copies carry no op_name at all
    assert None in by_tf_op
    assert {ev["meta_stats"]["hlo_category"] for ev in by_tf_op[None]} \
        >= {"copy-start", "copy-done"}


def test_filters_leave_out_before_decoding():
    planes = xplane_wire.read(
        TRACE, want_plane=lambda name: name.startswith("/device:TPU:"),
        want_line=lambda plane, line: line == "XLA Ops",
        want_event=lambda plane, name: "sort" in name)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert [ln["name"] for ln in planes[0]["lines"]] == ["XLA Ops"]
    assert planes[0]["lines"][0]["events"]
    assert all("sort" in ev["name"]
               for ev in planes[0]["lines"][0]["events"])


def test_not_an_xspace_is_an_error(tmp_path):
    bad = tmp_path / "bad.pb"
    bad.write_bytes(b"\x0b\x00\x00")        # wire type 3: a group
    with pytest.raises(ValueError):
        xplane_wire.read(str(bad))


def test_without_planes_drops_only_the_named_planes(tmp_path, planes):
    with open(TRACE, "rb") as f:
        raw = f.read()
    assert xplane_wire.without_planes(raw, set()) == raw
    small = tmp_path / "small.xplane.pb"
    small.write_bytes(xplane_wire.without_planes(raw, {"/host:CPU"}))
    kept = xplane_wire.read(str(small))
    assert [p["name"] for p in kept] == [p["name"] for p in planes
                                         if p["name"] != "/host:CPU"]
    assert kept == [p for p in planes if p["name"] != "/host:CPU"]
