"""The reduction from a profiler capture to numbers: on hand-made events
whose answer can be worked out on paper, and on a small capture recorded on
the chip (``benchmark/testdata/``), whose numbers are pinned."""

import json
import os

import pytest

from benchmark import manifest, xplane

TESTDATA = os.path.join(manifest.ROOT, "benchmark", "testdata")


def test_union_and_bare():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert xplane.covered([(0, 10), (2, 3), (20, 25)]) == 15
    # of [0, 10] and [20, 30], [5, 22] leaves [0, 5] and [22, 30] bare
    assert xplane.bare([(0, 10), (20, 30)], [(5, 22)]) == 13
    assert xplane.bare([(0, 10)], []) == 10


def test_op_name_keeps_the_instructions_name_and_what_it_produces():
    text = ("%fusion.46 = f32[1,1024,30522]{1,2,0:T(8,128)} fusion(f32[32,128,30522]{1,2,0} "
            "%get-tuple-element.1652), kind=kOutput, calls=%fused_computation.52")
    assert xplane.op_name(text) == "fusion.46 f32[1,1024,30522] fusion"
    assert xplane.op_name("%all-reduce-start.3 = (f32[8]{0}, f32[8]{0}) all-reduce-start(%x)") == (
        "all-reduce-start.3 f32[8] all-reduce-start")
    # JAX names an all-reduce after its psum: the opcode tells
    named = xplane.op_name("%psum.2379 = f32[1024,30522]{1,0:T(8,128)} all-reduce(%x), to_apply=%add")
    assert named == "psum.2379 f32[1024,30522] all-reduce" and xplane.COLLECTIVE.search(named)
    assert not xplane.COLLECTIVE.search(xplane.op_name(text))
    # what trim_xplane.py writes reads back to itself; a module's name has no opcode
    assert xplane.op_name("%psum.2379 = f32[1024,30522] all-reduce()") == named
    assert xplane.op_name("jit_local_step(123)") == "jit_local_step(123)"


def test_reduce_on_events_worked_out_on_paper():
    """Two steps of 100 ns.  In each: compute [0, 60], an asynchronous
    all-reduce in flight [40, 90] of which [40, 60] lies under compute, its
    ``done`` on the operations' line [60, 90], an update [90, 95], idle to
    100.  The host drew a batch during the first step's idle tail."""
    ops, asyncs, modules = [], [], []
    for base in (1000, 1100):
        ops += [("fusion.1 f32[4] fusion", base, base + 60),
                ("psum.1 f32[4] all-reduce-done", base + 60, base + 90),
                ("fusion.2 f32[4] fusion", base + 90, base + 95)]
        asyncs.append(("all-reduce-start.1 f32[4] all-reduce-start", base + 40, base + 90))
        modules.append(("jit_local_step(1)", base, base + 95))
    modules.append(("jit__lambda(2)", 1096, 1098))
    loaded = {"devices": {0: {xplane.OPS: ops, xplane.ASYNC_OPS: asyncs,
                              xplane.MODULES: modules}},
              "host": [(1094, 1099)]}
    got = xplane.reduce(loaded)
    assert got["devices"] == 1 and got["steps"] == 2
    assert got["window_s"] == pytest.approx(195e-9)
    assert got["busy_s"] == got["device0_busy_s"] == pytest.approx(190e-9)
    assert got["collective_s"] == pytest.approx(100e-9)         # [40, 90] twice
    assert got["exposed_collective_s"] == pytest.approx(60e-9)  # [60, 90] twice
    assert got["device_ops"][0] == ["fusion.1 f32[4] fusion", pytest.approx(120e-9)]
    assert got["idle_gaps"] == [["data", pytest.approx(5e-9)]]
    assert xplane.reduce({"devices": {}, "host": []}) is None


def test_busy_is_averaged_over_the_devices():
    def lines(busy):
        return {xplane.OPS: [("fusion.1 f32[4] fusion", 0, busy),
                             ("fusion.2 f32[4] fusion", 90, 100)]}

    got = xplane.reduce({"devices": {0: lines(50), 1: lines(70)}, "host": []})
    assert got["devices"] == 2
    assert got["busy_s"] == pytest.approx(70e-9) and got["device0_busy_s"] == pytest.approx(60e-9)
    assert got["idle_gaps"] == [["fit-loop", pytest.approx(40e-9)]]


RECORDED = sorted(f for f in os.listdir(TESTDATA) if f.endswith(".xplane.pb")) if os.path.isdir(
    TESTDATA) else []


@pytest.mark.parametrize("name", RECORDED)
def test_reduction_of_a_capture_recorded_on_the_chip(name):
    """The numbers were read once from this file by this code (PR 25) and are
    pinned: a change to the reduction that moves them changes every later
    reading too, and has to say so."""
    with open(os.path.join(TESTDATA, name.replace(".xplane.pb", ".expected.json"))) as f:
        expected = json.load(f)
    got = xplane.reduce(xplane.load(os.path.join(TESTDATA, name)))
    assert os.path.getsize(os.path.join(TESTDATA, name)) < 1_000_000
    for key, want in expected.items():
        if isinstance(want, list):
            assert [n for n, _ in got[key]] == [n for n, _ in want]
            assert [t for _, t in got[key]] == pytest.approx([t for _, t in want], rel=1e-9)
        else:
            assert got[key] == pytest.approx(want, rel=1e-9), key


def test_a_recorded_capture_is_there():
    assert RECORDED, "benchmark/testdata holds no recorded capture"
