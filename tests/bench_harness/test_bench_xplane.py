"""The trace reduction on a hand-built trace: busy union, idle share,
operation sums by name, and idle time by where it falls among the step
programs."""

import pytest

from benchmark import xplane
from benchmark.xplane import Event

MS = 1e6  # ns


@pytest.fixture
def trace():
    # two calls of two steps each; a step program lasts 20 ms
    modules = [Event("jit_train_step", t * MS, 20 * MS)
               for t in (10, 35, 70, 92)]
    ops = [
        Event("fusion.1", 10 * MS, 8 * MS),
        Event("convolution.2", 16 * MS, 10 * MS),   # overlaps: 10..26
        Event("flash_fwd", 27 * MS, 3 * MS),        # 27..30, 1 ms hole before
        Event("fusion.1", 35 * MS, 20 * MS),
        Event("fusion.1", 70 * MS, 20 * MS),
        Event("fusion.1", 92 * MS, 18 * MS),        # 2 ms hole at its end
    ]
    return ops, modules


def test_busy_union_and_idle_share(trace):
    ops, _modules = trace
    assert xplane.busy_union(ops) == [
        (10 * MS, 26 * MS), (27 * MS, 30 * MS), (35 * MS, 55 * MS),
        (70 * MS, 90 * MS), (92 * MS, 110 * MS)]
    assert xplane.busy_seconds(ops) == pytest.approx(0.077)
    # the window's length is the host's: 120 ms here
    assert 1.0 - xplane.busy_seconds(ops) / 0.120 == pytest.approx(0.358333)


def test_operation_seconds_by_name(trace):
    ops, _modules = trace
    by_name = xplane.op_seconds(ops)
    assert by_name == pytest.approx({"fusion.1": 0.066,
                                     "convolution.2": 0.010,
                                     "flash_fwd": 0.003})
    assert xplane.top(by_name, 2) == [["fusion.1", pytest.approx(0.066)],
                                      ["convolution.2",
                                       pytest.approx(0.010)]]


def test_idle_time_is_named_by_where_it_falls(trace):
    ops, modules = trace
    by_place = xplane.idle_by_place(ops, modules, steps_per_call=2,
                                    window_s=0.120)
    assert by_place == pytest.approx({
        xplane.INSIDE: 0.003,           # 26..27 and 110..112
        xplane.BETWEEN_STEPS: 0.007,    # 30..35 and 90..92
        xplane.BETWEEN_CALLS: 0.015,    # 55..70
        xplane.EDGES: 0.018})           # 120 ms less the 102 ms spanned
    assert sum(by_place.values()) == pytest.approx(
        0.120 - xplane.busy_seconds(ops))
    assert xplane.idle_by_place(ops, [], 2, 0.120) == {xplane.EDGES: 0.120}


def test_names_are_the_instructions(trace):
    assert xplane.short_name(
        "%multiply_reduce_fusion.2 = (bf16[256]{0:T(256)}, bf16[2]) "
        "fusion(bf16[256,56,56,256]{3,0,2,1} %x), kind=kOutput") \
        == "multiply_reduce_fusion.2"
    assert xplane.short_name("jit_train_step") == "jit_train_step"
