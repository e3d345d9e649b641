"""The trace reduction, checked on a trace recorded on a TPU v5e: two
2^16-element kv sorts through ``repro.sort``, each call and host copy in a
``bench.*`` host span."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr

TRACE = Path(__file__).parent / "data" / "kv_sort_2p16.xplane.pb"

CUSTOM = ('%bitonic_sort_rows_kv.1 = (s32[8,8192,1024]{2,1,0:T(8,128)}, '
          's32[8,8192,1024]{2,1,0:T(8,128)}) custom-call(s32[8,8192,1024]{2,1,0:T(8,128)} '
          '%copy_bitcast_fusion.1, s32[8,8192,1024]{2,1,0:T(8,128)} %copy_bitcast_fusion), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{s32[8,8192,1024]{2,1,0}, s32[8,8192,1024]{2,1,0}}')
SORT = ('%sort.15 = (s32[8,8388608]{1,0:T(8,128)}, s32[8,8388608]{1,0:T(8,128)}) '
        'sort(s32[8,8388608]{1,0:T(8,128)} %bitcast.95, s32[8,8388608]{1,0:T(8,128)} '
        '%bitcast.97), dimensions={1}, is_stable=true, to_apply=%region_0.13.clone')


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(str(TRACE), [0])


def test_classes_of_ops():
    assert tr.op_class(CUSTOM) == "pallas"
    assert tr.op_class(SORT) == "sort"
    assert tr.op_class("%all-to-all.3 = s32[4,128]{1,0} all-to-all(s32[4,128]{1,0} %p), "
                       "dimensions={0}") == "collective"
    assert tr.op_class("%all-gather-start.1 = (s32[8]{0}, s32[32]{0}) "
                       "all-gather-start(s32[8]{0} %x), dimensions={0}") == "collective"
    assert tr.op_class("%fusion.95 = s32[64]{0} fusion(s32[64]{0} %p), kind=kLoop") == "other"
    assert tr.op_class("%custom-call.2 = s32[8]{0} custom-call(s32[8]{0} %p), "
                       'custom_call_target="Sharding"') == "other"


def test_bytes_come_from_result_and_operand_shapes_only():
    # two 8*8192*1024 int32 results and two operands of the same shape; the
    # layout constraints after the operands are not counted
    assert tr.op_bytes(CUSTOM) == 4 * 8 * 8192 * 1024 * 4
    assert tr.op_bytes("%p = pred[64]{0} compare(s32[64]{0} %a, s32[64]{0} %b)") == 64 + 2 * 256


@pytest.mark.parametrize("ops,want", [
    # a while holding two body ops: the while keeps only its own time
    ([(0, 10, "other"), (2, 4, "sort"), (5, 9, "pallas")],
     {"other": 4, "sort": 2, "pallas": 4}),
    # back to back, with an idle gap between
    ([(0, 3, "sort"), (5, 6, "pallas")], {"sort": 3, "pallas": 1}),
    # a collective inside a loop body is collective time, not compute
    ([(0, 10, "other"), (3, 7, "collective")], {"other": 6, "collective": 4}),
])
def test_innermost_op_takes_each_instant(ops, want):
    objs = [tr.Op(s, e, "", c) for s, e, c in ops]
    compute = tr.attribute(objs)
    got = {}
    for o in objs:
        got[o.cls] = got.get(o.cls, 0) + o.self_ns
    assert got == want
    assert tr.length(compute) == sum(v for k, v in want.items() if k != "collective")


def test_interval_arithmetic():
    assert tr.union([(5, 6), (0, 3), (2, 4)]) == [[0, 4], [5, 6]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert tr.subtract([[0, 4], [6, 8]], []) == [[0, 4], [6, 8]]


def test_recorded_trace_reduces_to_its_numbers(reduced):
    assert reduced.n_sorts == 2
    assert reduced.window_ns == 14221941.0
    (dev,) = reduced.devices
    assert dev.busy_ns == 1231697.0
    assert dev.class_ns == {"pallas": 590389.0, "sort": 185471.0,
                            "collective": 0.0, "other": 455837.0}
    assert sum(dev.class_ns.values()) == dev.busy_ns
    assert dev.pallas_bytes == 16777216
    assert dev.collective_ns == 0 and dev.exposed_ns == 0
    assert reduced.busy_s == pytest.approx(0.001231697)
    assert reduced.idle_frac == pytest.approx(1 - 1231697 / 14221941)
    assert reduced.per_sort_ms(reduced.class_s("pallas")) == pytest.approx(0.2951945)


def test_breakdown_names_ops_and_gaps(reduced):
    b = reduced.breakdown()
    assert b["device_ops"][0] == ["sort:sort.15", 0.000168232]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert b["idle_gaps"][0] == ["call/np.asarray(jax.Array)", 0.002924887]
    assert all(n.split("/")[0] in ("call", "materialize", "no harness span")
               for n, _ in b["idle_gaps"])
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)


def test_missing_device_plane_is_an_error():
    with pytest.raises(ValueError, match="device planes"):
        tr.reduce_file(str(TRACE), [0, 1])
