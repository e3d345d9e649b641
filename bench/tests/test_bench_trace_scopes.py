"""Device time per library phase and the library's host spans, read from
the profiler's protobuf by ``bench/trace_scopes.py``, checked on traces
recorded on a TPU v5e through the harness (two 2^16-element kv sorts,
each call and host copy in a ``bench.*`` span): one of the program
before it had phase scopes, one of the scoped program with the library's
``repro.*`` spans."""
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench import trace_scopes as ts

DATA = Path(__file__).parent / "data"
UNSCOPED_TRACE = DATA / "kv_sort_2p16.xplane.pb"
SCOPED_TRACE = DATA / "kv_sort_2p16_scoped.xplane.pb"


@pytest.fixture(scope="module")
def unscoped():
    return ts.reduce_file(str(UNSCOPED_TRACE), [0])


@pytest.fixture(scope="module")
def scoped():
    return ts.reduce_file(str(SCOPED_TRACE), [0])


def test_reads_the_same_events_as_the_profile_reader(unscoped):
    # the window, the sorts and the busy time come out to the nanosecond
    # as bench/trace_reduce.py reads them through jax.profiler.ProfileData
    old = tr.reduce_file(str(UNSCOPED_TRACE), [0])
    assert unscoped.window_ns == old.window_ns == 14221941.0
    assert unscoped.n_sorts == old.n_sorts == 2
    assert unscoped.busy_ns == [d.busy_ns for d in old.devices] == [1231697.0]
    assert [s for s, _ in unscoped.gaps] == [s for s, _ in old.gaps]


def test_a_program_without_scopes_is_all_unscoped(unscoped):
    (phases,) = unscoped.phase_ns
    assert phases["unscoped"] == unscoped.busy_ns[0]
    assert all(phases[p] == 0 for p in ts.PHASES)
    assert unscoped.host_ns == {}
    assert {n for _, n in unscoped.gaps} <= {"call/no library span",
                                             "materialize/no library span"}


def test_every_phase_of_the_scoped_program_has_device_time(scoped):
    (phases,) = scoped.phase_ns
    assert scoped.n_sorts == 2
    assert all(phases[p] > 0 for p in ts.PHASES), phases
    assert sum(phases.values()) == scoped.busy_ns[0]
    # what stays unscoped is the front end's own small programs
    assert phases["unscoped"] < 0.05 * scoped.busy_ns[0]
    assert scoped.phase_ms("merge") == phases["merge"] / 2 / 1e6


def test_the_library_host_spans_are_found(scoped):
    spans = {"plan", "encode", "stage", "sort", "dispatch", "overflow_check",
             "decode", "d2h"}
    assert set(scoped.host_ns) == {"repro." + n for n in spans}
    assert all(v > 0 for v in scoped.host_ns.values())
    # the sort span holds the dispatch and the wait on the overflow flag
    assert scoped.host_ns["repro.sort"] >= (scoped.host_ns["repro.dispatch"]
                                            + scoped.host_ns["repro.overflow_check"])
    assert scoped.host_ms("plan", "encode") == pytest.approx(
        (scoped.host_ns["repro.plan"] + scoped.host_ns["repro.encode"]) / 2 / 1e6)


def test_idle_gaps_are_named_by_library_spans(scoped):
    names = [n for _, n in scoped.gaps]
    assert all(n.split("/")[0] in ("call", "materialize") for n in names)
    assert {n.split("/")[1] for n in names[:5]} <= {"repro." + n for n in (
        "encode", "stage", "dispatch", "overflow_check", "decode", "d2h")}


def test_the_scoped_trace_reads_the_same_in_both_reducers(scoped):
    old = tr.reduce_file(str(SCOPED_TRACE), [0])
    assert (scoped.window_ns, scoped.n_sorts) == (old.window_ns, old.n_sorts)
    assert scoped.busy_ns == [d.busy_ns for d in old.devices]


@pytest.mark.parametrize("ops,runs,want", [
    # XLA's own ops inherit from the scoped op enclosing them, else from
    # the last scoped op of the same program run
    ([(0, 10, "unscoped"), (1, 2, "exchange"), (3, 4, "unscoped"),
      (11, 12, "unscoped")], [(0, 20)],
     ["unscoped", "exchange", "exchange", "exchange"]),
    ([(0, 10, "splitter"), (1, 2, "exchange"), (3, 4, "unscoped")], [(0, 20)],
     ["splitter", "exchange", "splitter"]),
    ([(0, 1, "merge"), (2, 3, "unscoped")], [(0, 1.5), (1.5, 4)],
     ["merge", "unscoped"]),
    ([(0, 1, "unscoped"), (2, 3, "decode")], [(0, 4)], ["unscoped", "decode"]),
])
def test_unscoped_ops_inherit_a_phase(ops, runs, want):
    objs = [tr.Op(s, e, "", c) for s, e, c in ops]
    modules = [ts.Event(s, e, "", "") for s, e in runs]
    ts._inherit_phases(objs, modules)
    assert [o.cls for o in sorted(objs, key=lambda o: o.start)] == want


def test_phases_match_the_library():
    from repro.obs import tracing

    assert ts.PHASES == tracing.PHASES


@pytest.mark.parametrize("tf_op,phase", [
    ("jit(sample_sort_sim_kv)/vmap(local_sort)/jit(tile_sort_kv)/pallas_call:",
     "local_sort"),
    ("jit(sample_sort_sim_kv)/splitter/jit(sort)/sort:", "splitter"),
    ("jit(wrapped)/shard_map/exchange/all_to_all:", "exchange"),
    ("jit(sample_sort_sim_kv)/vmap(merge)/sort:", "merge"),
    ("jit(decode_grid)/decode/dynamic_update_slice:", "decode"),
    ("jit(sample_sort_sim_kv)/vmap(jit(tile_sort_kv))/gather:", "unscoped"),
    ("", "unscoped"),
])
def test_phase_of_a_tf_op(tf_op, phase):
    assert ts.phase_of(tf_op) == phase


def test_varints_and_fields():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed64 7
    msg = bytes([0x08, 0xAC, 0x02, 0x12, 0x02, 0x61, 0x62, 0x19]) + (7).to_bytes(8, "little")
    got = [(f, bytes(v) if isinstance(v, memoryview) else v) for f, v in ts._fields(msg)]
    assert got == [(1, 300), (2, b"ab"), (3, 7)]
    assert ts._varint(bytes([0xFF] * 9 + [0x01]), 0) == (-1, 10)
