"""``bench/phases.py``: the device-idle time of a request split by the
program's innermost span, on a small synthetic trace worked by hand,
and, on a real profiler trace of each kind of request on the CPU, the
shape of the program's spans that the split relies on.  Which phases a
request opens after its readback is the program's own affair and is
not pinned here."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import harness, phases, trace
from bench.trace import Interval, Trace

ROOT = Path(__file__).resolve().parents[2]
OP = "%clutch_predicate.1 = u32[8] custom-call()"


def _prog(start, end, phase):
    return Interval(start, end, f"clutch.{phase}")


def spanned() -> Trace:
    """Two requests on one chip over [0, 100), with the program's spans:

    request 0 [0, 40), a Q4: session [1, 39) > resolve [2, 6),
      dispatch [6, 9), readback [9, 33), unpack [33, 36), finish [36, 38);
      device busy [10, 32)
    request 1 [50, 100), a Q3: session [51, 99) > resolve [52, 58),
      dispatch [58, 61), readback [61, 75); device busy [60, 70) (the
      kernel starts during dispatch)
    """
    return Trace(
        device={"/device:TPU:0": [Interval(10, 30, OP), Interval(28, 32, OP),
                                  Interval(60, 70, OP)]},
        spans=[Interval(0, 40, "bench_request", (("i", "0"),)),
               Interval(50, 100, "bench_request", (("i", "1"),))])


PROGRAM = [_prog(1, 39, "session"), _prog(2, 6, "resolve"),
           _prog(6, 9, "dispatch"), _prog(9, 33, "readback"),
           _prog(33, 36, "unpack"), _prog(36, 38, "finish"),
           _prog(51, 99, "session"), _prog(52, 58, "resolve"),
           _prog(58, 61, "dispatch"), _prog(61, 75, "readback")]

#: Idle nanoseconds per request by hand: request 0 idles 18 ns (40 less
#: the busy [10, 32)), request 1 idles 40 ns (50 less [60, 70)).
BY_HAND = {"index_resolve_ms": (4 + 6) / 2, "dispatch_ms": (3 + 2) / 2,
           "readback_ms": (2 + 5) / 2, "unpack_ms": 3 / 2,
           "host_finish_ms": 2 / 2,
           # [0, 2), [38, 40); [50, 52), [75, 100)
           "host_unspanned_ms": (4 + 27) / 2}


def test_split_by_hand():
    tr = spanned()
    got = phases.split(tr.device, PROGRAM, tr.spans)
    assert got == pytest.approx({k: v * 1e-6 for k, v in BY_HAND.items()})


def test_split_sums_to_host_critical():
    """The phases of each request sum to what ``host_critical_ms``
    reads: its span less the device's busy time inside it."""
    tr = spanned()
    reqs = [harness.Request(i, None, 1e-6, None) for i in (0, 1)]
    reader = harness.load_module(
        ROOT / "bench" / "metrics" / "host_critical_ms.py")
    critical = reader.read(harness.Window(reqs, tr, 0, 100, None, "x"))
    assert critical == pytest.approx((18 + 40) / 2e6)
    assert sum(phases.split(tr.device, PROGRAM, tr.spans).values()) == \
        pytest.approx(critical)
    # per request too, averaged over two chips
    tr.device["/device:TPU:1"] = [Interval(0, 45, OP)]
    for span, busy in zip(tr.spans, ((22 + 40) / 2, (10 + 0) / 2)):
        idle = phases.idle_by_program_span(tr.device, PROGRAM, [span])
        assert sum(idle.values()) == pytest.approx(span.dur - busy)


def test_split_holds_only_the_phases_the_trace_has():
    tr = spanned()
    count_only = [e for e in PROGRAM
                  if e.name not in ("clutch.unpack", "clutch.finish")]
    got = phases.split(tr.device, count_only, tr.spans)
    assert set(got) == {"index_resolve_ms", "dispatch_ms", "readback_ms",
                        "host_unspanned_ms"}
    assert phases.split(tr.device, [], tr.spans) == {}
    assert phases.split(tr.device, PROGRAM, []) == {}


def test_without_a_device_every_instant_is_idle():
    tr = spanned()
    got = phases.split({}, PROGRAM, tr.spans)
    assert got["index_resolve_ms"] == pytest.approx((4 + 6) / 2e6)
    assert got["readback_ms"] == pytest.approx((24 + 14) / 2e6)
    assert sum(got.values()) == pytest.approx((40 + 50) / 2e6)


# ------------------------------------------------------------------ #
# The spans the program opens, on the CPU
# ------------------------------------------------------------------ #

#: The kinds of request the fixture sends, one each.
KINDS = ("q1", "q2", "q3", "q4", "q5", "compound", "compound_count",
         "predict")
#: The phases every request opens first, in this order.
FIRST = ["clutch.resolve", "clutch.dispatch", "clutch.readback"]


@pytest.fixture(scope="module")
def program_trace(tmp_path_factory):
    """One request of each kind through a fused session on a tiny table
    and forest, each in a ``bench_request`` span, under the profiler:
    (the trace file, the kinds in request order)."""
    import jax

    from repro.apps.gbdt import ObliviousForest
    from repro.apps.predicate import Table
    from repro.pud import Q1, Q2, Q3, Q4, Q5, PudSession
    from repro.pud.queries import Compound

    s = PudSession(num_devices=1, backend="fused")
    table = s.create_table(Table.generate(4096, 8, 4, seed=1), name="t",
                           num_chunks=2)
    forest = s.load_forest(ObliviousForest.random(16, 4, 4, 8, seed=2),
                           name="f", num_chunks=1)
    qa = dict(fi=0, x0=20, x1=200, fj=1, y0=40, y1=220)
    terms = (Q1(fi=2, x0=10, x1=90), Q2(**qa), Q3(**qa))
    calls = {"q1": lambda: s.query(table, Q1(fi=0, x0=31, x1=127)),
             "q2": lambda: s.query(table, Q2(**qa)),
             "q3": lambda: s.query(table, Q3(**qa)),
             "q4": lambda: s.query(table, Q4(fk=2, **qa)),
             "q5": lambda: s.query(table, Q5(fl=3, fk=2, **qa)),
             "compound": lambda: s.query(
                 table, Compound(terms=terms, ops=("and", "or"))),
             "compound_count": lambda: s.query(
                 table, Compound(terms=terms, ops=("and", "or"),
                                 count=True)),
             "predict": lambda: s.predict(
                 forest, np.random.default_rng(3).integers(
                     0, 256, (24, 4), dtype=np.int64))}
    assert tuple(calls) == KINDS
    for call in calls.values():      # compile outside the trace
        call()
    tdir = tmp_path_factory.mktemp("program_trace")
    jax.profiler.start_trace(str(tdir))
    try:
        for i, call in enumerate(calls.values()):
            with jax.profiler.TraceAnnotation("bench_request", i=i):
                call()
    finally:
        jax.profiler.stop_trace()
    return str(next(tdir.rglob("*.xplane.pb"))), list(calls)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_program_emits_its_spans(program_trace, kind):
    """What ``bench/phases.py`` relies on: the program opens only its
    session and phase spans, one session per request holding the rest,
    phases that never overlap, and resolve, dispatch and readback
    first."""
    path, kinds = program_trace
    tr, program = trace.load(path), phases.load_program(path)
    assert len(tr.spans) == len(kinds)
    req = next(s for s in tr.spans if kinds[tr.span_index(s)] == kind)
    inside = [e for e in program if req.start <= e.start and e.end <= req.end]
    # no program span reaches out of the request's span
    assert inside == [e for e in program
                      if e.start < req.end and req.start < e.end]
    assert {e.name for e in inside} <= {phases.SESSION,
                                        *phases.PHASES.values()}
    sessions = [e for e in inside if e.name == phases.SESSION]
    assert len(sessions) == 1
    sess = sessions[0]
    steps = [e for e in inside if e is not sess]
    assert all(sess.start <= e.start and e.end <= sess.end for e in steps)
    assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
    assert [e.name for e in steps[:len(FIRST)]] == FIRST


def test_cli_prints_the_split(program_trace, capsys, tmp_path):
    path, kinds = program_trace
    assert phases.main([path]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["requests"] == len(kinds)
    assert set(out) == {"requests", "device_planes", "host_unspanned_ms",
                        *phases.PHASES}
    assert all(v >= 0 for v in out.values())
    # a trace the program opened no span in
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench_request", i=0):
            np.asarray(jax.numpy.ones(4) + 1)
    finally:
        jax.profiler.stop_trace()
    assert phases.main([str(next(tmp_path.rglob("*.xplane.pb")))]) == 1
