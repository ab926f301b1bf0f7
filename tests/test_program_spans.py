"""The program's own spans, per kind of request: which host phases each
kind opens, in order, on a fused session on the CPU.

``bench/phases.py`` splits a request's device-idle time by the
innermost ``clutch.*`` span open (``repro.kernels.fused_session``
``SPAN_*``); this pins what each kind opens, so that a change that moves
a phase says so here.  Every request opens resolve, dispatch and
readback first; a bitmap then comes back and is unpacked (Q1, Q2, a
compound's rows), Q4 and Q5 finish their average on the host, and Q5
launches again for its second phase.  A forest batch is scored on the
device and reads back its predictions alone.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

# the benchmark's package (``bench``) lives at the repository's root
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import phases, trace  # noqa: E402

FIRST = ["clutch.resolve", "clutch.dispatch", "clutch.readback"]
UNPACK, FINISH = "clutch.unpack", "clutch.finish"
#: The phases each kind of request opens inside its session, in order.
EXPECTED = {"q1": FIRST + [UNPACK],
            "q2": FIRST + [UNPACK],
            "q3": FIRST,
            "q4": FIRST + [UNPACK, FINISH],
            "q5": FIRST + [UNPACK, FINISH] + FIRST,
            "compound": FIRST + [UNPACK],
            "compound_count": FIRST,
            "predict": FIRST}


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
    assert tuple(calls) == tuple(EXPECTED)
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


@pytest.mark.parametrize("kind", sorted(EXPECTED))
def test_program_emits_its_spans(program_trace, kind):
    """One session span per request holding every phase span, the
    phases in :data:`EXPECTED`'s order, none overlapping another."""
    path, kinds = program_trace
    tr, program = trace.load(path), phases.load_program(path)
    assert len(tr.spans) == len(kinds)
    req = next(s for s in tr.spans if kinds[tr.span_index(s)] == kind)
    inside = [e for e in program if req.start <= e.start and e.end <= req.end]
    assert inside == [e for e in program
                      if e.start < req.end and req.start < e.end]
    sessions = [e for e in inside if e.name == phases.SESSION]
    assert len(sessions) == 1
    sess = sessions[0]
    steps = [e for e in inside if e is not sess]
    assert all(sess.start <= e.start and e.end <= sess.end for e in steps)
    assert all(a.end <= b.start for a, b in zip(steps, steps[1:]))
    assert [e.name for e in steps] == EXPECTED[kind]
