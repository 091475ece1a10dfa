"""The span recorder: nesting, query ids, threads, errors, the ring's
bound, the spans of a served query and their attributes in a profiler
trace, and the measured stage seconds that ``explain`` prints."""
import collections
import glob
import os
import threading

import jax
import pytest

from repro.core.storage_service import ObjectStore
from repro.core import spans
from repro.engine import datagen, explain, queries
from repro.serve.query_server import QueryRequest, QueryServer


@pytest.fixture
def ring(monkeypatch):
    ring = spans.SpanRing(capacity=64)
    monkeypatch.setattr(spans, "RING", ring)
    return ring


def test_nesting_parent_ids_and_query_id_inheritance(ring):
    with spans.span("engine.serve", requests=1) as root:
        with spans.span("engine.plan") as plan:
            with spans.span("engine.store.get") as early:
                pass
            plan.qid = "q-1"
            with spans.span("engine.store.get") as late:
                pass
        with spans.span("engine.fragment", qid="q-2", fragment=3) as frag:
            with spans.span("engine.ops") as ops:
                pass
    records, dropped = ring.snapshot()
    assert dropped == 0
    assert [r.name for r in records] == [
        "engine.store.get", "engine.store.get", "engine.plan", "engine.ops",
        "engine.fragment", "engine.serve"]
    assert root.parent is None and root.qid is None
    assert root.attrs == {"requests": 1}
    assert plan.parent == root.id and frag.parent == root.id
    assert early.parent == late.parent == plan.id and ops.parent == frag.id
    assert early.qid is None and late.qid == "q-1"
    assert frag.qid == ops.qid == "q-2" and frag.attrs == {"fragment": 3}
    assert len({r.id for r in records}) == len(records)
    for child, parent in ((plan, root), (early, plan), (ops, frag)):
        assert parent.start_ns <= child.start_ns <= child.end_ns \
            <= parent.end_ns


def test_each_thread_has_its_own_stack(ring):
    barrier = threading.Barrier(4)
    seen = {}

    def work(i):
        with spans.span("engine.fragment", qid=f"q{i}") as outer:
            barrier.wait(timeout=10)
            with spans.span("engine.codec.encode") as inner:
                pass
        seen[i] = (outer, inner)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    with spans.span("engine.serve") as main:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert main.parent is None
    for i, (outer, inner) in seen.items():
        assert outer.parent is None            # a root on its own thread
        assert inner.parent == outer.id and inner.qid == f"q{i}"
    assert len(ring.snapshot()[0]) == 9


def test_a_span_whose_body_raises_is_closed_and_recorded(ring):
    with pytest.raises(KeyError):
        with spans.span("engine.serve") as root:
            with spans.span("engine.store.get") as get:
                raise KeyError("missing")
    records, _ = ring.snapshot()
    assert records == [get, root]
    assert get.attrs == root.attrs == {}
    assert get.end_ns >= get.start_ns and root.end_ns >= get.end_ns
    with spans.span("engine.serve") as after:        # the stack is empty
        pass
    assert after.parent is None


def test_the_ring_is_bounded_and_counts_what_it_drops():
    ring = spans.SpanRing(capacity=3)
    made = [spans.span(f"engine.s{i}") for i in range(5)]
    for s in made:
        ring.append(s)
    records, dropped = ring.snapshot()
    assert records == made[2:] and dropped == 2
    assert spans.RING._records.maxlen == spans.CAPACITY


def test_the_decorator_wraps_each_call(ring):
    @spans.spanned("engine.ops")
    def double(x):
        return 2 * x

    with spans.span("engine.fragment", qid="q") as frag:
        assert double(21) == 42
    (ops, _), _ = ring.snapshot()
    assert ops.name == "engine.ops" and ops.parent == frag.id
    assert ops.qid == "q" and double.__name__ == "double"


@pytest.fixture(scope="module")
def server():
    store = ObjectStore()
    srv = QueryServer(store, result_cache=False)
    srv.register_table("lineitem",
                       datagen.load_table(store, "lineitem", 6000, 2))
    srv.register_table("orders", datagen.load_table(store, "orders", 1500, 1))
    return srv


def test_served_query_spans_are_on_the_host_plane_of_the_trace(server,
                                                              tmp_path):
    from jax.profiler import ProfileData
    server.serve([QueryRequest(queries.q6_logical(731, 0.06, 24))])  # warm
    with jax.profiler.trace(str(tmp_path)):
        server.serve([QueryRequest(queries.q6_logical(731, 0.06, 24))])
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
               dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("engine.")]
    names = {e[0] for e in events}
    assert {"engine.serve", "engine.plan", "engine.schedule",
            "engine.fragment", "engine.fragment.assemble",
            "engine.store.get", "engine.codec.decode", "engine.ops",
            "engine.device", "engine.finalize"} <= names
    # The attributes known when a span opens are its trace arguments.
    for name, _, _, args in events:
        if name == "engine.device":
            assert args["kernel"] in {"mask", "project", "tail", "segment_reduce"}
            assert int(args["h2d_bytes"]) > 0
        if name == "engine.fragment":
            assert args["qid"].startswith("tpch_q6-")
            assert {"pipeline", "fragment", "attempt"} <= set(args)
    tiers = {args["tier"] for name, _, _, args in events
             if name == "engine.store.get"}
    assert "object" in tiers and tiers <= {"object", "kv"}
    (serve,) = [e for e in events if e[0] == "engine.serve"]
    assert serve[3] == {"requests": 1}
    fragments = [e for e in events if e[0] == "engine.fragment"]
    assert fragments
    for _, start, end, _ in fragments:
        assert serve[1] <= start <= end <= serve[2]


def test_explain_prints_measured_stage_seconds(server):
    report = server.serve([QueryRequest(queries.q12_logical(8, 731))])
    result = report.queries[0].result
    for m in result.stage_metrics.values():
        assert m["measured_s"] > 0
    text = explain.format_adaptive(result)
    assert "stage timings (start, end, duration: modelled; measured: " \
        "host seconds of the stage's fragments)" in text
    for name, m in result.stage_metrics.items():
        assert (f"  {name}: start={m['start']:.3f}s end={m['end']:.3f}s "
                f"duration={m['duration']:.3f}s "
                f"measured={m['measured_s']:.3f}s ") in text


def test_served_aggregations_count_how_they_grouped(server, monkeypatch):
    """Q1's and Q12's group keys are small integer domains, so each of
    their keyed aggregations groups with no sort, and counts so in a
    span below its query's ``engine.serve`` span."""
    ring = spans.SpanRing(capacity=1 << 14)
    monkeypatch.setattr(spans, "RING", ring)
    server.serve([QueryRequest(queries.q1_logical(90)),
                  QueryRequest(queries.q12_logical(8, 731))])
    records, dropped = ring.snapshot()
    assert not dropped
    by_id = {r.id: r for r in records}

    def root(r):
        while r.parent is not None:
            r = by_id[r.parent]
        return r

    counts = collections.Counter()
    for r in records:
        mine = {k: n for k, n in r.attrs.items() if k.startswith("hash_agg.")}
        if mine:
            assert root(r).name == "engine.serve"
            counts.update(mine)
    assert set(counts) == {"hash_agg.dense"}
    assert counts["hash_agg.dense"] >= 2      # at least one a query
