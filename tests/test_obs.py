"""repro.obs: traceparent propagation, span nesting, ring buffer, export.

Tests build private ``Tracer`` instances rather than mutating the global
``obs.TRACER`` so they stay independent of the HTTP-level tests running in
the same process.
"""
import json
import threading
import time

from repro import obs
from repro.obs import (NOOP, Span, SpanContext, Tracer, format_traceparent,
                       mint_span_id, mint_trace_id, parse_traceparent)
from repro.obs import profile
from repro.obs.trace import _CURRENT


# ------------------------------------------------------------- traceparent
def test_traceparent_roundtrip():
    tid, sid = mint_trace_id(), mint_span_id()
    assert len(tid) == 32 and len(sid) == 16
    hdr = format_traceparent(tid, sid)
    assert hdr == f"00-{tid}-{sid}-01"
    assert parse_traceparent(hdr) == (tid, sid)
    # whitespace and case are normalized per the spec
    assert parse_traceparent("  " + hdr.upper() + " ") == (tid, sid)


def test_traceparent_rejects_malformed_and_reserved():
    good_tid, good_sid = "ab" * 16, "cd" * 8
    for bad in (
            None, "", "garbage",
            f"00-{good_tid}-{good_sid}",            # missing flags
            f"00-{good_tid[:-1]}-{good_sid}-01",    # short trace id
            f"00-{good_tid}-{good_sid}-0",          # short flags
            f"00-{'z' * 32}-{good_sid}-01",         # non-hex
            f"ff-{good_tid}-{good_sid}-01",         # reserved version
            f"00-{'0' * 32}-{good_sid}-01",         # all-zero trace id
            f"00-{good_tid}-{'0' * 16}-01"):        # all-zero span id
        assert parse_traceparent(bad) is None, bad


def test_ids_unique():
    assert len({mint_trace_id() for _ in range(256)}) == 256
    assert len({mint_span_id() for _ in range(256)}) == 256


# ---------------------------------------------------------------- spanning
def test_span_nesting_records_parent_chain():
    tr = Tracer(capacity=8)
    root = tr.start_trace("req")
    with tr.attach(root):
        with tr.span("outer") as outer:
            with tr.span("inner", op="x") as inner:
                assert inner.parent_id == outer.span_id
            assert _CURRENT.get() is outer
    root.end()
    t = tr.get(root.trace_id)
    by_name = {s["name"]: s for s in t["spans"]}
    assert set(by_name) == {"req", "outer", "inner"}
    assert by_name["outer"]["parent_id"] == root.span_id
    assert by_name["inner"]["parent_id"] == by_name["outer"]["span_id"]
    assert by_name["inner"]["attrs"] == {"op": "x"}
    assert by_name["req"]["parent_id"] is None
    assert t["root"] == "req" and t["duration_us"] >= 0


def test_child_span_is_noop_outside_a_trace_and_when_disabled():
    tr = Tracer(capacity=8)
    assert tr.child_span("orphan") is NOOP
    with tr.span("orphan-cm") as sp:
        assert sp is NOOP and not sp
    tr.set_enabled(False)
    assert tr.start_trace("req") is NOOP
    assert not tr.stats()["enabled"]
    tr.set_enabled(True)
    root = tr.start_trace("req")
    assert root  # truthy again
    root.end()


def test_noop_span_absorbs_all_calls():
    NOOP.set_attr("k", "v")
    NOOP.add_link(SpanContext("ab" * 16, "cd" * 8))
    NOOP.end()
    assert NOOP.context is None
    assert not NOOP


def test_traceparent_continues_callers_trace():
    tr = Tracer(capacity=8)
    tid, parent_sid = mint_trace_id(), mint_span_id()
    root = tr.start_trace("req",
                          traceparent=format_traceparent(tid, parent_sid))
    assert root.trace_id == tid and root.parent_id == parent_sid
    root.end()
    assert tr.get(tid)["trace_id"] == tid


def test_attach_carries_span_across_threads():
    tr = Tracer(capacity=8)
    root = tr.start_trace("req")
    seen = {}

    def worker(parent):
        # a fresh thread has no inherited context ...
        seen["before"] = _CURRENT.get()
        with tr.attach(parent):
            with tr.span("work") as sp:
                seen["span"] = sp

    th = threading.Thread(target=worker, args=(root,))
    th.start()
    th.join(timeout=10)
    assert seen["before"] is None
    assert seen["span"].trace_id == root.trace_id
    assert seen["span"].parent_id == root.span_id
    root.end()
    names = [s["name"] for s in tr.get(root.trace_id)["spans"]]
    assert names == ["work", "req"]


def test_span_end_is_idempotent():
    tr = Tracer(capacity=8)
    root = tr.start_trace("req")
    root.end()
    first = tr.get(root.trace_id)["duration_us"]
    root.end()
    assert tr.get(root.trace_id)["duration_us"] == first
    assert tr.stats()["completed_total"] == 1


# -------------------------------------------------------------- ring buffer
def test_ring_buffer_caps_completed_traces():
    tr = Tracer(capacity=4)
    ids = []
    for i in range(10):
        root = tr.start_trace(f"t{i}")
        root.end()
        ids.append(root.trace_id)
    st = tr.stats()
    assert st["buffered"] == 4 and st["completed_total"] == 10
    assert [t["root"] for t in tr.recent()] == ["t9", "t8", "t7", "t6"]
    assert tr.recent(limit=2) == tr.recent()[:2]
    assert tr.get(ids[0]) is None          # evicted
    assert tr.get(ids[-1]) is not None     # newest survives


def test_max_spans_per_trace_drops_and_counts():
    tr = Tracer(capacity=4, max_spans_per_trace=3)
    root = tr.start_trace("req")
    with tr.attach(root):
        for i in range(5):
            with tr.span(f"c{i}"):
                pass
    root.end()
    # 2 children over the cap were dropped, root still finalizes the trace
    assert tr.stats()["spans_dropped"] == 3  # c3, c4, and the root record
    assert len(tr.get(root.trace_id)["spans"]) == 3


def test_straggler_span_lands_in_finished_trace():
    tr = Tracer(capacity=4)
    root = tr.start_trace("req")
    late = tr.child_span("late", parent=root)
    root.end()          # finalizes with just the root
    late.end()          # straggler: appended to the finished trace
    names = [s["name"] for s in tr.get(root.trace_id)["spans"]]
    assert names == ["req", "late"]
    assert tr.stats()["spans_dropped"] == 0


# ------------------------------------------------------------------- links
def test_links_resolve_one_hop():
    tr = Tracer(capacity=8)
    fused = tr.start_trace("fused")
    req = tr.start_trace("req")
    req.add_link(fused.context, kind="fused_dispatch")
    fused.add_link(req.context)
    fused.end()
    req.end()
    t = tr.get(req.trace_id)
    [link] = t["spans"][0]["links"]
    assert link["trace_id"] == fused.trace_id
    assert link["attrs"] == {"kind": "fused_dispatch"}
    [lt] = t["linked_traces"]
    assert lt["trace_id"] == fused.trace_id and lt["root"] == "fused"
    assert tr.get(req.trace_id, resolve_links=False).get("linked_traces") is None


# ------------------------------------------------------------ chrome export
def test_chrome_export_structure():
    tr = Tracer(capacity=8)
    fused = tr.start_trace("fused")
    root = tr.start_trace("req")
    with tr.attach(root):
        with tr.span("child", op="q") as sp:
            sp.add_link(fused.context)
    fused.end()
    root.end()
    doc = json.loads(tr.chrome_json(root.trace_id))
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} >= {"req", "child", "fused"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and "pid" in e and "tid" in e
    # per-trace process groups, named
    metas = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert len({e["pid"] for e in metas}) == 2
    # flow event along the cross-trace link
    assert any(e["ph"] == "s" for e in evs)
    assert tr.chrome("0" * 32) is None


# -------------------------------------------------------------- profile hook
def test_profile_hooks_fire_and_survive_exceptions():
    calls = []

    def bad(*a):
        raise RuntimeError("hook must not break dispatch")

    def good(op, backend, size, seconds):
        calls.append((op, backend, size))

    profile.add_hook(bad)
    profile.add_hook(good)
    try:
        profile.record("fitting_loss", "numpy", 128, 0.001)
    finally:
        profile.remove_hook(bad)
        profile.remove_hook(good)
    assert calls == [("fitting_loss", "numpy", 128)]
    profile.record("fitting_loss", "numpy", 1, 0.0)  # no hooks: no-op
    assert calls == [("fitting_loss", "numpy", 128)]


def test_shape_bucket_boundaries():
    assert profile.shape_bucket(None) == "none"
    assert profile.shape_bucket(0) == "le_2^0"
    assert profile.shape_bucket(1) == "le_2^0"
    assert profile.shape_bucket(2) == "le_2^1"
    assert profile.shape_bucket(3) == "le_2^2"
    assert profile.shape_bucket(1024) == "le_2^10"
    assert profile.shape_bucket(1025) == "le_2^11"


# ------------------------------------------------------- attrs are immutable
def test_recorded_spans_are_snapshots():
    tr = Tracer(capacity=8)
    root = tr.start_trace("req")
    root.set_attr("k", 1)
    root.end()
    got = tr.get(root.trace_id)
    got["spans"][0]["attrs"]["k"] = 999
    assert tr.get(root.trace_id)["spans"][0]["attrs"]["k"] == 1


def test_span_reprs_do_not_crash():
    # Span is __slots__-only; just make sure the public surface holds
    tr = Tracer(capacity=2)
    sp = tr.start_trace("req")
    assert isinstance(sp, Span)
    ctx = sp.context
    assert ctx.to_dict() == {"trace_id": sp.trace_id, "span_id": sp.span_id}
    sp.end()


# ------------------------------------------------- private tracers, cpu_s
def test_module_helpers_record_into_the_current_spans_tracer():
    # beneath a private tracer's span, obs.span / obs.child_span must land
    # in that tracer, not be dropped by the global one
    tr = Tracer(capacity=4)
    dropped = obs.TRACER.stats()["spans_dropped"]
    root = tr.start_trace("req")
    with tr.attach(root):
        with obs.span("helper.span") as sp:
            assert sp and sp.trace_id == root.trace_id
            obs.child_span("helper.child").end()
        with obs.cpu_span("helper.cpu"):
            pass
    root.end()
    names = {s["name"] for s in tr.get(root.trace_id)["spans"]}
    assert names == {"req", "helper.span", "helper.child", "helper.cpu"}
    assert tr.stats()["spans_dropped"] == 0
    assert obs.TRACER.stats()["spans_dropped"] == dropped


def test_cpu_span_records_thread_time_only_when_live():
    tr = Tracer(capacity=4)
    with tr.cpu_span("orphan") as sp:
        assert sp is NOOP
    root = tr.start_trace("req")
    with tr.attach(root):
        with tr.cpu_span("busy", k=1):
            # spin on the thread's own CPU clock: on a loaded machine 20 ms
            # of wall time may hold only a few ms of CPU
            t_end = time.thread_time() + 0.02
            while time.thread_time() < t_end:
                pass
        with tr.cpu_span("asleep"):
            time.sleep(0.05)
    root.end()
    by = {s["name"]: s for s in tr.get(root.trace_id)["spans"]}
    assert by["busy"]["attrs"]["k"] == 1
    assert 0.01 < by["busy"]["attrs"]["cpu_s"] <= \
        by["busy"]["duration_us"] / 1e6 + 1e-3
    assert by["asleep"]["attrs"]["cpu_s"] < 0.02
    assert by["asleep"]["duration_us"] >= 5e4


def _union_us(spans) -> float:
    iv = sorted((s["start_us"], s["start_us"] + s["duration_us"])
                for s in spans)
    total, cur0, cur1 = 0.0, None, None
    for a, b in iv:
        if cur1 is None or a > cur1:
            if cur1 is not None:
                total += cur1 - cur0
            cur0, cur1 = a, b
        else:
            cur1 = max(cur1, b)
    return total + (cur1 - cur0 if cur1 is not None else 0.0)


def test_sharded_build_phases_join_the_callers_trace():
    """Every pool thread re-enters the caller's trace: the bands' bounds
    beneath ``core.shared_tolerance``, one ``core.band`` per band beneath
    ``core.bands``, each holding its phases and its integral-image
    dispatch, none dropped.  The caller's thread holds every top-level
    phase, waits for and joins the pool inside them, and nothing of the
    build's own falls between them, so the phases cover the build however
    late the threads run."""
    from repro.core.sharded import band_bounds, sharded_coreset
    from repro.data import piecewise_signal
    # large enough that the pool's start-up stays a small share
    y = piecewise_signal(512, 320, 6, noise=0.15, seed=4) + 100.0
    tr = Tracer(capacity=4, max_spans_per_trace=1024)
    root = tr.start_trace("engine.compress")
    with tr.attach(root):
        sharded_coreset(y, 6, 0.3, num_bands=4)
    root.end()
    spans = tr.get(root.trace_id)["spans"]
    assert tr.stats()["spans_dropped"] == 0
    assert all(s["trace_id"] == root.trace_id for s in spans)
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    top = {s["name"]: s for s in kids[root.span_id]}
    assert len(kids[root.span_id]) == 3
    assert set(top) == {"core.shared_tolerance", "core.bands", "core.compose"}
    expect = [(i, r0, r1) for i, (r0, r1) in enumerate(band_bounds(512, 4))]
    bounds = [s for s in kids[top["core.shared_tolerance"]["span_id"]]
              if s["name"] == "core.bicriteria"]
    assert sorted(b["attrs"]["band"] for b in bounds) == [0, 1, 2, 3]
    bands = kids[top["core.bands"]["span_id"]]
    assert sorted((b["name"], b["attrs"]["band"], b["attrs"]["row0"],
                   b["attrs"]["row1"]) for b in bands) == \
        [("core.band",) + e for e in expect]
    for b in bands:
        names = {c["name"] for c in kids[b["span_id"]]}
        assert {"core.partition", "core.caratheodory",
                "ops.dispatch"} <= names
    for s in spans:
        if s["name"].startswith("core."):
            assert s["attrs"]["cpu_s"] <= s["duration_us"] / 1e6 + 1e-3
    assert _union_us(kids[root.span_id]) >= \
        0.95 * tr.get(root.trace_id)["duration_us"]


def _phases(build):
    """(spans, children-by-parent) of ``build()`` traced in its own tracer."""
    tr = Tracer(capacity=4, max_spans_per_trace=1024)
    root = tr.start_trace("engine.compress")
    with tr.attach(root):
        build()
    root.end()
    spans = tr.get(root.trace_id)["spans"]
    kids: dict = {}
    for s in spans:
        kids.setdefault(s["parent_id"], []).append(s)
    return root, spans, kids


def test_shared_build_bands_run_only_the_bounds():
    """Under the shared tolerance each band's bound is one ``core.bicriteria``
    span saying ``bound_only``, run beside the greedy pass beneath
    ``core.shared_tolerance``; no band computes a bound or a sigma of its
    own.  The shared tolerance carries both estimates and whether the bound
    set the sigma (``certified``); the compose span carries the shared
    sigma, the band bound it was judged by and the composed block count."""
    from repro.core.sharded import sharded_coreset
    from repro.data import piecewise_signal
    y = piecewise_signal(256, 160, 6, noise=0.15, seed=4)
    cs = None

    def build():
        nonlocal cs
        cs = sharded_coreset(y, 6, 0.3, num_bands=4)
    _, spans, kids = _phases(build)
    bands = [s for s in spans if s["name"] == "core.band"]
    assert len(bands) == 4
    for b in bands:
        assert "core.bicriteria" not in {c["name"]
                                         for c in kids[b["span_id"]]}
    [tol] = [s for s in spans if s["name"] == "core.shared_tolerance"]
    bics = [c for c in kids[tol["span_id"]] if c["name"] == "core.bicriteria"]
    assert len(bics) == 4
    assert all(b["attrs"]["bound_only"] is True for b in bics)
    assert not [s for s in spans if s["name"] == "core.sigma_greedy"]
    a = tol["attrs"]
    assert a["sigma_greedy"] > 0 and a["sigma_bound"] > 0
    assert a["certified"] is (a["sigma_bound"] >= a["sigma_greedy"])
    assert a["certified"] is cs.certified
    [comp] = [s for s in spans if s["name"] == "core.compose"]
    assert comp["attrs"]["sigma_shared"] == max(a["sigma_greedy"],
                                                a["sigma_bound"])
    assert comp["attrs"]["sigma_bound"] == a["sigma_bound"]
    assert comp["attrs"]["blocks"] == cs.num_blocks


def test_plain_build_still_estimates_its_sigma():
    from repro.core import signal_coreset
    from repro.data import piecewise_signal
    y = piecewise_signal(128, 96, 6, noise=0.15, seed=4)
    root, _, kids = _phases(lambda: signal_coreset(y, 6, 0.3))
    phases = {c["name"]: c for c in kids[root.span_id]}
    assert {"core.bicriteria", "core.sigma_greedy"} <= set(phases)
    assert "bound_only" not in phases["core.bicriteria"]["attrs"]


def test_partition_span_counts_its_searches():
    """``core.partition`` carries ``searches``: the column and row extent
    searches its balanced partition ran, counted per search."""
    from repro.core import PrefixStats, balanced_partition, signal_coreset
    from repro.data import piecewise_signal
    y = piecewise_signal(128, 96, 6, noise=0.15, seed=4)
    cs = None

    def build():
        nonlocal cs
        cs = signal_coreset(y, 6, 0.3)
    _, spans, _ = _phases(build)
    [part] = [s for s in spans if s["name"] == "core.partition"]
    searches = part["attrs"]["searches"]
    assert searches > 0
    assert searches == balanced_partition(
        PrefixStats.build(y), cs.tolerance, cs.max_slices).searches


# ------------------------------------------------------------------ compiles
def test_compile_lands_on_the_compiling_threads_span():
    import jax
    import jax.numpy as jnp
    profile.install_compile_listener()
    profile.install_compile_listener()       # idempotent
    seen = []
    hook = profile.add_compile_hook(seen.append)
    tr = Tracer(capacity=4)
    root = tr.start_trace("req")
    try:
        with tr.attach(root), tr.span("step") as sp:
            jax.jit(lambda x: x * 3.0 + 1.5)(jnp.ones(7)).block_until_ready()
    finally:
        profile.remove_compile_hook(hook)
        root.end()
    assert sp.attrs["compiles"] >= 1
    assert seen and all(s >= 0 for s in seen)
    assert "compiles" not in (root.attrs or {})


# ------------------------------------------------------ the profiler's clock
def test_spans_share_the_profilers_clock(tmp_path):
    """A span and a profiler annotation of the same step start within 1 ms
    of each other, so a ``?format=chrome`` export lays over a device
    trace without a shift."""
    import jax
    from jax.profiler import ProfileData
    tr = Tracer(capacity=4)
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.05)
        root = tr.start_trace("req")
        with tr.attach(root), tr.span("clock.probe"):
            with jax.profiler.TraceAnnotation("clock.probe"):
                time.sleep(0.01)
        root.end()
    finally:
        jax.profiler.stop_trace()
    span = next(s for s in tr.get(root.trace_id)["spans"]
                if s["name"] == "clock.probe")
    pd = ProfileData.from_file(str(next(tmp_path.glob("**/*.xplane.pb"))))
    start = {}
    for pl in pd.planes:
        if pl.name == "Task Environment":
            start.update(dict(pl.stats))
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name == "clock.probe":
                    start["event_ns"] = ev.start_ns
    # xplane events are stamped from the session's start, on the wall clock
    event_us = (start["profile_start_time"] + start["event_ns"]) / 1e3
    assert abs(event_us - span["start_us"]) < 1e3
