"""Coreset serving engine: dominance cache, scheduler, streamed ingest, HTTP."""
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from repro.client import CoresetAPIError, CoresetClient
from repro.core import (fitting_loss, random_tree_segmentation, signal_coreset,
                        true_loss)
from repro.data import piecewise_signal
from repro.service import (BuildScheduler, CacheEntry, CoresetEngine,
                           DominanceCache, ServiceMetrics, make_server,
                           serve_forever_in_thread)

N, M, KMAX = 72, 48, 8


def _engine(**kw):
    kw.setdefault("workers", 2)
    kw.setdefault("metrics", ServiceMetrics())
    return CoresetEngine(**kw)


def _signal(seed=0):
    return piecewise_signal(N, M, KMAX, noise=0.15, seed=seed)


# ------------------------------------------------------------------ dominance
def test_dominance_hit_serves_weaker_requests_without_rebuild():
    eng = _engine()
    try:
        eng.register_signal("s", _signal())
        cs, eps_eff, how = eng.get_coreset("s", KMAX, 0.2)
        assert how == "built" and eps_eff == 0.2
        # weaker request (smaller k, looser eps): dominated, same build count
        cs2, eps2, how2 = eng.get_coreset("s", 4, 0.35)
        assert how2 == "dominated"
        assert cs2.fingerprint() == cs.fingerprint()
        assert eps2 <= 0.35
        assert eng.metrics.get("coreset_builds") == 1
        # stronger request (larger k) must NOT be served by dominance
        _, _, how3 = eng.get_coreset("s", KMAX + 2, 0.2)
        assert how3 == "built"
        assert eng.metrics.get("coreset_builds") == 2
    finally:
        eng.close()


def test_tree_loss_defaults_k_to_leaf_count_and_is_accurate():
    eng = _engine()
    try:
        y = _signal(1)
        eng.register_signal("s", y)
        rng = np.random.default_rng(0)
        eng.get_coreset("s", KMAX, 0.2)  # anchor
        for _ in range(4):
            q = random_tree_segmentation(N, M, 6, rng)
            r = eng.tree_loss("s", q.rects, q.labels, eps=0.3)
            assert r["served_from"] in ("exact", "dominated")
            tl = true_loss(y, q.rects, q.labels)
            assert abs(r["loss"] - tl) <= 0.3 * max(tl, 1e-9)
        assert eng.metrics.get("cache_hit_dominated") >= 1
    finally:
        eng.close()


def test_cache_byte_budget_evicts_and_rebuilds():
    # budget fits ~one coreset: the second distinct signal overflows and the
    # GDSF policy evicts the lower-priority entry; the evicted one rebuilds
    eng = _engine(cache_bytes=1)  # any insert overflows; keeps one entry
    try:
        eng.register_signal("a", _signal(0))
        eng.register_signal("b", _signal(1))
        eng.get_coreset("a", 4, 0.3)
        eng.get_coreset("b", 4, 0.3)
        assert len(eng.cache) == 1  # cost-aware eviction kept one entry
        assert eng.metrics.get("cache_evictions") >= 1
        builds = eng.metrics.get("coreset_builds")
        # exactly one of the two is gone; re-requesting it rebuilds
        missing = [s for s in ("a", "b")
                   if eng.cache.lookup(s, eng.signal(s).version, 4, 0.3,
                                       record=False)[0] is None]
        assert len(missing) == 1
        _, _, how = eng.get_coreset(missing[0], 4, 0.3)
        assert how == "built"
        assert eng.metrics.get("coreset_builds") == builds + 1
    finally:
        eng.close()


# ------------------------------------------------------------ streamed ingest
def test_streamed_ingest_consistent_with_one_shot_build():
    eng = _engine()
    try:
        y = _signal(2)
        for i in range(0, N, 12):
            info = eng.ingest_band("st", y[i:i + 12])
        assert info["n"] == N and info["streamed"]
        cs, eps_eff, _ = eng.get_coreset("st", KMAX, 0.25)
        assert np.isclose(cs.total_mass(), y.size)
        one = signal_coreset(y, KMAX, 0.25)
        rng = np.random.default_rng(3)
        for _ in range(5):
            q = random_tree_segmentation(N, M, 6, rng)
            tl = true_loss(y, q.rects, q.labels)
            ls = fitting_loss(cs, q.rects, q.labels)
            lo = fitting_loss(one, q.rects, q.labels)
            # each side is within its eps of the true loss -> composed bound
            assert abs(ls - lo) <= (eps_eff + 0.25) * max(tl, 1e-9)
            assert abs(ls - tl) <= eps_eff * max(tl, 1e-9)
    finally:
        eng.close()


def test_ingest_bumps_version_and_invalidates_cache():
    eng = _engine()
    try:
        y = _signal(4)
        eng.ingest_band("st", y[:24])
        v1 = eng.signal("st").version
        eng.get_coreset("st", 4, 0.3)
        assert len(eng.cache) == 1
        eng.ingest_band("st", y[24:48])
        assert eng.signal("st").version != v1
        assert len(eng.cache) == 0  # stale version freed eagerly
        _, _, how = eng.get_coreset("st", 4, 0.3)
        assert how == "built"
    finally:
        eng.close()


# --------------------------------------------------------- concurrent clients
def test_concurrent_clients_identical_answers_and_coalesced_builds():
    eng = _engine(workers=4)
    try:
        y = _signal(5)
        eng.register_signal("s", y)
        q = random_tree_segmentation(N, M, 5, np.random.default_rng(1))
        results, errors = [], []
        barrier = threading.Barrier(6)

        def client():
            try:
                barrier.wait()
                for _ in range(3):
                    r = eng.tree_loss("s", q.rects, q.labels, eps=0.25, k=KMAX)
                    results.append(r["loss"])
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(set(results)) == 1  # deterministic: one coreset served all
        # identical concurrent keys collapsed to a single actual construction
        # (coreset_builds counts real builds; the scheduler may complete more
        # jobs when a late submitter's worker short-circuits on the cache)
        assert eng.metrics.get("coreset_builds") == 1
    finally:
        eng.close()


def test_scheduler_coalesces_identical_keys():
    sched = BuildScheduler(max_workers=2, batch_window=0.02)
    try:
        gate = threading.Event()
        calls = []

        def slow():
            gate.wait(5.0)
            calls.append(1)
            return "done"

        f1, created1 = sched.submit(("k",), slow)
        f2, created2 = sched.submit(("k",), slow)
        assert created1 and not created2 and f1 is f2
        gate.set()
        assert f1.result(timeout=10.0) == "done"
        assert calls == [1]
        # after completion the key is free again
        f3, created3 = sched.submit(("k",), lambda: "again")
        assert created3 and f3.result(timeout=10.0) == "again"
    finally:
        sched.shutdown()


# ------------------------------------------------------------------- HTTP API
def _server():
    eng = _engine()
    srv = make_server(eng)
    serve_forever_in_thread(srv)
    return eng, srv, f"http://127.0.0.1:{srv.server_address[1]}"


def test_http_v1_end_to_end_sdk():
    eng, srv, base = _server()
    try:
        y = _signal(6)
        for encoding in ("json", "binary"):
            cl = CoresetClient(base, encoding=encoding)
            cl.register_signal(f"s-{encoding}", values=y)
            b = cl.build(f"s-{encoding}", KMAX, 0.2)
            assert b.served_from == "built" and b.size > 0
            assert len(b.fingerprint) == 32 and b.build_seconds > 0
            q = random_tree_segmentation(N, M, 4, np.random.default_rng(2))
            r = cl.query_loss(f"s-{encoding}", q.rects, q.labels, eps=0.3)
            assert r.served_from in ("exact", "dominated")
            tl = true_loss(y, q.rects, q.labels)
            assert abs(r.loss - tl) <= 0.3 * max(tl, 1e-9)
            fit = cl.fit(f"s-{encoding}", KMAX, n_estimators=2,
                         predict=[[1, 1], [N - 2, M - 2]])
            assert fit.predictions.shape == (2,)
            comp = cl.compress(f"s-{encoding}", KMAX, 0.2, max_points=64)
            assert len(comp.X) <= 64 and comp.served_from == "exact"
            cl.ingest(f"st-{encoding}", synthetic={"kind": "piecewise",
                                                   "n": 16, "m": M, "seed": 1})
        health = CoresetClient(base).healthz()
        assert health["status"] == "ok" and health["signals"] == 4
        assert health["protocol"] == "v1"
        metrics = CoresetClient(base).metrics_text()
        assert "coreset_cache_hit_dominated" in metrics
        assert "coreset_build_seconds_bucket" in metrics
        # structured API error: unknown signal -> 404 envelope, server stays up
        try:
            CoresetClient(base).build("nope", 4, 0.3)
            raise AssertionError("expected CoresetAPIError")
        except CoresetAPIError as exc:
            assert exc.http == 404 and exc.code == "not_found"
        assert CoresetClient(base).healthz()["status"] == "ok"
    finally:
        srv.shutdown()
        eng.close()


def test_http_legacy_routes_answer_with_deprecation_header():
    eng, srv, base = _server()

    def post(path, payload):
        req = urllib.request.Request(base + path, data=json.dumps(payload).encode(),
                                     headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read()), dict(r.headers)

    try:
        y = _signal(6)
        body, hdr = post("/signals", {"name": "s", "values": y.tolist()})
        assert hdr.get("Deprecation") == "true"
        assert '</v1/signals>; rel="successor-version"' in hdr.get("Link", "")
        assert body["n"] == N and body["version"]
        body, hdr = post("/build", {"name": "s", "k": KMAX, "eps": 0.2})
        assert hdr.get("Deprecation") == "true"
        assert body["served_from"] == "built" and len(body["fingerprint"]) == 32
        # pre-v1 response compatibility: old key names still answer
        assert body["cache"] == "built" and "type" not in body
        comp, _ = post("/query/compress", {"name": "s", "k": KMAX, "eps": 0.2,
                                           "max_points": 64})
        assert comp["cache"] in ("exact", "dominated")
        assert len(comp["points"]["X"]) <= 64   # old nested points layout
        q = random_tree_segmentation(N, M, 4, np.random.default_rng(2))
        body, hdr = post("/query/loss", {"name": "s", "rects": q.rects.tolist(),
                                         "labels": q.labels.tolist(), "eps": 0.3})
        assert hdr.get("Deprecation") == "true"
        assert body["served_from"] in ("exact", "dominated")
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert r.headers.get("Deprecation") == "true"
            assert json.loads(r.read())["status"] == "ok"
        # v1 routes do NOT carry the deprecation header
        with urllib.request.urlopen(base + "/v1/healthz", timeout=30) as r:
            assert r.headers.get("Deprecation") is None
        # malformed legacy request -> 400 with the uniform v1 envelope
        try:
            post("/query/loss", {"name": "nope", "rects": [], "labels": []})
            raise AssertionError("expected HTTP error")
        except urllib.error.HTTPError as exc:
            assert exc.code == 400
            env = json.loads(exc.read())
            assert env["error"]["code"] == "bad_request"
            assert env["error"]["message"]
    finally:
        srv.shutdown()
        eng.close()


def test_http_400_envelope_for_ragged_and_non_numeric_arrays():
    eng, srv, base = _server()

    def post_raw(path, payload):
        req = urllib.request.Request(base + path, data=json.dumps(payload).encode(),
                                     headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=60).close()

    try:
        for bad_values in ([[1.0, 2.0], [3.0]],          # ragged
                           [["a", "b"], ["c", "d"]],     # non-numeric
                           [1.0, 2.0, 3.0],              # wrong ndim
                           [[1.0, float("nan")]]):       # non-finite signal
            for path, payload in (
                    ("/v1/signals", {"type": "register",
                                     "signal": {"name": "bad"},
                                     "values": bad_values}),
                    ("/signals", {"name": "bad", "values": bad_values})):
                try:
                    post_raw(path, payload)
                    raise AssertionError(f"expected 400 for {path} {bad_values}")
                except urllib.error.HTTPError as exc:
                    assert exc.code == 400, (path, bad_values)
                    env = json.loads(exc.read())
                    assert env["error"]["code"] == "bad_request"
                    assert isinstance(env["error"]["message"], str)
        # nothing got registered, server healthy
        assert CoresetClient(base).healthz()["signals"] == 0
    finally:
        srv.shutdown()
        eng.close()


def test_http_415_for_undecodable_codec_and_unknown_media_type():
    from repro.service import protocol as P
    eng, srv, base = _server()

    def post_raw(path, body, ctype):
        req = urllib.request.Request(base + path, data=body,
                                     headers={"Content-Type": ctype})
        urllib.request.urlopen(req, timeout=30).close()

    try:
        try:
            post_raw("/v1/signals", b"<xml/>", "application/xml")
            raise AssertionError("expected 415")
        except urllib.error.HTTPError as exc:
            assert exc.code == 415
            assert json.loads(exc.read())["error"]["code"] == "unsupported_media"
        if P.zstandard is None:
            # a zstd frame on this zlib-only host: 415 tells the SDK to
            # renegotiate down to JSON instead of failing with 400
            frame = b"RPV1" + b"Z" + b"\x28\xb5\x2f\xfd" + b"\x00" * 8
            try:
                post_raw("/v1/signals", frame, P.CONTENT_TYPE_BINARY)
                raise AssertionError("expected 415")
            except urllib.error.HTTPError as exc:
                assert exc.code == 415
                env = json.loads(exc.read())
                assert env["error"]["code"] == "unsupported_media"
    finally:
        srv.shutdown()
        eng.close()


# --------------------------------------------------- fused batch loss queries
def test_batch_loss_uses_fewer_scoring_calls_than_sequential():
    eng, srv, base = _server()
    try:
        y = _signal(8)
        cl = CoresetClient(base)
        cl.register_signal("s", values=y)
        rng = np.random.default_rng(3)
        segs = [random_tree_segmentation(N, M, 5, rng) for _ in range(32)]
        rects = np.stack([s.rects for s in segs])
        labels = np.stack([s.labels for s in segs])

        base_calls = eng.metrics.get("loss_scoring_calls")
        seq = [cl.query_loss("s", s.rects, s.labels, eps=0.3, k=KMAX).loss
               for s in segs]
        seq_calls = eng.metrics.get("loss_scoring_calls") - base_calls
        assert seq_calls == 32

        base_calls = eng.metrics.get("loss_scoring_calls")
        rb = cl.query_loss_batch("s", rects, labels, eps=0.3, k=KMAX)
        batch_calls = eng.metrics.get("loss_scoring_calls") - base_calls
        assert batch_calls == 1 < seq_calls
        assert rb.scoring_calls == 1
        assert rb.losses.shape == (32,)
        assert np.allclose(rb.losses, seq, rtol=1e-4)
        # the fused result honors the same guarantee as the sequential path
        for s, lb in zip(segs, rb.losses):
            tl = true_loss(y, s.rects, s.labels)
            assert abs(lb - tl) <= 0.3 * max(tl, 1e-9) * (1 + 1e-4)
    finally:
        srv.shutdown()
        eng.close()


def test_batch_loss_validates_shapes():
    eng = _engine()
    try:
        eng.register_signal("s", _signal())
        rng = np.random.default_rng(0)
        q = random_tree_segmentation(N, M, 4, rng)
        try:
            eng.tree_loss_batch("s", q.rects, q.labels)  # 2-D, not (T, K, 4)
            raise AssertionError("expected ValueError")
        except ValueError:
            pass
    finally:
        eng.close()


# ------------------------------------------------------- forest model caching
def test_fit_forest_caches_by_fingerprint_and_hyperparams():
    eng = _engine()
    try:
        eng.register_signal("s", _signal(9))
        r1 = eng.fit_forest("s", k=4, eps=0.3, n_estimators=2, seed=7,
                            predict=[[1, 1]])
        assert r1["model_cache"] == "fit"
        r2 = eng.fit_forest("s", k=4, eps=0.3, n_estimators=2, seed=7,
                            predict=[[1, 1]])
        assert r2["model_cache"] == "hit"
        assert r2["predictions"] == r1["predictions"]
        assert eng.metrics.get("forest_cache_hit") == 1
        # different hyperparams / seed -> distinct cache slots
        assert eng.fit_forest("s", k=4, eps=0.3, n_estimators=3,
                              seed=7)["model_cache"] == "fit"
        assert eng.fit_forest("s", k=4, eps=0.3, n_estimators=2,
                              seed=8)["model_cache"] == "fit"
    finally:
        eng.close()


# ------------------------------------------- cache build_seconds + eviction
def test_cache_records_build_seconds_and_exposes_in_stats():
    eng, srv, base = _server()
    try:
        cl = CoresetClient(base)
        cl.register_signal("s", values=_signal(10))
        b = cl.build("s", 4, 0.3)
        assert b.build_seconds > 0
        stats = cl.stats()
        keys = stats["cache"]["keys"]
        assert len(keys) == 1
        assert keys[0]["build_seconds"] > 0
        # insert-time record matches the build response's wall clock
        assert abs(keys[0]["build_seconds"] - b.build_seconds) < 1e-9
    finally:
        srv.shutdown()
        eng.close()


def test_dominance_cache_evicts_stale_versions_on_ingest():
    # cache-level: invalidate_signal drops every entry of other versions
    cache = DominanceCache(metrics=ServiceMetrics())
    cs = signal_coreset(_signal(11), 4, 0.3)

    def entry(version, k):
        return CacheEntry(signal="s", version=version, k=k, eps=0.3,
                          eps_eff=0.3, coreset=cs, nbytes=cs.nbytes,
                          fingerprint=cs.fingerprint(),
                          build_seconds=cs.build_seconds)

    cache.put(entry("v1", 4))
    cache.put(entry("v1", 8))
    cache.put(entry("v2", 4))
    assert len(cache) == 3
    dropped = cache.invalidate_signal("s", keep_version="v2")
    assert len(dropped) == 2 and len(cache) == 1
    assert {e.version for e in dropped} == {"v1"}
    assert cache.stats()["reanchor_candidates"] == 2
    e, kind = cache.lookup("s", "v2", 4, 0.3)
    assert kind == "exact" and e.build_seconds == cs.build_seconds
    assert cache.lookup("s", "v1", 4, 0.3) == (None, None)

    # engine-level: a fresh band bumps the version and evicts eagerly
    eng = _engine()
    try:
        y = _signal(11)
        eng.ingest_band("st", y[:24])
        eng.get_coreset("st", 4, 0.3)
        assert len(eng.cache) == 1
        eng.ingest_band("st", y[24:48])
        assert len(eng.cache) == 0
    finally:
        eng.close()


# ------------------------------------------------ cost-aware (GDSF) eviction
def _gdsf_entry(cs, name, *, build_seconds, nbytes=None):
    return CacheEntry(signal=name, version="v", k=4, eps=0.3, eps_eff=0.3,
                      coreset=cs, nbytes=nbytes or cs.nbytes,
                      fingerprint=cs.fingerprint(),
                      build_seconds=build_seconds)


def test_gdsf_expensive_entry_outlives_cheap_same_size_one():
    cs = signal_coreset(_signal(12), 4, 0.3)
    # budget fits exactly two entries of cs.nbytes
    cache = DominanceCache(byte_budget=2 * cs.nbytes, metrics=ServiceMetrics())
    cheap = _gdsf_entry(cs, "cheap", build_seconds=1e-4)
    pricey = _gdsf_entry(cs, "pricey", build_seconds=5.0)
    cache.put(cheap)
    cache.put(pricey)
    # equal recency, equal size, no hits: overflow must pick the cheap one
    cache.put(_gdsf_entry(cs, "third", build_seconds=1e-4))
    assert len(cache) == 2
    got, kind = cache.lookup("pricey", "v", 4, 0.3)
    assert kind == "exact" and got.build_seconds == 5.0
    assert cache.lookup("cheap", "v", 4, 0.3) == (None, None)


def test_gdsf_hit_rate_expensive_entry_survives_churn():
    # an expensive-to-rebuild entry keeps hitting across a stream of cheap
    # same-size inserts that each overflow the budget
    cs = signal_coreset(_signal(13), 4, 0.3)
    m = ServiceMetrics()
    cache = DominanceCache(byte_budget=2 * cs.nbytes, metrics=m)
    cache.put(_gdsf_entry(cs, "pricey", build_seconds=3.0))
    hits = 0
    for i in range(8):
        cache.put(_gdsf_entry(cs, f"cheap{i}", build_seconds=1e-4))
        e, kind = cache.lookup("pricey", "v", 4, 0.3)
        hits += kind == "exact"
    assert hits == 8                       # 100% hit rate for the hot entry
    assert m.get("cache_evictions") >= 7   # the cheap stream churned instead


def test_gdsf_clock_ages_out_untouched_entries():
    # recency still matters: once the clock has advanced past an idle
    # entry's stale priority, a fresher cheap entry outranks it
    cs = signal_coreset(_signal(14), 4, 0.3)
    cache = DominanceCache(byte_budget=2 * cs.nbytes, metrics=ServiceMetrics())
    cache.put(_gdsf_entry(cs, "idle", build_seconds=0.05))
    # churn enough cheap entries that each eviction raises the clock
    for i in range(50):
        cache.put(_gdsf_entry(cs, f"c{i}", build_seconds=0.02))
        cache.lookup(f"c{i}", "v", 4, 0.3)   # keep the newest one hot
    assert cache.lookup("idle", "v", 4, 0.3) == (None, None)
    assert cache.stats()["clock"] > 0.0


# ------------------------------------------- observability: /metrics grammar
_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_LABEL_BODY = (r'[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\["\\n])*"')
_TYPE_RE = re.compile(rf"^# TYPE ({_NAME}) (counter|histogram)$")
_SAMPLE_RE = re.compile(
    rf"^({_NAME})(\{{{_LABEL_BODY}(?:,{_LABEL_BODY})*\}})?"
    r" (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)"
    rf'( # \{{trace_id="(?:[^"\\\n]|\\["\\n])*"\}}'
    r" -?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)?$")
_LE_RE = re.compile(r'le="([^"]*)"')


def _check_prometheus_grammar(body: str):
    """Strict line-by-line parse of a /metrics exposition body.  Returns
    {family: type} after asserting: every line is a TYPE header or a
    well-formed sample, one unique TYPE per family, samples contiguous
    under their family's header, histogram bucket counts cumulative with
    le="+Inf" equal to the _count sample."""
    families: dict[str, str] = {}
    closed: set = set()
    current = None
    buckets: dict[tuple, list] = {}
    counts: dict[tuple, float] = {}
    assert body.endswith("\n")
    for line in body.splitlines():
        m = _TYPE_RE.match(line)
        if m:
            fam, typ = m.groups()
            assert fam not in families, f"duplicate # TYPE for {fam}"
            if current is not None:
                closed.add(current)
            families[fam] = typ
            current = fam
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed exposition line: {line!r}"
        name, labels, value, exemplar = m.groups()
        fam = name
        for sfx in ("_bucket", "_sum", "_count"):
            if name.endswith(sfx) and name[:-len(sfx)] in families:
                fam = name[:-len(sfx)]
                break
        assert fam in families, f"sample {name!r} precedes its # TYPE"
        assert fam == current, f"sample {name!r} outside its family block"
        assert fam not in closed, f"family {fam} not contiguous"
        if families[fam] == "histogram" and name.endswith("_bucket"):
            assert exemplar is None or "trace_id=" in exemplar
            le = _LE_RE.search(labels or "")
            assert le, f"bucket sample without le label: {line!r}"
            key = (fam, _LE_RE.sub("", labels or ""))
            buckets.setdefault(key, []).append((le.group(1), float(value)))
        elif families[fam] == "histogram" and name.endswith("_count"):
            counts[(fam, labels or "")] = float(value)
        else:
            assert exemplar is None, f"exemplar on non-bucket line: {line!r}"
    for (fam, labels), series in buckets.items():
        vals = [v for _, v in series]
        assert vals == sorted(vals), f"{fam}{labels} buckets not cumulative"
        assert series[-1][0] == "+Inf", f"{fam}{labels} missing +Inf bucket"
        ckey = (fam, labels.replace("{,", "{").replace(",}", "}")
                .replace("{}", ""))
        assert counts[ckey] == vals[-1], \
            f"{fam}{labels}: +Inf bucket != _count"
    return families


def test_metrics_exposition_grammar_end_to_end():
    eng, srv, base = _server()
    try:
        cl = CoresetClient(base)
        cl.register_signal("s", values=_signal(15))
        cl.build("s", 4, 0.3)
        q = random_tree_segmentation(N, M, 4, np.random.default_rng(5))
        cl.query_loss("s", q.rects, q.labels, eps=0.3)
        body = cl.metrics_text()
        families = _check_prometheus_grammar(body)
        # the per-(op, backend, shape-bucket) dispatch families are present
        assert families.get("coreset_ops_dispatch_total") == "counter"
        assert families.get("coreset_ops_dispatch_seconds") == "histogram"
        assert re.search(r'coreset_ops_dispatch_total\{[^}]*backend="', body)
        assert re.search(r'coreset_ops_dispatch_total\{[^}]*bucket="le_2', body)
        # latency histograms carry OpenMetrics exemplars with trace ids
        assert re.search(r'_bucket\{[^}]*\} \d+ # \{trace_id="[0-9a-f]{32}"\}',
                         body)
    finally:
        srv.shutdown()
        eng.close()


def test_metrics_label_values_are_escaped():
    m = ServiceMetrics()
    hostile = 'x"y\\z\nw'
    m.inc("labelled_total", op=hostile)
    m.observe("labelled_lat", 0.01, op=hostile)
    body = m.render()
    _check_prometheus_grammar(body)     # hostile value must not break parse
    assert '\\"y' in body and "\\\\z" in body and "\\nw" in body
    assert "\nw" not in body.replace("\\nw", "")  # no raw newline leaked
    from repro.service.metrics import escape_label_value
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"
    # backslash first: an escaped quote does not double-escape
    assert escape_label_value('\\"') == '\\\\\\"'


def test_uptime_uses_monotonic_clock():
    m = ServiceMetrics()
    # a wall-clock step (NTP, DST) must not affect uptime: started_at is
    # display-only, uptime reads the monotonic clock
    m.started_at = time.time() + 3600.0
    u1 = m.uptime_s()
    assert 0.0 <= u1 < 60.0
    time.sleep(0.01)
    u2 = m.snapshot()["uptime_s"]
    assert u2 > u1


# ------------------------------------------------ observability: HTTP traces
def test_http_trace_retrieval_and_chrome_export():
    eng, srv, base = _server()
    try:
        cl = CoresetClient(base)
        cl.register_signal("s", values=_signal(16))
        cl.build("s", 4, 0.3)
        q = random_tree_segmentation(N, M, 4, np.random.default_rng(6))
        cl.query_loss("s", q.rects, q.labels, eps=0.3)
        tid = cl.last_trace_id
        assert tid and len(tid) == 32
        # the client's minted traceparent is the server-side trace id
        assert cl.last_traceparent.split("-")[1] == tid
        trace = cl.trace(tid)
        names = [s["name"] for s in trace["spans"]]
        assert "POST /v1/query/loss" in names
        assert "engine.tree_loss" in names and "coreset.get" in names
        # recent listing includes it, newest first
        recent = cl.traces_recent(limit=5)
        assert any(t["trace_id"] == tid for t in recent)
        # chrome export parses and has complete events
        chrome = cl.trace(tid, format="chrome")
        assert any(e.get("ph") == "X" for e in chrome["traceEvents"])
        # unknown id -> 404; bad format -> 400; bad limit -> 400
        try:
            cl.trace("0" * 32)
            raise AssertionError("expected 404")
        except CoresetAPIError as exc:
            assert exc.http == 404
        with urllib.request.urlopen(
                base + f"/v1/trace/{tid}?format=chrome", timeout=30) as r:
            assert json.loads(r.read())["traceEvents"]
        for bad in (f"/v1/trace/{tid}?format=xml", "/v1/traces:recent?limit=x"):
            try:
                urllib.request.urlopen(base + bad, timeout=30).close()
                raise AssertionError(f"expected 400 for {bad}")
            except urllib.error.HTTPError as exc:
                assert exc.code == 400
    finally:
        srv.shutdown()
        eng.close()


def test_register_trace_times_body_read_and_decode():
    eng, srv, base = _server()
    try:
        cl = CoresetClient(base)
        cl.register_signal("s", values=_signal(17))
        by = {s["name"]: s for s in cl.trace(cl.last_trace_id)["spans"]}
        root = by["POST /v1/signals"]
        for name in ("http.read_body", "http.decode"):
            assert by[name]["parent_id"] == root["span_id"]
            assert by[name]["duration_us"] <= root["duration_us"]
        assert by["http.read_body"]["attrs"]["bytes"] >= N * M
    finally:
        srv.shutdown()
        eng.close()


def test_client_surfaces_trace_id_on_api_errors():
    eng, srv, base = _server()
    try:
        cl = CoresetClient(base)
        try:
            cl.build("missing-signal", 4, 0.3)
            raise AssertionError("expected CoresetAPIError")
        except CoresetAPIError as exc:
            assert exc.http == 404
            assert exc.trace_id and len(exc.trace_id) == 32
            assert f"[trace {exc.trace_id}]" in str(exc)
            # the failed request's trace is itself retrievable
            assert cl.trace(exc.trace_id)["root"].startswith("POST ")
    finally:
        srv.shutdown()
        eng.close()


# ------------------------------------------------- satellite: fingerprint API
def test_fingerprint_stable_and_repr_informative():
    y = _signal(7)
    a = signal_coreset(y, 4, 0.3)
    b = signal_coreset(y, 4, 0.3)
    c = signal_coreset(y, 4, 0.2)
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != c.fingerprint()
    assert a.nbytes == a.rects.nbytes + a.labels.nbytes + a.weights.nbytes + a.moments.nbytes
    r = repr(a)
    assert f"k={a.k}" in r and "eps=0.3" in r and f"size={a.size}" in r
    assert a.fingerprint()[:10] in r
