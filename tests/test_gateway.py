"""HTTP/SSE gateway validation — the transport is provably transparent.

Five layers, mirroring the PR contract:
  1. ACCEPTANCE identity — greedy token streams served over HTTP as SSE
     are byte-identical to the sequential ``engine.generate`` oracle (the
     same oracle the in-process session parity suite pins, so SSE ==
     ``RequestHandle.tokens()`` by transitivity) across dense, packed,
     kv-quant, ssm and hybrid smoke configs, under concurrent requests;
  2. typed rejection mapping — every ``ShedError`` reason surfaces as the
     stable HTTP status from serve/reasons.py (queue-full / tenant-quota
     → 429 with Retry-After, page-budget → 503), malformed bodies and
     never-fitting capacity requests as 400, before any SSE stream
     starts; a mid-flight deadline EXPIRED ends the stream with exactly
     one terminal ``error`` event carrying ``Request.fail_reason``;
  3. /metrics — Prometheus text with the scheduler lifecycle counters,
     pool/queue gauges, prefix-cache counters and TTFT/inter-token
     histograms all present and consistent with the traffic served;
  4. lifecycle — /healthz flips 200→503 at drain begin, draining
     gateways refuse new work while in-flight streams finish, client
     disconnect cancels the request (lane + pages free for co-tenants);
  5. request parsing — the JSON body validator rejects bad shapes with
     client-facing messages, never stack traces.

Everything runs a REAL server on an ephemeral localhost port via
``GatewayHTTP.start_background()`` and speaks actual HTTP/1.1 through
``http.client`` — no mocked transport anywhere.
"""
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.gateway import Gateway, GatewayHTTP, parse_generate_body
from repro.models import lm_init
from repro.serve import ServeEngine

RNG = np.random.default_rng(7)


def _engine(arch="gemma2-2b", packed=False, quant=False, max_len=32):
    cfg = get_smoke(arch)
    if quant:
        cfg = cfg.scaled(kv_cache_quant=True)
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, max_len=max_len, packed=packed), cfg


def _boot(engine, **kw):
    gw = Gateway(engine, **kw)
    srv = GatewayHTTP(gw)
    host, port = srv.start_background()
    return gw, srv, host, port


def _post(host, port, body, timeout=300):
    # generous: the hybrid config's first session prefill/segment compile
    # happens inside the step thread while this client blocks on the
    # socket — on the shared CI container that can exceed a minute.
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = (resp.status, dict(resp.getheaders()), resp.read().decode())
    conn.close()
    return out


def _get(host, port, path, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = (resp.status, resp.read().decode())
    conn.close()
    return out


def _parse_sse(text):
    """→ (tokens, [(terminal_event, payload_dict)]). The terminal list
    must have exactly one element for a well-formed stream."""
    toks, terminals = [], []
    for block in text.strip().split("\n\n"):
        fields = dict(line.split(": ", 1) for line in block.splitlines())
        if fields.get("event") == "token":
            toks.append(int(fields["data"]))
        elif "event" in fields:
            terminals.append((fields["event"], json.loads(fields["data"])))
    return toks, terminals


def _ref(engine, p, n):
    return np.asarray(engine.generate(jnp.asarray(p[None]), n)[0])


def _wait(cond, timeout=30.0, msg="condition"):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.01)


# ---------------------------------------------------------------------------
# 1. acceptance identity: SSE over HTTP == sequential oracle, all configs
# ---------------------------------------------------------------------------
CONFIGS = [
    pytest.param("gemma2-2b", False, False, id="dense"),
    pytest.param("gemma2-2b", True, False, id="packed"),
    pytest.param("gemma2-2b", False, True, id="kv-quant"),
    pytest.param("falcon-mamba-7b", False, False, id="ssm"),
    pytest.param("jamba-1.5-large-398b", False, False, id="hybrid"),
]


@pytest.mark.parametrize("arch,packed,quant", CONFIGS)
def test_sse_stream_matches_sequential(arch, packed, quant):
    """Concurrent greedy requests over live HTTP: each SSE stream is
    token-for-token the sequential oracle, one event per token, exactly
    one terminal ``end`` event. 1:1 with ``tokens()`` by the session
    parity suite's oracle transitivity."""
    engine, cfg = _engine(arch, packed, quant)
    lens, ntoks = [5, 8, 11], [6, 3, 8]
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in lens]
    refs = [_ref(engine, p, n) for p, n in zip(prompts, ntoks)]
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4, segment=2)
    try:
        results = [None] * len(prompts)

        def worker(i):
            results[i] = _post(host, port, {"prompt": prompts[i].tolist(),
                                            "max_tokens": ntoks[i]})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (status, headers, body) in enumerate(results):
            assert status == 200
            assert headers["Content-Type"] == "text/event-stream"
            toks, terminals = _parse_sse(body)
            np.testing.assert_array_equal(np.asarray(toks, np.int32), refs[i])
            assert terminals == [("end", {"status": "done",
                                          "tokens": ntoks[i],
                                          "preempted": 0,
                                          "preempted_swap": 0,
                                          "preempted_recompute": 0})]
    finally:
        srv.stop()
        gw.close()


def test_nonstream_json_matches_sequential():
    engine, cfg = _engine()
    p = RNG.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    ref = _ref(engine, p, 8)
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4)
    try:
        status, _, body = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 8,
                                             "stream": False})
        assert status == 200
        obj = json.loads(body)
        assert obj["status"] == "done" and obj["event"] == "end"
        np.testing.assert_array_equal(np.asarray(obj["tokens"], np.int32),
                                      ref)
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 2. typed rejections → stable HTTP codes; EXPIRED → terminal SSE error
# ---------------------------------------------------------------------------
def test_queue_full_is_429_with_retry_after():
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, max_pending=0)
    try:
        status, headers, body = _post(
            host, port, {"prompt": [1, 2, 3], "max_tokens": 4})
        assert status == 429
        assert headers.get("Retry-After") == "1"
        obj = json.loads(body)
        assert obj["error"] == "queue-full" and "rid" in obj
    finally:
        srv.stop()
        gw.close()


def test_tenant_quota_is_429_with_retry_after():
    """Tenant A's first request holds its quota'd lane; A's second sheds
    tenant-quota (429) while tenant B still admits (200) — the quota is
    per-tenant, not global."""
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4,
                                tenant_lane_quota=1)
    try:
        # occupy tenant A's one-lane quota deterministically in-process
        # (quota accounts worst-case pending+active at submit, so the
        # HTTP rejection below does not race admission timing)
        from repro.serve import SamplingParams
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        gw.submit(p, SamplingParams(max_tokens=12, tenant="A"))
        status, headers, body = _post(
            host, port, {"prompt": p.tolist(), "max_tokens": 12,
                         "tenant": "A"})
        assert status == 429
        assert headers.get("Retry-After") == "1"
        assert json.loads(body)["error"] == "tenant-quota"
        status, _, body = _post(
            host, port, {"prompt": p.tolist(), "max_tokens": 4,
                         "tenant": "B"})
        assert status == 200     # other tenants unaffected
    finally:
        srv.stop()
        gw.close()


def test_page_budget_is_503_without_retry_after():
    """A request whose page budget can NEVER fit this pool is not
    retryable: 503, no Retry-After header."""
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, n_pages=3)
    try:
        status, headers, body = _post(
            host, port, {"prompt": [1, 2, 3, 4], "max_tokens": 12})
        assert status == 503
        assert "Retry-After" not in headers
        assert json.loads(body)["error"] == "page-budget"
    finally:
        srv.stop()
        gw.close()


def test_expired_midflight_ends_stream_with_error_event():
    """Deadline passes while the request is decoding (driven by an
    injectable fake clock): the SSE stream ends with exactly one terminal
    ``error`` event carrying ``Request.fail_reason`` (= "deadline"), and
    the partial tokens already streamed are a prefix of the oracle."""
    engine, cfg = _engine()
    clk = [0.0]
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, segment=1,
                                clock=lambda: clk[0])
    try:
        p = RNG.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        ref = _ref(engine, p, 24)
        result = {}

        def worker():
            result["r"] = _post(host, port, {
                "prompt": p.tolist(), "max_tokens": 24,
                "deadline_ms": 10_000})

        t = threading.Thread(target=worker)
        t.start()
        # wait until it is live and has streamed at least one token, then
        # blow past the deadline — the next step's sweep expires it
        _wait(lambda: any(tr.handle.tokens_ready >= 1 and
                          tr.handle.status.value == "decoding"
                          for tr in list(gw._tracked.values())),
              msg="request decoding")
        clk[0] = 20_000.0
        t.join(timeout=30)
        assert not t.is_alive()
        status, _, body = result["r"]
        assert status == 200                 # stream started before expiry
        toks, terminals = _parse_sse(body)
        assert len(terminals) == 1
        ev, payload = terminals[0]
        assert ev == "error"
        assert payload["status"] == "expired"
        assert payload["reason"] == "deadline"
        assert 1 <= len(toks) < 24
        np.testing.assert_array_equal(np.asarray(toks, np.int32),
                                      ref[:len(toks)])
    finally:
        srv.stop()
        gw.close()


def test_malformed_bodies_are_400(monkeypatch=None):
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4)
    try:
        for body in ({"prompt": "text"}, {"prompt": []},
                     {"prompt": [1, -2]}, {"prompt": [1], "bogus": 1},
                     {"prompt": [1], "max_tokens": "many"}):
            status, _, resp = _post(host, port, body)
            assert status == 400, body
            assert json.loads(resp)["error"] == "bad-request"
        # capacity validation (prompt+budget > max_len) is a 400 too —
        # client error, not overload
        status, _, resp = _post(host, port,
                                {"prompt": [1, 2, 3], "max_tokens": 1000})
        assert status == 400
        # non-JSON body
        conn = http.client.HTTPConnection(host, port, timeout=10)
        conn.request("POST", "/v1/generate", "not json{",
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 400
        r.read()
        conn.close()
        # wrong method / unknown route
        conn = http.client.HTTPConnection(host, port, timeout=10)
        conn.request("GET", "/v1/generate")
        r = conn.getresponse()
        assert r.status == 405
        r.read()
        conn.close()
        assert _get(host, port, "/nope")[0] == 404
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 3. /metrics: Prometheus text, consistent with the traffic served
# ---------------------------------------------------------------------------
def test_metrics_exposition():
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4,
                                prefix_cache=True)
    try:
        p = RNG.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
        for _ in range(2):       # second run hits the prefix index
            status, _, _ = _post(host, port, {"prompt": p.tolist(),
                                              "max_tokens": 4})
            assert status == 200
        _wait(lambda: gw.session.idle, msg="session idle")
        status, text = _get(host, port, "/metrics")
        assert status == 200
        metrics = {}
        for line in text.splitlines():
            if line and not line.startswith("#"):
                name, value = line.rsplit(" ", 1)
                metrics[name] = float(value)
        # scheduler lifecycle + occupancy
        assert metrics["serve_sched_admitted_total"] == 2
        assert metrics["serve_active_requests"] == 0
        assert metrics["serve_lanes_total"] == 2
        # pool gauges consistent: total = free + owned + garbage page
        assert (metrics["serve_pool_pages_total"]
                == metrics["serve_pool_pages_free"]
                + metrics["serve_pool_pages_owned"] + 1)
        # prefix counters present and the second request hit
        assert metrics["serve_prefix_lookups_total"] == 2
        assert metrics["serve_prefix_exact_hits_total"] >= 1
        # queue wait: both admissions counted, on the session's clock;
        # programs built: at least the cold prefill and the decode segment,
        # and the series reads the engine's own count
        assert metrics["serve_queue_waited_total"] == 2
        assert metrics["serve_queue_wait_ms_total"] >= 0
        assert metrics["serve_programs_built_total"] \
            == engine.programs_built >= 2
        # latency histograms: one TTFT observation per stream, cumulative
        # buckets monotone, +Inf bucket == count
        assert metrics["gateway_ttft_seconds_count"] == 2
        buckets = [(float(n.split('le="')[1].rstrip('"}')
                          .replace("+Inf", "inf")), v)
                   for n, v in metrics.items()
                   if n.startswith("gateway_ttft_seconds_bucket")]
        buckets.sort()
        assert [v for _, v in buckets] == sorted(v for _, v in buckets)
        assert buckets[-1][1] == metrics["gateway_ttft_seconds_count"]
        assert metrics["gateway_inter_token_seconds_count"] == 6  # 2*(4-1)
        assert metrics["gateway_tokens_streamed_total"] == 8
        # HTTP + stream outcome counters
        assert metrics[
            'gateway_http_requests_total{code="200",path="/v1/generate"}'] == 2
        assert metrics['gateway_streams_total{outcome="done"}'] == 2
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 4. lifecycle: healthz, graceful drain, disconnect-cancels
# ---------------------------------------------------------------------------
def test_healthz_and_graceful_drain():
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4)
    try:
        assert _get(host, port, "/healthz") == (200, '{"status": "ok"}\n')
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        ref = _ref(engine, p, 16)
        result = {}

        def worker():
            result["r"] = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 16})

        t = threading.Thread(target=worker)
        t.start()
        _wait(lambda: gw._tracked, msg="request in flight")
        gw.begin_drain()
        # draining: ejected from rotation, new work refused with 503 ...
        status, body = _get(host, port, "/healthz")
        assert (status, json.loads(body)["status"]) == (503, "draining")
        status, headers, body = _post(host, port, {"prompt": [1, 2],
                                                   "max_tokens": 2})
        assert status == 503 and json.loads(body)["error"] == "draining"
        assert headers.get("Retry-After") == "1"
        # ... but the in-flight stream runs to completion, untruncated
        t.join(timeout=60)
        assert not t.is_alive()
        status, _, body = result["r"]
        toks, terminals = _parse_sse(body)
        assert status == 200 and terminals[0][0] == "end"
        np.testing.assert_array_equal(np.asarray(toks, np.int32), ref)
        _wait(lambda: gw.drained, msg="gateway drained")
    finally:
        srv.stop()
        gw.close()


def test_client_disconnect_cancels_request():
    """Dropping the SSE connection mid-stream cancels the request: its
    lane and pages free (session goes idle without finishing the token
    budget) and the stream outcome is recorded as cancelled."""
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, segment=1)
    try:
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        conn = http.client.HTTPConnection(host, port, timeout=30)
        conn.request("POST", "/v1/generate",
                     json.dumps({"prompt": p.tolist(), "max_tokens": 24}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        resp.fp.read(16)                  # first event is on the wire
        # hard drop. http.client detaches the socket into resp.fp for
        # Connection: close responses (conn.sock is already None), so
        # closing the response file IS closing the socket — with unread
        # data pending the kernel answers the server's next write with
        # RST, which the writer surfaces as ConnectionReset → cancel.
        resp.fp.close()
        _wait(lambda: not gw._tracked and gw.session.idle, timeout=30,
              msg="request cancelled after disconnect")
        st = gw.session.stats()
        assert st["active"] == 0 and st["pending"] == 0
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 5. body validation unit layer
# ---------------------------------------------------------------------------
def test_parse_generate_body():
    from repro.serve import SamplingParams
    prompt, params, request_id = parse_generate_body(
        {"prompt": [1, 2, 3], "max_tokens": 7, "temperature": 0.5,
         "seed": 9, "stop_token": 2, "deadline_ms": 100, "priority": 3,
         "tenant": "acme", "stream": True, "request_id": "cli-1"})
    np.testing.assert_array_equal(prompt, np.asarray([1, 2, 3], np.int32))
    assert params == SamplingParams(max_tokens=7, temperature=0.5, seed=9,
                                    stop_token=2, deadline_ms=100.0,
                                    priority=3, tenant="acme")
    assert request_id == "cli-1"
    # defaults pass through untouched; request_id stays optional
    _, params, request_id = parse_generate_body({"prompt": [4]})
    assert params == SamplingParams() and request_id is None
    for bad in ("x", {}, {"prompt": [0.5]}, {"prompt": [1], "nope": 2},
                {"prompt": [1], "request_id": 7},
                {"prompt": [1], "request_id": ""},
                {"prompt": [1], "request_id": "x" * 129}):
        with pytest.raises(ValueError):
            parse_generate_body(bad if isinstance(bad, dict) else bad)


# ---------------------------------------------------------------------------
# 6. HTTP/1.1 keep-alive: scrape endpoints reuse one connection
# ---------------------------------------------------------------------------
def _raw_request(sock, path, extra_headers=""):
    """One GET on an already-open socket; returns (status, headers, body).
    Reads exactly Content-Length body bytes so the socket stays usable."""
    import socket as _socket
    sock.sendall((f"GET {path} HTTP/1.1\r\nHost: t\r\n"
                  f"{extra_headers}\r\n").encode())
    buf = b""
    while b"\r\n\r\n" not in buf:
        chunk = sock.recv(4096)
        if not chunk:
            raise ConnectionError("server closed before response head")
        buf += chunk
    head, _, rest = buf.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    status = int(lines[0].split()[1])
    headers = {k.strip().lower(): v.strip() for k, v in
               (ln.split(":", 1) for ln in lines[1:])}
    clen = int(headers["content-length"])
    while len(rest) < clen:
        chunk = sock.recv(4096)
        if not chunk:
            raise ConnectionError("server closed mid-body")
        rest += chunk
    return status, headers, rest[:clen].decode()


def test_keepalive_reuses_one_connection():
    """A Prometheus scraper's pattern: many GETs down ONE HTTP/1.1
    connection. Every response must carry Connection: keep-alive and the
    socket must survive across requests; a request carrying
    ``Connection: close`` is honored with close + EOF."""
    import socket

    engine, _ = _engine()
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4)
    try:
        with socket.create_connection((host, port), timeout=30) as sock:
            for path in ("/healthz", "/metrics", "/healthz", "/metrics"):
                status, headers, body = _raw_request(sock, path)
                assert status == 200
                assert headers["connection"] == "keep-alive"
                assert body
            # Connection: close is honored: response then EOF
            status, headers, _ = _raw_request(
                sock, "/healthz", "Connection: close\r\n")
            assert status == 200
            assert headers["connection"] == "close"
            sock.settimeout(10)
            assert sock.recv(1) == b""      # server closed its side
    finally:
        srv.stop()
        gw.close()


def test_http10_connections_close():
    """Pre-1.1 clients get one response per connection (no implicit
    keep-alive), and SSE streams always close regardless of version."""
    import socket

    engine, _ = _engine()
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4)
    try:
        with socket.create_connection((host, port), timeout=30) as sock:
            sock.sendall(b"GET /healthz HTTP/1.0\r\nHost: t\r\n\r\n")
            buf = b""
            while True:
                chunk = sock.recv(4096)
                if not chunk:
                    break
                buf += chunk
            assert b" 200 " in buf.split(b"\r\n", 1)[0]
            assert b"connection: close" in buf.lower()
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 7. per-tenant metrics labels under a hard cardinality bound
# ---------------------------------------------------------------------------
def test_metrics_tenant_labels_bounded():
    """Three tenants through a ``max_tenants=2`` registry: the first two
    get their own ``tenant=`` label on the by-tenant series, the third
    aggregates under ``tenant="other"`` — and the unlabelled aggregate
    histogram still counts every request (existing dashboards keep
    working)."""
    from repro.gateway import GatewayMetrics

    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4,
                                metrics=GatewayMetrics(max_tenants=2))
    try:
        for tenant in ("acme", "globex", "initech"):
            p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
            status, _, _ = _post(host, port, {"prompt": p.tolist(),
                                              "max_tokens": 2,
                                              "tenant": tenant})
            assert status == 200
        _, text = _get(host, port, "/metrics")
        assert 'gateway_ttft_by_tenant_seconds_count{tenant="acme"} 1' in text
        assert ('gateway_ttft_by_tenant_seconds_count{tenant="globex"} 1'
                in text)
        assert ('gateway_ttft_by_tenant_seconds_count{tenant="other"} 1'
                in text)
        assert "initech" not in text        # bounded: never its own label
        assert "gateway_ttft_seconds_count 3" in text
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 8. client request_id: terminal echo, live-duplicate 409, reuse after drain
# ---------------------------------------------------------------------------
def test_request_id_echoed_in_terminal_payload():
    """A client-supplied ``request_id`` comes back verbatim in the SSE
    terminal payload (the idempotency receipt), and requests without one
    get no ``request_id`` key at all — absent, not null."""
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=2, page_size=4)
    try:
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        status, _, text = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 3,
                                             "request_id": "echo-1"})
        _, terminals = _parse_sse(text)
        assert status == 200 and terminals[0][0] == "end"
        assert terminals[0][1]["request_id"] == "echo-1"
        status, _, text = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 3})
        _, terminals = _parse_sse(text)
        assert status == 200 and "request_id" not in terminals[0][1]
        _, metrics = _get(host, port, "/metrics")
        assert "gateway_requests_with_id_total 1" in metrics
        assert "gateway_request_id_conflicts_total 0" in metrics
    finally:
        srv.stop()
        gw.close()


def test_duplicate_live_request_id_is_409_then_reusable():
    """While request_id ``dup-1`` is live, a second submission with the
    same id is refused with 409 naming the original rid — and once the
    original drains, the id is submittable again (duplicate detection
    covers LIVE requests only, per the idempotency-token contract)."""
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, segment=1)
    try:
        p = RNG.integers(0, cfg.vocab_size, (5,)).astype(np.int32)
        result = {}

        def original():
            result["r"] = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 24,
                                             "request_id": "dup-1"})

        t = threading.Thread(target=original)
        t.start()
        _wait(lambda: "dup-1" in gw._live_ids, msg="original live")
        rid = gw._live_ids["dup-1"]
        status, _, body = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 2,
                                             "request_id": "dup-1"})
        assert status == 409
        err = json.loads(body)
        assert err["error"] == "duplicate-request-id"
        assert err["request_id"] == "dup-1" and err["rid"] == rid
        # the original stream is untouched by the collision
        t.join(timeout=120)
        assert not t.is_alive()
        status, _, text = result["r"]
        toks, terminals = _parse_sse(text)
        assert status == 200 and terminals[0][0] == "end"
        assert terminals[0][1]["request_id"] == "dup-1"
        assert len(toks) == 24
        # terminal → the id is released and reusable
        _wait(lambda: "dup-1" not in gw._live_ids, msg="id released")
        status, _, text = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 2,
                                             "request_id": "dup-1"})
        _, terminals = _parse_sse(text)
        assert status == 200 and terminals[0][0] == "end"
        assert terminals[0][1]["request_id"] == "dup-1"
        _, metrics = _get(host, port, "/metrics")
        assert "gateway_requests_with_id_total 2" in metrics
        assert "gateway_request_id_conflicts_total 1" in metrics
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 9. Retry-After derived from live queue depth
# ---------------------------------------------------------------------------
def test_retry_after_reflects_live_queue_depth():
    """A queue-full shed against a backed-up gateway advertises a
    depth-scaled Retry-After — ceil((pending + active) / lanes) admission
    rounds — not the static floor of 1. Five in-flight requests on one
    lane → ``Retry-After: 5``."""
    from repro.serve import SamplingParams
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, segment=1,
                                max_pending=4)
    try:
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        handles = [gw.submit(p, SamplingParams(max_tokens=24))]
        _wait(lambda: gw.session.stats()["active"] == 1, msg="lane busy")
        for _ in range(4):                     # fill the pending queue
            handles.append(gw.submit(p, SamplingParams(max_tokens=24)))
        assert gw.session.stats()["pending"] == 4
        status, headers, body = _post(host, port, {"prompt": p.tolist(),
                                                   "max_tokens": 2})
        assert status == 429
        assert json.loads(body)["error"] == "queue-full"
        # depth 5 (1 active + 4 pending) over 1 lane → 5 rounds
        assert headers.get("Retry-After") == "5"
        for h in handles:                      # don't drain 120 tokens
            gw.cancel(h)
    finally:
        srv.stop()
        gw.close()


# ---------------------------------------------------------------------------
# 10. watchdog self-healing: stalled/crashed step driver
# ---------------------------------------------------------------------------
def test_watchdog_trips_on_stalled_step_driver():
    """Wedge the step driver mid-stream: the watchdog flips /healthz to
    503 degraded, the live SSE stream ends with exactly one typed
    ``watchdog`` error (request_id echoed, zero hung clients), new
    submissions are refused with 503 degraded, and the trip is counted
    in /metrics."""
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4, segment=1,
                                watchdog_timeout=0.25)
    try:
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        result = {}

        def stream():
            result["r"] = _post(host, port, {"prompt": p.tolist(),
                                             "max_tokens": 24,
                                             "request_id": "wd-1"},
                                timeout=60)

        t = threading.Thread(target=stream)
        t.start()
        _wait(lambda: "wd-1" in gw._live_ids, msg="request live")
        # every iteration now overruns the watchdog budget (but stays
        # interruptible per-iteration, so close() can still join)
        gw.session.step = lambda: time.sleep(0.5)
        _wait(lambda: gw.watchdog_tripped, msg="watchdog trip")
        assert "stalled" in gw.watchdog_reason
        status, body = _get(host, port, "/healthz")
        assert status == 503
        health = json.loads(body)
        assert health["status"] == "degraded"
        assert health["reason"] == "watchdog"
        # the live stream terminates with the typed watchdog error
        t.join(timeout=60)
        assert not t.is_alive(), "SSE stream hung after watchdog trip"
        status, _, text = result["r"]
        _, terminals = _parse_sse(text)
        assert status == 200 and len(terminals) == 1
        ev, payload = terminals[0]
        assert ev == "error" and payload["reason"] == "watchdog"
        assert payload["status"] == "failed"
        assert payload["request_id"] == "wd-1"
        # degraded gateways refuse new work — no Retry-After lie
        status, headers, body = _post(host, port, {"prompt": p.tolist(),
                                                   "max_tokens": 2})
        assert status == 503
        assert json.loads(body)["error"] == "degraded"
        assert "Retry-After" not in headers
        assert gw.cancel(None) is False        # cancels refuse too
        _, metrics = _get(host, port, "/metrics")
        assert "gateway_watchdog_trips_total 1" in metrics
    finally:
        srv.stop()
        gw.close()


def test_watchdog_trips_immediately_on_step_crash():
    """A crashed step loop doesn't wait out the heartbeat: the exception
    is recorded on the gateway and the trip happens from the driver's own
    except handler, flipping /healthz to degraded."""
    from repro.serve import SamplingParams
    engine, cfg = _engine()
    gw, srv, host, port = _boot(engine, lanes=1, page_size=4,
                                watchdog_timeout=30.0)
    try:
        def boom():
            raise RuntimeError("induced step crash")

        gw.session.step = boom
        # idle loops skip step(): submit to make the driver call it
        p = RNG.integers(0, cfg.vocab_size, (4,)).astype(np.int32)
        gw.submit(p, SamplingParams(max_tokens=2))
        _wait(lambda: gw.watchdog_tripped, msg="trip on crash")
        assert "induced step crash" in gw.watchdog_reason
        assert isinstance(gw._step_error, RuntimeError)
        status, body = _get(host, port, "/healthz")
        assert status == 503 and json.loads(body)["reason"] == "watchdog"
    finally:
        srv.stop()
        gw.close()
