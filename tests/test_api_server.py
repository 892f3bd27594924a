"""OpenAI-compatible HTTP front door contracts (ISSUE 15).

The pinned semantics of ``paddle_tpu.inference.api_server``, one
scenario per test:

- **request-option mapping** — tenant defaulting, priority clamping
  into ``PRIORITY_RANGE``, millisecond deadlines -> engine seconds,
  body-beats-header precedence, and a structured 400 for anything
  malformed (never a stack trace over the wire);
- **SSE framing** — ``data: {json}`` frames, a terminal
  ``data: [DONE]``, OpenAI chunk schemas for both endpoints, and the
  trace id surfaced as a response header;
- **token fidelity** — the streamed greedy text reassembles to
  byte-identical output vs the SAME request pushed straight into an
  identically configured engine;
- **admission mapping** — ``Overloaded`` becomes HTTP 429 with a
  ``Retry-After`` header computed from the controller's
  ``retry_after_s``;
- **disconnect containment** — a client hanging up mid-stream
  cancels the backend request and the pages come back (the page
  audit is on suite-wide);
- **trace hops** — ``http_recv`` / ``first_byte`` / ``last_byte``
  stamped onto the request's cross-replica trace.

The fleet-backed chaos sweep lives in ``tests/test_api_chaos.py``.
"""

import json
import socket
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (AdmissionController, ApiServer,
                                  ContinuousBatchingEngine)
from paddle_tpu.inference.api_server import (ApiError, default_detokenize,
                                             default_tokenize,
                                             parse_request_options)
from paddle_tpu.inference.serving import PRIORITY_RANGE
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

pytestmark = pytest.mark.http_api

_MODEL = None
_REF_ENG = None
_REF_TOKENS = {}


def _model():
    global _MODEL
    if _MODEL is None:
        cfg = LlamaConfig.tiny()
        cfg.tensor_parallel = False
        cfg.scan_layers = False
        cfg.num_hidden_layers = 1
        paddle.seed(0)
        m = LlamaForCausalLM(cfg)
        m.eval()
        _MODEL = (m, cfg)
    return _MODEL


def _engine(**kw):
    m, _ = _model()
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 48)
    kw.setdefault("decode_chunk", 4)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("greedy", True)
    return ContinuousBatchingEngine(m, **kw)


def _reference(prompt, n_new, eos=None):
    """Uncontended greedy tokens for one request (one shared engine,
    compiled once for the whole module)."""
    global _REF_ENG
    key = (tuple(prompt), int(n_new), eos)
    if key not in _REF_TOKENS:
        if _REF_ENG is None:
            _REF_ENG = _engine()
        _REF_ENG.add_request(np.asarray(prompt, np.int32), n_new,
                             eos_token_id=eos)
        _REF_TOKENS[key] = [int(t) for t in _REF_ENG.run()[-1].tokens]
    return _REF_TOKENS[key]


def _post(url, body, headers=None, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, dict(r.headers), r.read()


@pytest.fixture(scope="module")
def server():
    srv = ApiServer(_engine(), model_id="tiny-test").start()
    yield srv
    srv.stop()


# ---- option mapping (pure) ------------------------------------------------


def test_options_tenant_defaulting():
    opts = parse_request_options({}, {})
    assert opts["tenant"] == "default"
    assert opts["priority"] == 0
    assert opts["ttft_deadline_s"] is None
    assert opts["deadline_s"] is None
    # non-string and empty tenants fall back, never crash
    assert parse_request_options({"tenant": 7}, {})["tenant"] == "default"
    assert parse_request_options({"tenant": ""}, {})["tenant"] == "default"
    assert parse_request_options(
        {}, {"x-tenant": "acme"})["tenant"] == "acme"


def test_options_priority_clamped_to_range():
    lo, hi = PRIORITY_RANGE
    assert parse_request_options(
        {"priority": hi + 90}, {})["priority"] == hi
    assert parse_request_options(
        {"priority": lo - 90}, {})["priority"] == lo
    # header parse + clamp; body beats header
    assert parse_request_options(
        {}, {"x-priority": str(hi + 1)})["priority"] == hi
    assert parse_request_options(
        {"priority": 2}, {"x-priority": "9"})["priority"] == 2


def test_options_deadlines_ms_to_seconds():
    opts = parse_request_options(
        {"ttft_deadline_ms": 1500, "deadline_ms": 30000}, {})
    assert opts["ttft_deadline_s"] == pytest.approx(1.5)
    assert opts["deadline_s"] == pytest.approx(30.0)
    opts = parse_request_options({}, {"x-deadline-ms": "250"})
    assert opts["deadline_s"] == pytest.approx(0.25)


@pytest.mark.parametrize("body", [
    {"deadline_ms": "soon"},
    {"deadline_ms": -5},
    {"deadline_ms": float("nan")},
    {"ttft_deadline_ms": 0},
    {"priority": "high"},
    {"priority": True},
])
def test_options_malformed_raise_400(body):
    with pytest.raises(ApiError) as ei:
        parse_request_options(body, {})
    assert ei.value.status == 400
    doc = ei.value.body()
    assert doc["error"]["type"] == "invalid_request_error"
    assert doc["error"]["code"] == 400


def test_default_codec_roundtrip():
    assert default_tokenize("5 6 7") == [5, 6, 7]
    assert default_detokenize([5, 6, 7]) == "5 6 7"
    with pytest.raises(ApiError):
        default_tokenize("not tokens")


# ---- HTTP surface ---------------------------------------------------------


def test_models_and_healthz(server):
    with urllib.request.urlopen(server.url + "/v1/models",
                                timeout=30) as r:
        doc = json.loads(r.read())
    assert doc["object"] == "list"
    assert doc["data"][0]["id"] == "tiny-test"
    with urllib.request.urlopen(server.url + "/healthz",
                                timeout=30) as r:
        assert r.status == 200


def test_unary_completion_matches_oracle(server):
    prompt, n_new = [5, 6, 7], 6
    status, headers, raw = _post(
        server.url + "/v1/completions",
        {"prompt": prompt, "max_tokens": n_new})
    assert status == 200
    doc = json.loads(raw)
    assert doc["object"] == "text_completion"
    choice = doc["choices"][0]
    assert choice["finish_reason"] == "length"
    assert choice["text"] == default_detokenize(_reference(prompt, n_new))
    assert doc["usage"] == {"prompt_tokens": 3, "completion_tokens": 6,
                            "total_tokens": 9}
    assert headers.get("X-Trace-Id")


def test_sse_framing_and_stream_fidelity(server):
    prompt, n_new = [9, 2, 4], 8
    status, headers, raw = _post(
        server.url + "/v1/completions",
        {"prompt": prompt, "max_tokens": n_new, "stream": True})
    assert status == 200
    assert headers["Content-Type"].startswith("text/event-stream")
    assert headers.get("X-Trace-Id")
    frames = raw.decode().split("\n\n")
    assert frames[-1] == ""              # body ends with a blank line
    frames = [f for f in frames if f]
    assert all(f.startswith("data: ") for f in frames)
    assert frames[-1] == "data: [DONE]"
    chunks = [json.loads(f[len("data: "):]) for f in frames[:-1]]
    assert all(c["object"] == "text_completion" for c in chunks)
    assert all(c["id"].startswith("cmpl-") for c in chunks)
    assert chunks[-1]["choices"][0]["finish_reason"] == "length"
    assert all(c["choices"][0]["finish_reason"] is None
               for c in chunks[:-1])
    text = "".join(c["choices"][0]["text"] for c in chunks)
    assert text == default_detokenize(_reference(prompt, n_new))


def test_eos_maps_to_stop(server):
    prompt = [5, 6, 7]
    oracle = _reference(prompt, 6)
    eos = oracle[2]                      # the 3rd greedy token
    status, _, raw = _post(
        server.url + "/v1/completions",
        {"prompt": prompt, "max_tokens": 6, "eos_token_id": eos})
    assert status == 200
    doc = json.loads(raw)
    assert doc["choices"][0]["finish_reason"] == "stop"
    assert doc["choices"][0]["text"] == \
        default_detokenize(_reference(prompt, 6, eos=eos))


def test_chat_completions_both_modes(server):
    body = {"messages": [{"role": "system", "content": "1 2"},
                         {"role": "user", "content": "3 4"}],
            "max_tokens": 4}
    status, _, raw = _post(server.url + "/v1/chat/completions", body)
    assert status == 200
    doc = json.loads(raw)
    assert doc["object"] == "chat.completion"
    msg = doc["choices"][0]["message"]
    assert msg["role"] == "assistant"
    # the chat prompt is the concatenated message contents
    assert msg["content"] == default_detokenize(
        _reference([1, 2, 3, 4], 4))

    status, _, raw = _post(server.url + "/v1/chat/completions",
                           {**body, "stream": True})
    frames = [f for f in raw.decode().split("\n\n") if f]
    chunks = [json.loads(f[len("data: "):]) for f in frames[:-1]]
    assert chunks[0]["object"] == "chat.completion.chunk"
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    text = "".join(c["choices"][0]["delta"].get("content", "")
                   for c in chunks)
    assert text == default_detokenize(_reference([1, 2, 3, 4], 4))


def test_tenant_priority_reach_the_engine(server):
    status, headers, _ = _post(
        server.url + "/v1/completions",
        {"prompt": [3, 1], "max_tokens": 2, "priority": 999},
        headers={"X-Tenant": "acme"})
    assert status == 200
    rid = int(headers["X-Trace-Id"])
    req = server._backend.live(rid)
    assert req.tenant == "acme"
    assert req.priority == PRIORITY_RANGE[1]


def test_trace_hops_stamped(server):
    status, headers, _ = _post(
        server.url + "/v1/completions",
        {"prompt": [8, 8], "max_tokens": 2, "stream": True})
    assert status == 200
    req = server._backend.live(int(headers["X-Trace-Id"]))
    # last_byte lands just AFTER the final write reaches the client:
    # give the handler coroutine a beat
    deadline = time.time() + 10
    while (not any(h["kind"] == "last_byte" for h in req.hops)
           and time.time() < deadline):
        time.sleep(0.005)
    kinds = [h["kind"] for h in req.hops]
    assert "http_recv" in kinds
    assert "first_byte" in kinds
    assert "last_byte" in kinds
    assert kinds.index("http_recv") < kinds.index("first_byte") \
        <= kinds.index("last_byte")


def test_statusz_sections(server):
    with urllib.request.urlopen(server.url + "/statusz",
                                timeout=30) as r:
        doc = json.loads(r.read())
    assert doc["http"]["pump_alive"] is True
    assert doc["http"]["requests"] >= 1
    assert "/v1/completions" in doc["routes"]


# ---- structured errors ----------------------------------------------------


def _expect_http_error(url, body=None, headers=None, method=None):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json", **(headers or {})})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    return ei.value.code, json.loads(ei.value.read())


def test_malformed_json_is_400(server):
    req = urllib.request.Request(
        server.url + "/v1/completions", data=b"{nope",
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=30)
    assert ei.value.code == 400
    assert json.loads(ei.value.read())["error"]["type"] == \
        "invalid_request_error"


def test_malformed_deadline_is_400(server):
    code, doc = _expect_http_error(
        server.url + "/v1/completions",
        {"prompt": [1], "max_tokens": 2, "deadline_ms": "soon"})
    assert code == 400
    assert doc["error"]["type"] == "invalid_request_error"


def test_unknown_route_and_method(server):
    code, doc = _expect_http_error(server.url + "/v1/nope",
                                   {"x": 1})
    assert code == 404
    code, doc = _expect_http_error(server.url + "/v1/completions",
                                   method="GET")
    assert code == 405


def test_overloaded_maps_to_429_with_retry_after():
    eng = _engine()
    ctl = AdmissionController(eng, max_queue=0, min_retry_after_s=2.0)
    srv = ApiServer(ctl).start()
    try:
        code, doc = _expect_http_error(
            srv.url + "/v1/completions",
            {"prompt": [1, 2], "max_tokens": 2})
        assert code == 429
        assert doc["error"]["type"] == "overloaded"
        assert doc["error"]["retry_after_s"] >= 2.0
        # the header is the ceil of the controller's computed value
        req = urllib.request.Request(
            srv.url + "/v1/completions",
            data=json.dumps({"prompt": [1], "max_tokens": 1}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(req, timeout=30)
            raise AssertionError("expected 429")
        except urllib.error.HTTPError as e:
            assert int(e.headers["Retry-After"]) >= 2
    finally:
        srv.stop()


def test_disconnect_mid_stream_cancels_and_reclaims():
    # a LONG generation (far more than the disconnect-detection
    # latency) so the cancel must be what ends it, not completion
    eng = _engine(max_len=512)
    srv = ApiServer(eng).start()
    try:
        body = json.dumps({"prompt": [4, 4, 4], "max_tokens": 480,
                           "stream": True}).encode()
        with socket.create_connection((srv.host, srv.port),
                                      timeout=30) as sk:
            sk.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                       b"Host: t\r\nContent-Type: application/json\r\n"
                       + f"Content-Length: {len(body)}\r\n\r\n".encode()
                       + body)
            sk.recv(1)          # first byte of the stream is flowing
        # client is gone: the server must notice and cancel. Poll for
        # the disconnect COUNTER, not has_work() — right after the
        # close the pump may not have admitted the request yet (and
        # has_work() can read False transiently mid-step from another
        # thread), so it is not a quiesce signal.
        deadline = time.time() + 60
        while time.time() < deadline:
            m = srv.metrics.get("http/disconnects")
            if m is not None and m.value >= 1:
                break
            time.sleep(0.01)
        assert srv.metrics.get("http/disconnects").value == 1
        with srv._lock:
            assert not srv._streams
    finally:
        srv.stop()      # joins the pump: the engine is ours again
    # drain the cancelled request single-threaded — the suite-wide
    # page audit trips at drain on any leaked page
    while eng.has_work():
        eng.step()
    # the engine still serves cleanly afterwards
    eng.add_request(np.asarray([1, 2], np.int32), 2)
    assert len(eng.run()[-1].tokens) == 2


def test_stream_chunk_knob_preserves_content():
    """stream_chunk_tokens batches mid-stream flushes but never
    changes WHAT is delivered (and the final flush is immediate)."""
    eng = _engine()
    srv = ApiServer(eng, stream_chunk_tokens=64).start()
    try:
        prompt, n_new = [9, 2, 4], 8
        status, _, raw = _post(
            srv.url + "/v1/completions",
            {"prompt": prompt, "max_tokens": n_new, "stream": True})
        assert status == 200
        frames = [f for f in raw.decode().split("\n\n") if f]
        assert frames[-1] == "data: [DONE]"
        chunks = [json.loads(f[len("data: "):]) for f in frames[:-1]]
        text = "".join(c["choices"][0]["text"] for c in chunks)
        assert text == default_detokenize(_reference(prompt, n_new))
    finally:
        srv.stop()
