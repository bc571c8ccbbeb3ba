"""Front-door tests: telemetry parity, HTTP end-to-end, and the soak test
pinning the long-running-server bugfix (bounded ``_results`` + uid reuse).

The HTTP tests drive the real ``ServeHTTPServer`` on an ephemeral port
with raw asyncio stream clients (the server speaks plain HTTP/1.1 with
``Connection: close``, so one read-to-EOF captures unary and SSE bodies
alike).
"""
import asyncio
import json

import jax
import numpy as np
import pytest

from repro.configs.base import DasConfig, LpsaConfig, ModelConfig, TernaryConfig
from repro.kernels import ops
from repro.models import model as MD
from repro.models.transformer import Runtime
from repro.serve import Request, ServeConfig, ServeEngine, Telemetry
from repro.serve.server import ServeHTTPServer

CFG = ModelConfig(
    name="tiny-http", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
    ternary=TernaryConfig(das=DasConfig(16, 8)),
    lpsa=LpsaConfig(sink=4, window=12, chunk=8),
    dtype="float32", remat=False, scan_layers=False,
)


@pytest.fixture(scope="module")
def sparams():
    params = MD.init_params(jax.random.PRNGKey(0), CFG)
    return MD.export_serving(params, CFG)


def _engine(sparams, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 64)
    return ServeEngine(CFG, sparams, Runtime(), config=ServeConfig(**kw))


def _req(uid, plen=4, gen=3, arrival=0, slo=None, rng=None):
    rng = rng or np.random.default_rng(uid)
    return Request(uid=uid,
                   prompt=np.asarray(rng.integers(0, CFG.vocab, plen),
                                     np.int32),
                   max_new_tokens=gen, arrival=arrival, slo_steps=slo)


# =========================================================================
# telemetry parity with EngineStats
# =========================================================================

def test_telemetry_matches_engine_stats(sparams, tmp_path):
    path = tmp_path / "metrics.jsonl"
    eng = _engine(sparams, scheduler="deadline")
    tele = Telemetry(engine=eng, jsonl_path=str(path), snapshot_every=4)
    for i in range(5):
        eng.submit(_req(i, slo=200 if i % 2 else None))
    res = eng.run()
    assert len(res) == 5

    assert tele.tokens_out == eng.stats.generated_tokens
    assert tele.requests_finished == 5
    assert tele.preemptions == eng.stats.preemptions == 0
    assert tele.slo_tracked == 2 and tele.slo_met == 2
    assert tele.queue_wait_steps == sum(r.queue_wait_steps
                                        for r in res.values())

    snap = tele.snapshot(eng)
    assert snap["totals"]["tokens_out"] == eng.stats.generated_tokens
    assert snap["slo_attainment"] == 1.0
    assert snap["engine"]["decode_steps"] == eng.stats.decode_steps
    assert snap["engine"]["kernel_fallbacks"] == eng.kernel_fallback_deltas()
    assert snap["pool"]["layout"] in ("paged", "dense")
    assert 0.0 < snap["rolling"]["slot_utilization"] <= 1.0
    # wall-clock gauges from the engine's submit / admit / first-token stamps
    roll = snap["rolling"]
    assert 0.0 <= roll["queue_wait_s_p50"] <= roll["queue_wait_s_p95"]
    assert 0.0 < roll["ttft_s_p50"] <= roll["ttft_s_p95"]
    assert roll["queue_wait_s_p95"] <= roll["ttft_s_p95"]
    assert not hasattr(tele, "prefill_tokens")    # the dead counter is gone

    tele.close()
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    reqs = [x for x in lines if x["type"] == "request"]
    ticks = [x for x in lines if x["type"] == "tick"]
    assert len(reqs) == 5
    assert ticks, "expected periodic tick snapshots"
    assert sum(x["new_tokens"] for x in reqs) == eng.stats.generated_tokens
    assert all(x["slo_met"] for x in reqs if x["slo_steps"] is not None)
    by_uid = {x["uid"]: x for x in reqs}
    for uid, r in res.items():
        assert by_uid[uid]["queue_wait_s"] == r.queue_wait_s >= 0.0
        assert by_uid[uid]["ttft_s"] == r.ttft_s > r.queue_wait_s


def test_request_results_carry_wall_clock_stamps(sparams):
    """submit <= admission <= first token <= retirement, on the engine's
    perf_counter; a request with a prompt tail gets its first token from a
    decode tick, one whose prompt is whole packs at admission."""
    import time
    eng = _engine(sparams)
    t0 = time.perf_counter()
    eng.submit(_req(0, plen=8))     # one whole pack (chunk 8)
    eng.submit(_req(1, plen=11))    # one pack and a 3-token tail
    res = eng.run()
    t1 = time.perf_counter()
    for r in res.values():
        assert t0 <= r.submit_time <= r.admit_time <= r.first_token_time <= t1
        assert r.queue_wait_s == r.admit_time - r.submit_time
        assert r.ttft_s == r.first_token_time - r.submit_time
    assert res[0].first_token_vtime == res[0].admit_vtime
    assert res[1].first_token_vtime > res[1].admit_vtime


def test_kernel_fallback_deltas_are_per_engine(sparams):
    """satellite bugfix: stats.kernel_fallbacks used to snapshot the
    process-wide counter, so an engine inherited every fallback any other
    engine (or test) had ever recorded."""
    ops.note_fallback("das_matmul", ("x",), "pre-existing noise")
    eng_a = _engine(sparams)
    ops.note_fallback("lpsa_attn", ("y",), "between constructions")
    eng_b = _engine(sparams)

    assert "lpsa_attn" in " ".join(eng_a.kernel_fallback_deltas())
    assert eng_b.kernel_fallback_deltas() == {}
    # reset_clock re-baselines: eng_a forgets the old noise too
    eng_a.reset_clock()
    assert eng_a.kernel_fallback_deltas() == {}


# =========================================================================
# pop_result / drain_results: bounded memory + uid reuse (satellite bugfix)
# =========================================================================

def test_pop_result_allows_uid_reuse(sparams):
    eng = _engine(sparams)
    eng.submit(_req(7))
    eng.run_forever()            # drain-and-return; results NOT drained
    assert 7 in eng._results

    with pytest.raises(ValueError, match="unclaimed result"):
        eng.submit(_req(7))      # old bug: permanent uid exhaustion

    first = eng.pop_result(7)
    assert first is not None and len(first.tokens) == 3
    assert eng.pop_result(7) is None          # single-claim
    assert eng._results == {}

    eng.submit(_req(7))                        # same uid, accepted again
    res = eng.run()
    assert res[7].admit_vtime > first.admit_vtime


def test_drain_results_empties_store(sparams):
    eng = _engine(sparams)
    for i in range(3):
        eng.submit(_req(i))
    eng.run_forever()
    out = eng.drain_results()
    assert sorted(out) == [0, 1, 2]
    assert eng.drain_results() == {}
    for i in range(3):
        eng.submit(_req(i))                    # all uids reusable


def test_soak_bounded_results_and_uid_cycling(sparams):
    """10k sequential requests through run_forever with incremental
    pop_result keep len(_results) bounded while uids cycle through a tiny
    space, and telemetry deltas match EngineStats — the long-running
    server can actually run long."""
    N, UIDS = 10_000, 16
    eng = _engine(sparams, max_slots=8)
    tele = Telemetry(engine=eng)
    rng = np.random.default_rng(0)
    state = {"submitted": 0, "inflight": set(), "finished": 0,
             "max_results": 0}

    def on_finish(result):
        claimed = eng.pop_result(result.uid)
        assert claimed is not None and claimed.uid == result.uid
        state["inflight"].discard(result.uid)
        state["finished"] += 1

    eng.on_finish = on_finish

    def poll():
        while state["submitted"] < N:
            uid = state["submitted"] % UIDS
            if uid in state["inflight"]:
                return
            eng.submit(Request(
                uid=uid,
                prompt=np.asarray(rng.integers(0, CFG.vocab,
                                               int(rng.integers(3, 6))),
                                  np.int32),
                max_new_tokens=2, arrival=eng.vtime))
            state["inflight"].add(uid)
            state["submitted"] += 1
            state["max_results"] = max(state["max_results"],
                                       len(eng._results))

    eng.run_forever(poll=poll)

    assert state["submitted"] == N
    assert state["finished"] == N
    assert eng._results == {}, "results leaked past pop_result"
    assert state["max_results"] <= UIDS
    # telemetry kept pace with the authoritative engine counters
    assert tele.requests_finished == N
    assert tele.tokens_out == eng.stats.generated_tokens == 2 * N


# =========================================================================
# HTTP end-to-end
# =========================================================================

async def _http(port, method, path, body=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    head = (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n")
    writer.write(head.encode() + payload)
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), timeout=60)
    writer.close()
    head_raw, _, body_raw = raw.partition(b"\r\n\r\n")
    lines = head_raw.decode("latin-1").split("\r\n")
    status = int(lines[0].split(" ")[1])
    headers = {}
    for ln in lines[1:]:
        if ":" in ln:
            k, v = ln.split(":", 1)
            headers[k.strip().lower()] = v.strip()
    return status, headers, body_raw


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, timeout=120))


def test_http_end_to_end(sparams):
    eng = _engine(sparams, scheduler="deadline")
    srv = ServeHTTPServer(eng, port=0, max_queue_depth=8,
                          default_slo_steps=100)

    async def scenario():
        await srv.start()
        p = srv.port
        assert p != 0

        # unary completion
        st, hdr, body = await _http(p, "POST", "/v1/completions",
                                    {"prompt": [1, 2, 3, 4],
                                     "max_tokens": 4})
        assert st == 200
        out = json.loads(body)
        assert out["object"] == "text_completion"
        assert len(out["choices"][0]["token_ids"]) == 4
        assert out["usage"]["prompt_tokens"] == 4
        assert out["usage"]["completion_tokens"] == 4
        assert out["usage"]["slo_met"] is True      # default_slo_steps

        # string prompt convenience (byte-tokenized)
        st, _, body = await _http(p, "POST", "/v1/completions",
                                  {"prompt": "hello", "max_tokens": 2})
        assert st == 200
        assert len(json.loads(body)["choices"][0]["token_ids"]) == 2

        # SSE streaming: one chunk per token, final usage chunk, [DONE]
        st, hdr, body = await _http(p, "POST", "/v1/completions",
                                    {"prompt": [5, 6, 7], "max_tokens": 3,
                                     "stream": True, "slo_steps": 200})
        assert st == 200
        assert hdr["content-type"] == "text/event-stream"
        events = [ln[len("data: "):] for ln in body.decode().split("\n\n")
                  if ln.startswith("data: ")]
        assert events[-1] == "[DONE]"
        chunks = [json.loads(e) for e in events[:-1]]
        assert all(c["object"] == "text_completion.chunk" for c in chunks)
        token_chunks = [c for c in chunks if c["choices"][0]["token_ids"]]
        assert len(token_chunks) == 3
        final = chunks[-1]
        assert final["choices"][0]["finish_reason"] == "stop"
        assert final["usage"]["completion_tokens"] == 3
        assert final["usage"]["slo_met"] is True

        # /metrics reflects the three finished requests
        st, _, body = await _http(p, "GET", "/metrics")
        assert st == 200
        snap = json.loads(body)
        assert snap["totals"]["requests_finished"] == 3
        assert snap["totals"]["tokens_out"] == 9
        assert snap["engine"]["active_slots"] == 0
        assert "pages_in_use" in snap["pool"]

        # /healthz
        st, _, body = await _http(p, "GET", "/healthz")
        assert st == 200 and json.loads(body)["ok"] is True

        # malformed inputs -> 400 with an error message
        for bad in ({"prompt": []}, {"prompt": ""}, {"prompt": 42},
                    {"prompt": [999999]}, {"prompt": [1], "max_tokens": -1},
                    {"prompt": [1], "max_tokens": "lots"}):
            st, _, body = await _http(p, "POST", "/v1/completions", bad)
            assert st == 400, bad
            assert "message" in json.loads(body)["error"]
        st, _, _ = await _http(p, "GET", "/nope")
        assert st == 404

        await srv.stop()
        assert not srv._thread.is_alive(), "engine thread not joined"
        # results were popped as they finished: nothing leaked
        assert eng._results == {}

    _run(scenario())


def test_http_backpressure_429(sparams):
    eng = _engine(sparams)
    srv = ServeHTTPServer(eng, port=0, max_queue_depth=0)  # always full

    async def scenario():
        await srv.start()
        st, hdr, body = await _http(srv.port, "POST", "/v1/completions",
                                    {"prompt": [1, 2], "max_tokens": 1})
        assert st == 429
        assert hdr.get("retry-after") == "1"
        assert "capacity" in json.loads(body)["error"]["message"]
        await srv.stop()

    _run(scenario())


def test_http_concurrent_streams(sparams):
    """several clients in flight at once: every stream completes and the
    engine batches them (telemetry sees overlapping slots)."""
    eng = _engine(sparams, max_slots=4, scheduler="deadline")
    srv = ServeHTTPServer(eng, port=0, max_queue_depth=16)

    async def one(i):
        st, _, body = await _http(srv.port, "POST", "/v1/completions",
                                  {"prompt": [i + 1, i + 2, i + 3],
                                   "max_tokens": 4, "stream": True})
        assert st == 200
        assert body.rstrip().endswith(b"data: [DONE]")

    async def scenario():
        await srv.start()
        await asyncio.gather(*(one(i) for i in range(6)))
        st, _, body = await _http(srv.port, "GET", "/metrics")
        assert json.loads(body)["totals"]["requests_finished"] == 6
        await srv.stop()
        assert eng._results == {}

    _run(scenario())
