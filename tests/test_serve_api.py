"""OpenAI API surface tests over a tiny CPU-mesh engine.

Exercises the model-server contract the reference router depends on
(docs/architecture/core/model-servers.md:38-100): completions (stream +
non-stream), chat, models, health, metrics scrape, render/tokenize.
"""

import json

import pytest
from aiohttp.test_utils import TestClient, TestServer

pytestmark = pytest.mark.anyio


@pytest.fixture
def anyio_backend():
    return "asyncio"

from llmd_tpu.config import CacheConfig, EngineConfig, SchedulerConfig, tiny_model_config
from llmd_tpu.engine import LLMEngine
from llmd_tpu.serve.api import build_app
from llmd_tpu.serve.async_engine import AsyncEngine
from llmd_tpu.serve.tokenizer import ByteTokenizer


def make_engine(**model_overrides) -> LLMEngine:
    cfg = EngineConfig(
        model=tiny_model_config(vocab_size=512, max_model_len=128, **model_overrides),
        cache=CacheConfig(page_size=4, num_blocks=128, dtype="float32"),
        scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64),
    )
    return LLMEngine(cfg)


@pytest.fixture
async def client():
    engine = make_engine()
    app = build_app(AsyncEngine(engine), ByteTokenizer(), "tiny", 128)
    c = TestClient(TestServer(app))
    await c.start_server()
    yield c
    await c.close()


async def test_health_and_models(client):
    r = await client.get("/health")
    assert r.status == 200
    r = await client.get("/v1/models")
    data = await r.json()
    assert data["data"][0]["id"] == "tiny"
    assert data["data"][0]["max_model_len"] == 128


async def test_completion_basic(client):
    r = await client.post(
        "/v1/completions",
        json={"prompt": "hello world", "max_tokens": 8, "temperature": 0.0},
    )
    assert r.status == 200
    data = await r.json()
    assert data["object"] == "text_completion"
    assert data["usage"]["completion_tokens"] >= 1
    assert data["choices"][0]["finish_reason"] in ("length", "stop")


async def test_completion_token_ids_prompt(client):
    r = await client.post(
        "/v1/completions",
        json={"prompt": [5, 6, 7, 8], "max_tokens": 4, "temperature": 0.0},
    )
    data = await r.json()
    assert r.status == 200, data
    assert data["usage"]["prompt_tokens"] == 4


async def test_completion_streaming(client):
    r = await client.post(
        "/v1/completions",
        json={"prompt": "abc", "max_tokens": 6, "temperature": 0.0, "stream": True},
    )
    assert r.status == 200
    assert r.headers["Content-Type"].startswith("text/event-stream")
    chunks = []
    async for line in r.content:
        line = line.decode().strip()
        if not line.startswith("data: "):
            continue
        payload = line[len("data: ") :]
        if payload == "[DONE]":
            break
        chunks.append(json.loads(payload))
    assert chunks, "no SSE chunks"
    assert chunks[-1]["choices"][0]["finish_reason"] in ("length", "stop")
    assert "usage" in chunks[-1]


async def test_chat_completion(client):
    r = await client.post(
        "/v1/chat/completions",
        json={
            "messages": [{"role": "user", "content": "hi"}],
            "max_tokens": 5,
            "temperature": 0.0,
        },
    )
    assert r.status == 200
    data = await r.json()
    assert data["object"] == "chat.completion"
    assert data["choices"][0]["message"]["role"] == "assistant"


async def test_metrics_scrape(client):
    await client.post(
        "/v1/completions", json={"prompt": "xy", "max_tokens": 3, "temperature": 0.0}
    )
    r = await client.get("/metrics")
    text = await r.text()
    assert "vllm:num_requests_waiting" in text
    assert "llmd:generation_tokens_total" in text
    from llmd_tpu.serve.metrics import parse_prometheus

    parsed = parse_prometheus(text)
    assert parsed["vllm:generation_tokens_total"] >= 3


async def test_render_endpoints(client):
    r = await client.post("/v1/completions/render", json={"prompt": "hello"})
    data = await r.json()
    ids = data["prompt_token_ids"]
    assert ids == ByteTokenizer().encode("hello")
    r = await client.post(
        "/v1/chat/completions/render",
        json={"messages": [{"role": "user", "content": "hello"}]},
    )
    data = await r.json()
    assert len(data["prompt_token_ids"]) > 5


async def test_validation_errors(client):
    r = await client.post("/v1/completions", json={"prompt": [], "max_tokens": 2})
    assert r.status == 400
    r = await client.post(
        "/v1/completions", json={"prompt": "x" * 500, "max_tokens": 2}
    )
    assert r.status == 400
    r = await client.post(
        "/v1/completions", json={"prompt": "ok", "n": 0, "max_tokens": 2}
    )
    assert r.status == 400


async def test_stop_token_ids(client):
    # Greedy decoding with every possible token as a stop => stops at 1 token.
    r = await client.post(
        "/v1/completions",
        json={
            "prompt": "hello",
            "max_tokens": 10,
            "temperature": 0.0,
            "stop_token_ids": list(range(512)),
        },
    )
    data = await r.json()
    assert data["choices"][0]["finish_reason"] == "stop"
    assert data["usage"]["completion_tokens"] == 1


def test_detokenizer_stop_holdback():
    from llmd_tpu.serve.api import Detokenizer

    tok = ByteTokenizer()
    # "ab" is the stop; feed "x", "a", "b" one token at a time.
    d = Detokenizer(tok, ["ab"])
    deltas = [d.feed(tok.encode("x", add_special_tokens=False))]
    deltas.append(d.feed(tok.encode("a", add_special_tokens=False)))
    assert "a" not in "".join(deltas), "stop-prefix leaked to the stream"
    deltas.append(d.feed(tok.encode("b", add_special_tokens=False)))
    assert d.stopped
    assert "".join(deltas) == "x"
    # Earliest occurrence across stops wins, not first-in-list.
    d2 = Detokenizer(tok, ["zzz", "c"])
    d2.feed(tok.encode("abczzz", add_special_tokens=False), final=True)
    assert d2.stopped and d2.emitted == "ab"
    # Holdback is flushed when generation finishes without a stop match.
    d3 = Detokenizer(tok, ["QQ"])
    out = d3.feed(tok.encode("hel", add_special_tokens=False))
    out += d3.feed(tok.encode("lo", add_special_tokens=False), final=True)
    assert out == "hello"


async def test_concurrent_requests(client):
    import asyncio

    async def one(i):
        r = await client.post(
            "/v1/completions",
            json={"prompt": f"prompt number {i}", "max_tokens": 4, "temperature": 0.0},
        )
        assert r.status == 200
        return await r.json()

    results = await asyncio.gather(*[one(i) for i in range(6)])
    assert all(r["usage"]["completion_tokens"] >= 1 for r in results)


async def test_embeddings_endpoint(client):
    import math

    # string input
    r = await client.post("/v1/embeddings", json={"model": "tiny", "input": "hello world"})
    assert r.status == 200, await r.text()
    d = await r.json()
    v1 = d["data"][0]["embedding"]
    assert d["object"] == "list" and d["data"][0]["index"] == 0
    tok = await client.post("/tokenize", json={"prompt": "hello world"})
    assert d["usage"]["prompt_tokens"] == (await tok.json())["count"]
    # unit norm
    assert abs(math.sqrt(sum(x * x for x in v1)) - 1.0) < 1e-4

    # deterministic + input-sensitive
    r = await client.post("/v1/embeddings", json={"input": "hello world"})
    assert (await r.json())["data"][0]["embedding"] == v1
    r = await client.post("/v1/embeddings", json={"input": "different text"})
    v2 = (await r.json())["data"][0]["embedding"]
    assert v2 != v1

    # batch of strings: rows match the single calls
    r = await client.post(
        "/v1/embeddings", json={"input": ["hello world", "different text"]}
    )
    d = await r.json()
    assert len(d["data"]) == 2
    import numpy as np

    np.testing.assert_allclose(d["data"][0]["embedding"], v1, atol=1e-5)
    np.testing.assert_allclose(d["data"][1]["embedding"], v2, atol=1e-5)

    # token-array input == its string equivalent (tokenize first: the
    # byte tokenizer may add special tokens)
    ids = (await (await client.post(
        "/tokenize", json={"prompt": "hello world"}
    )).json())["tokens"]
    r = await client.post("/v1/embeddings", json={"input": ids})
    np.testing.assert_allclose(
        (await r.json())["data"][0]["embedding"], v1, atol=1e-5
    )

    # validation
    r = await client.post("/v1/embeddings", json={"input": []})
    assert r.status == 400
    r = await client.post("/v1/embeddings", json={"input": {"bad": 1}})
    assert r.status == 400
    r = await client.post("/v1/embeddings", json={"input": "x" * 4096})
    assert r.status == 400  # over the embed length limit
    r = await client.post("/v1/embeddings", json=[1, 2])  # non-object body
    assert r.status == 400

    # batches larger than max_num_seqs slice internally (engine max is 8)
    r = await client.post(
        "/v1/embeddings", json={"input": [f"text {i}" for i in range(11)]}
    )
    assert r.status == 200, await r.text()
    d = await r.json()
    assert len(d["data"]) == 11
    r1 = await client.post("/v1/embeddings", json={"input": "text 9"})
    np.testing.assert_allclose(
        d["data"][9]["embedding"],
        (await r1.json())["data"][0]["embedding"], atol=1e-5,
    )


async def test_embeddings_model_validation_with_adapters():
    """Embeddings enforce the same model-id discipline as generation:
    adapter ids embed through their slot, unknown ids 404."""
    engine = make_engine(num_lora_adapters=1, lora_rank=4)
    app = build_app(
        AsyncEngine(engine), ByteTokenizer(), "tiny", 128,
        lora_adapters={"ad": 1},
    )
    c = TestClient(TestServer(app))
    await c.start_server()
    try:
        r = await c.post("/v1/embeddings", json={"model": "typo", "input": "x"})
        assert r.status == 404
        r = await c.post("/v1/embeddings", json={"model": "ad", "input": "x"})
        assert r.status == 200, await r.text()
    finally:
        await c.close()


async def test_grpc_embed_endpoint(client):
    ids = [ord(c) for c in "token surface"]
    r = await client.post("/vllm.Generation/Embed", json={"prompt_token_ids": ids})
    assert r.status == 200, await r.text()
    d = await r.json()
    assert len(d["embeddings"]) == 1
    # matches the OpenAI surface for the same tokens
    r2 = await client.post("/v1/embeddings", json={"input": ids})
    import numpy as np

    np.testing.assert_allclose(
        d["embeddings"][0], (await r2.json())["data"][0]["embedding"], atol=1e-5
    )


async def test_completion_n_choices(client):
    # n seeded samples: reproducible, indexed, usage sums choices
    r = await client.post("/v1/completions", json={
        "model": "tiny", "prompt": "hello", "max_tokens": 5,
        "n": 3, "temperature": 1.0, "seed": 42,
    })
    assert r.status == 200, await r.text()
    d = await r.json()
    assert [c["index"] for c in d["choices"]] == [0, 1, 2]
    # usage sums ALL choices: at least 1 token each, at most max_tokens
    assert 3 <= d["usage"]["completion_tokens"] <= 3 * 5
    texts = [c["text"] for c in d["choices"]]
    # seeded: same request reproduces the same choice set
    r2 = await client.post("/v1/completions", json={
        "model": "tiny", "prompt": "hello", "max_tokens": 5,
        "n": 3, "temperature": 1.0, "seed": 42,
    })
    assert [c["text"] for c in (await r2.json())["choices"]] == texts
    # seed+i derivation: choices differ from each other (overwhelmingly)
    assert len(set(texts)) > 1

    # greedy n: all choices identical (OpenAI semantics)
    r = await client.post("/v1/completions", json={
        "prompt": "hello", "max_tokens": 4, "n": 2, "temperature": 0.0,
    })
    d = await r.json()
    assert d["choices"][0]["text"] == d["choices"][1]["text"]

    # chat n
    r = await client.post("/v1/chat/completions", json={
        "messages": [{"role": "user", "content": "hi"}],
        "max_tokens": 4, "n": 2, "temperature": 1.0, "seed": 7,
    })
    assert r.status == 200, await r.text()
    d = await r.json()
    assert len(d["choices"]) == 2
    assert all("content" in c["message"] for c in d["choices"])

    # limits
    r = await client.post("/v1/completions", json={
        "prompt": "x", "n": 99,
    })
    assert r.status == 400
    # streaming with n>1 is now a supported surface (interleaved SSE,
    # covered by test_streaming_n_gt_1_interleaves_choices)
    r = await client.post("/v1/completions", json={
        "prompt": "x", "n": 2, "stream": True, "max_tokens": 2,
    })
    assert r.status == 200
    async for _ in r.content:
        pass


async def test_streaming_n_gt_1_interleaves_choices(client):
    """SSE with n>1 (reference capability the round-2 build rejected):
    every choice index streams deltas and a finish chunk; the final frame
    aggregates usage across choices."""
    r = await client.post(
        "/v1/completions",
        json={"prompt": "abc", "max_tokens": 5, "temperature": 0.8,
              "seed": 7, "n": 3, "stream": True},
    )
    assert r.status == 200
    chunks = []
    async for line in r.content:
        line = line.decode().strip()
        if not line.startswith("data: "):
            continue
        payload = line[len("data: "):]
        if payload == "[DONE]":
            break
        chunks.append(json.loads(payload))
    indices = {c["choices"][0]["index"] for c in chunks if c.get("choices")}
    assert indices == {0, 1, 2}
    finishes = [
        c["choices"][0] for c in chunks
        if c.get("choices") and c["choices"][0].get("finish_reason")
    ]
    assert len(finishes) == 3
    assert {f["index"] for f in finishes} == {0, 1, 2}
    assert chunks[-1]["usage"]["completion_tokens"] == 15


async def test_streaming_chat_n_gt_1(client):
    r = await client.post(
        "/v1/chat/completions",
        json={"messages": [{"role": "user", "content": "hi"}],
              "max_tokens": 3, "temperature": 0.9, "n": 2, "stream": True},
    )
    assert r.status == 200
    roles, finishes = set(), set()
    async for line in r.content:
        line = line.decode().strip()
        if not line.startswith("data: ") or line.endswith("[DONE]"):
            continue
        c = json.loads(line[len("data: "):])
        for ch in c.get("choices", []):
            if ch.get("delta", {}).get("role"):
                roles.add(ch["index"])
            if ch.get("finish_reason"):
                finishes.add(ch["index"])
    assert roles == {0, 1}
    assert finishes == {0, 1}


async def test_responses_create_retrieve_delete(client):
    r = await client.post(
        "/v1/responses",
        json={"model": "tiny", "input": "hello there",
              "max_output_tokens": 6, "temperature": 0.0},
    )
    assert r.status == 200
    data = await r.json()
    assert data["object"] == "response"
    assert data["status"] == "completed"
    assert data["output"][0]["content"][0]["type"] == "output_text"
    assert data["usage"]["output_tokens"] >= 1
    rid = data["id"]

    r = await client.get(f"/v1/responses/{rid}")
    assert r.status == 200
    assert (await r.json())["id"] == rid

    r = await client.delete(f"/v1/responses/{rid}")
    assert (await r.json())["deleted"] is True
    r = await client.get(f"/v1/responses/{rid}")
    assert r.status == 404


async def test_responses_previous_response_chaining(client):
    r = await client.post(
        "/v1/responses",
        json={"model": "tiny", "input": "first turn", "max_output_tokens": 4,
              "temperature": 0.0},
    )
    first = await r.json()
    r = await client.post(
        "/v1/responses",
        json={"model": "tiny", "input": "second turn",
              "previous_response_id": first["id"],
              "max_output_tokens": 4, "temperature": 0.0},
    )
    assert r.status == 200
    second = await r.json()
    # chained: the second request's input tokens include the first turn
    assert second["usage"]["input_tokens"] > first["usage"]["input_tokens"]
    # unknown previous id is a client error
    r = await client.post(
        "/v1/responses",
        json={"model": "tiny", "input": "x", "previous_response_id": "resp_nope"},
    )
    assert r.status == 404


async def test_responses_streaming_events(client):
    r = await client.post(
        "/v1/responses",
        json={"model": "tiny", "input": "stream me",
              "max_output_tokens": 5, "temperature": 0.0, "stream": True},
    )
    assert r.status == 200
    events = []
    cur_event = None
    async for line in r.content:
        line = line.decode().strip()
        if line.startswith("event: "):
            cur_event = line[len("event: "):]
        elif line.startswith("data: ") and cur_event:
            events.append((cur_event, json.loads(line[len("data: "):])))
    names = [e for e, _ in events]
    assert names[0] == "response.created"
    assert "response.output_text.delta" in names
    assert names[-1] == "response.completed"
    final = events[-1][1]["response"]
    assert final["status"] == "completed"
    assert final["output"][0]["content"][0]["text"]


async def test_conversations_flow(client):
    r = await client.post("/v1/conversations", json={"metadata": {"t": "1"}})
    conv = await r.json()
    assert conv["object"] == "conversation"
    cid = conv["id"]

    r = await client.post(
        f"/v1/conversations/{cid}/items",
        json={"items": [{"type": "message", "role": "user",
                         "content": "remember me"}]},
    )
    assert r.status == 200
    r = await client.get(f"/v1/conversations/{cid}/items")
    items = (await r.json())["data"]
    assert items[0]["content"] == "remember me"

    # a response in the conversation consumes + appends its turns
    r = await client.post(
        "/v1/responses",
        json={"model": "tiny", "input": "and this", "conversation": cid,
              "max_output_tokens": 4, "temperature": 0.0},
    )
    assert r.status == 200
    r = await client.get(f"/v1/conversations/{cid}/items")
    items = (await r.json())["data"]
    assert items[-1]["role"] == "assistant"
    # unknown conversation 404s
    r = await client.post(
        "/v1/responses", json={"model": "tiny", "input": "x",
                               "conversation": "conv_nope"},
    )
    assert r.status == 404


def test_deliver_is_atomic_against_same_id_reregistration():
    """Regression: _deliver (engine thread) must hold the lock across
    its get/pop of _subs. Unlocked, a loop-thread abort+resubmit of the
    same request id could interleave between the get and the pop, and
    the pop would silently drop the NEW stream's queue — the resubmitted
    request would hang forever. Surfaced by the CC001 guarded-by triage
    (static-analysis.md)."""
    import asyncio
    import threading

    from llmd_tpu.engine.request import RequestOutput

    class _StubEngine:
        stats = None

        def has_work(self):
            return False

    inst = AsyncEngine(_StubEngine())
    loop = asyncio.new_event_loop()
    try:
        inst._loop = loop
        rid = "req-1"
        inst.submit(rid, [1, 2, 3], None)

        windows = threading.Event()   # _deliver is inside its window
        resubmitted = threading.Event()

        class _RacingDict(dict):
            def get(self, k, default=None):
                out = dict.get(self, k, default)
                if k == rid and not windows.is_set():
                    windows.set()
                    # Give the racer the whole window between the get
                    # and the pop. With _deliver holding the lock the
                    # racer stays blocked and this times out; unlocked,
                    # the racer swaps in the new queue mid-window.
                    resubmitted.wait(0.3)
                return out

        with inst._lock:
            inst._subs = _RacingDict(inst._subs)

        def racer():
            windows.wait(2)
            inst.abort(rid)            # client disconnected...
            inst.submit(rid, [4], None)  # ...and retried with the same id
            resubmitted.set()

        t = threading.Thread(target=racer)
        t.start()
        final = RequestOutput(
            request_id=rid, new_token_ids=[7], finished=True,
            finish_reason="stop", num_prompt_tokens=3, num_output_tokens=1,
        )
        inst._deliver(rid, final)  # engine-thread side
        t.join(timeout=5)
        assert resubmitted.is_set()
        # The resubmitted stream's queue must have survived the pop.
        with inst._lock:
            assert rid in inst._subs
    finally:
        inst._fetch_pool.shutdown(wait=False, cancel_futures=True)
        loop.close()


# --------------------------------------------------------------------- #
# what the process runs on (llmd_tpu/jaxrt.py; serve/__main__.py wires it)


def test_serving_device_refuses_anything_but_a_tpu_unless_asked():
    """No silent CPU fallback: without --platform the server serves from a
    TPU or exits; --platform cpu is the explicit request (this suite's)."""
    from llmd_tpu import jaxrt

    info = jaxrt.serving_device("cpu")
    assert info["platform"] == "cpu" and info["count"] >= 1
    assert set(info) == {"platform", "kind", "count"}
    with pytest.raises(SystemExit, match="not a TPU.*--platform cpu"):
        jaxrt.serving_device(None)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: used as is, no directory set in code.
    Unset: one fixed path inside the checkout."""
    import jax

    from llmd_tpu import jaxrt

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(jaxrt.COMPILE_CACHE_ENV, "/somewhere/else")
        assert jaxrt.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(jaxrt.COMPILE_CACHE_ENV)
        fixed = jaxrt.enable_compile_cache()
        assert fixed == str(jaxrt.DEFAULT_COMPILE_CACHE_DIR)
        assert fixed == jaxrt.enable_compile_cache()  # no pid, no timestamp
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:  # tests stay without a cache
        jax.config.update("jax_compilation_cache_dir", before)


async def test_admin_status_reports_the_runtime():
    """/admin/status carries what the entry point says the process runs on
    (device, kernel plans, compile counters) beside the engine's state."""
    engine = make_engine()
    report = {
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "kernel_plans": {"flat_attention": ["xla:geometry"]},
    }
    app = build_app(
        AsyncEngine(engine), ByteTokenizer(), "tiny", 128,
        runtime_report=lambda: report,
    )
    async with TestClient(TestServer(app)) as c:
        status = await (await c.get("/admin/status")).json()
    assert status["device"] == report["device"]
    assert status["kernel_plans"] == report["kernel_plans"]
    assert status["paused"] is False and status["running"] == 0
