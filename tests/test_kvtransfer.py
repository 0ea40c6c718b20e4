"""KV transfer layer tests: shipper protocol, leases, and P/D end-to-end.

The P/D invariance test is the core guarantee: a decode engine that pulls
prefill KV from a producer must emit exactly the tokens an aggregated
engine would (cache-seeded remote KV may never change numerics), while
actually hitting the transferred pages.
"""

import threading
import time

import numpy as np
import pytest

from llmd_tpu.config import (
    CacheConfig,
    EngineConfig,
    ParallelConfig,
    SchedulerConfig,
    tiny_model_config,
)
from llmd_tpu.engine import LLMEngine, SamplingParams
from llmd_tpu.kvtransfer import shipper as shipper_mod
from llmd_tpu.kvtransfer.connector import TPUConnector, pack_pages, unpack_pages
from llmd_tpu.kvtransfer.shipper import PullError, ShipperServer


# --------------------------------------------------------------------------- #
# shipper protocol


@pytest.fixture(params=["native", "python"])
def server(request, monkeypatch):
    if request.param == "python":
        from llmd_tpu.kvtransfer import native

        monkeypatch.setattr(native, "load", lambda: None)
    srv = ShipperServer(port=0)
    if request.param == "native" and srv.backend != "native":
        pytest.skip("native kvship unavailable")
    yield srv
    srv.close()


def test_register_pull_free(server):
    data = b"kv-bytes-" * 1000
    server.register("req-1", data, lease_ms=60_000)
    assert server.registered_count == 1
    assert server.registered_bytes == len(data)

    got = shipper_mod.pull("127.0.0.1", server.port, "req-1")
    assert got == data
    # pull is one-sided: entry survives until free-notify
    assert server.registered_count == 1
    assert shipper_mod.free_notify("127.0.0.1", server.port, "req-1")
    assert server.registered_count == 0
    with pytest.raises(PullError):
        shipper_mod.pull("127.0.0.1", server.port, "req-1")


def test_lease_expiry_and_renew(server):
    server.register("short", b"x" * 64, lease_ms=700)
    server.register("renewed", b"y" * 64, lease_ms=700)
    # Consumer heartbeat extends the lease (operations-vllm.md:155-160).
    assert shipper_mod.renew("127.0.0.1", server.port, "renewed", lease_ms=60_000)
    # Reaper cadence is 500ms; give "short" time to expire.
    time.sleep(1.5)
    with pytest.raises(PullError):
        shipper_mod.pull("127.0.0.1", server.port, "short")
    assert server.expired_count >= 1
    assert shipper_mod.pull("127.0.0.1", server.port, "renewed") == b"y" * 64


def test_stat(server):
    server.register("a", b"1234", lease_ms=60_000)
    n, b = shipper_mod.stat("127.0.0.1", server.port)
    assert (n, b) == (1, 4)


def test_python_client_native_server_interop():
    srv = ShipperServer(port=0)
    if srv.backend != "native":
        pytest.skip("native kvship unavailable")
    try:
        srv.register("k", b"payload", lease_ms=60_000)
        st, payload = shipper_mod._py_roundtrip(
            "127.0.0.1", srv.port, shipper_mod.OP_PULL, "k"
        )
        assert st == shipper_mod.ST_OK and payload == b"payload"
    finally:
        srv.close()


def test_pack_unpack_roundtrip():
    pages = np.random.default_rng(0).normal(size=(2, 3, 2, 4, 16)).astype(np.float32)
    out = unpack_pages(pack_pages(pages))
    np.testing.assert_array_equal(out, pages)


# --------------------------------------------------------------------------- #
# P/D end-to-end through two engines


def make_engine(kv_role=None, seed=0, page=4, num_blocks=64, dtype="float32"):
    cfg = EngineConfig(
        model=tiny_model_config(dtype=dtype),
        cache=CacheConfig(page_size=page, num_blocks=num_blocks, dtype=dtype),
        scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64),
        parallel=ParallelConfig(tensor_parallel_size=1),
        seed=seed,
        kv_role=kv_role,
        kv_transfer_port=0,  # ephemeral
        # This module tests the WIRE protocol (both engines share the
        # pytest process); the in-process device fast path is covered by
        # tests/test_pd_e2e.py::test_pd_local_fastpath*.
        kv_local_fastpath=False,
    )
    return LLMEngine(cfg)


PROMPT = [1, 5, 9, 13, 2, 8, 4, 4, 4, 4, 6, 6, 6, 6, 11, 7, 3, 2]  # 18 toks


def _run(eng, prompt, max_tokens, kv_transfer_params=None):
    rid = eng.add_request(
        list(prompt),
        SamplingParams(temperature=0.0, max_tokens=max_tokens),
        kv_transfer_params=kv_transfer_params,
    )
    outs = []
    final = None
    while eng.has_work():
        for out in eng.step():
            if out.request_id == rid:
                outs.extend(out.new_token_ids)
                if out.finished:
                    final = out
    return outs, final


def test_pd_disagg_matches_aggregated():
    ref_tokens, _ = _run(make_engine(), PROMPT, max_tokens=8)

    producer = make_engine(kv_role="kv_producer")
    consumer = make_engine(kv_role="kv_consumer")
    try:
        # Phase 1: prefill with max_tokens=1 + do_remote_decode (the routing
        # sidecar's prefill request, reference disaggregation/README.md:33-46).
        _, pre = _run(
            producer, PROMPT, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        assert params is not None
        assert params["num_full_pages"] == len(PROMPT) // 4
        # Export staging runs on a background thread (the response leaves
        # after prefill compute); wait for every (layer-group, chunk)
        # cell's registration to land (v3 group framing: transfer_keys
        # is the single source of the key scheme).
        from llmd_tpu.kvtransfer.connector import transfer_keys

        n_cells = len(transfer_keys(params))
        deadline = time.time() + 5
        while time.time() < deadline:
            if producer.kv_connector.server.registered_count == n_cells:
                break
            time.sleep(0.02)
        assert producer.kv_connector.server.registered_count == n_cells

        # Phase 2: decode with the captured params injected.
        toks, final = _run(consumer, PROMPT, max_tokens=8, kv_transfer_params=params)
        assert toks == ref_tokens
        # (18-1)//4 = 4 pages come from the transfer; free-notify reclaimed
        # the producer entry.
        assert final.num_cached_tokens == 16
        assert consumer.kv_connector.imported_requests == 1
        assert producer.kv_connector.server.registered_count == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_disagg_bfloat16_cache_transfers():
    """bf16 (the production cache dtype) must export/pull byte-exact:
    ml_dtypes arrays lack the buffer protocol, so the shipper moves a
    uint8 view and the bundle header carries the dtype by name."""
    ref_tokens, _ = _run(make_engine(dtype="bfloat16"), PROMPT, max_tokens=6)
    producer = make_engine(kv_role="kv_producer", dtype="bfloat16")
    consumer = make_engine(kv_role="kv_consumer", dtype="bfloat16")
    try:
        _, pre = _run(
            producer, PROMPT, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        assert pre.kv_transfer_params is not None
        assert producer.kv_connector.exported_requests == 1
        toks, final = _run(
            consumer, PROMPT, max_tokens=6,
            kv_transfer_params=pre.kv_transfer_params,
        )
        assert toks == ref_tokens
        assert consumer.kv_connector.imported_requests == 1
        assert consumer.kv_connector.import_failures == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_multi_chunk_pipeline_matches_aggregated():
    """A prompt spanning several transfer chunks (the pipelined export
    path: background staging, per-chunk keys, device-side scatters) must
    reproduce the aggregated engine exactly, including the padded tail
    chunk."""
    prompt = list(range(1, 45))  # 44 tokens, page=4 -> 11 full pages
    ref_tokens, _ = _run(make_engine(), prompt, max_tokens=6)

    producer = make_engine(kv_role="kv_producer")
    consumer = make_engine(kv_role="kv_consumer")
    try:
        _, pre = _run(
            producer, prompt, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        assert params["num_full_pages"] == 11
        assert params["num_chunks"] == 2  # 11 pages / 8 per chunk
        assert params["chunk_pages"] == 8
        toks, final = _run(
            consumer, prompt, max_tokens=6, kv_transfer_params=params
        )
        assert toks == ref_tokens
        assert consumer.kv_connector.imported_requests == 1
        assert consumer.kv_connector.import_failures == 0
        # free-notify covered every chunk key
        deadline = time.time() + 5
        while time.time() < deadline:
            if producer.kv_connector.server.registered_count == 0:
                break
            time.sleep(0.02)
        assert producer.kv_connector.server.registered_count == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pull_wait_blocks_until_registered(server):
    """pull_wait absorbs producer staging lag: the key appears mid-wait."""
    import threading

    from llmd_tpu.kvtransfer import shipper as shipper_mod

    def late_register():
        time.sleep(0.15)
        server.register("late", b"chunk-bytes", 5_000)

    threading.Thread(target=late_register, daemon=True).start()
    t0 = time.monotonic()
    blob = shipper_mod.pull_wait(
        "127.0.0.1", server.port, "late", deadline=time.monotonic() + 5
    )
    assert blob == b"chunk-bytes"
    assert time.monotonic() - t0 >= 0.1
    # hard timeout on a key that never appears
    with pytest.raises(shipper_mod.PullError):
        shipper_mod.pull_wait(
            "127.0.0.1", server.port, "never", deadline=time.monotonic() + 0.2
        )


def test_producer_crash_mid_pull_recompute():
    """Producer dies BETWEEN chunk pulls (crash-mid-transfer seam): the
    consumer's load-failure policy degrades to local recompute and the
    output still matches the aggregated engine."""
    prompt = list(range(1, 45))  # 11 full pages -> 2 chunks
    ref_tokens, _ = _run(make_engine(), prompt, max_tokens=5)
    producer = make_engine(kv_role="kv_producer")
    consumer = make_engine(kv_role="kv_consumer")
    try:
        _, pre = _run(
            producer, prompt, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        assert params["num_chunks"] == 2
        # let staging finish, then crash the producer after the consumer's
        # FIRST chunk pull
        deadline = time.time() + 5
        while time.time() < deadline and (
            producer.kv_connector.server.registered_count < 2
        ):
            time.sleep(0.02)
        orig_pull_wait = shipper_mod.pull_wait
        calls = {"n": 0}

        def crashing_pull_wait(host, port, key, deadline, poll_s=0.01):
            blob = orig_pull_wait(host, port, key, deadline, poll_s)
            calls["n"] += 1
            if calls["n"] == 1:
                producer.kv_connector.server.close()  # crash mid-transfer
            return blob

        shipper_mod.pull_wait = crashing_pull_wait
        try:
            toks, _ = _run(
                consumer, prompt, max_tokens=5, kv_transfer_params=params
            )
        finally:
            shipper_mod.pull_wait = orig_pull_wait
        assert toks == ref_tokens  # recomputed locally, numerics intact
        assert consumer.kv_connector.import_failures == 1
        assert consumer.kv_connector.imported_requests == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_producer_crash_fail_policy_raises():
    """Same seam with kv_load_failure_policy='fail' (the reference's
    recommended strict mode, operations-vllm.md:118-139): the import
    surfaces KVLoadError instead of silently recomputing."""
    from llmd_tpu.kvtransfer.connector import KVLoadError

    producer = make_engine(kv_role="kv_producer")
    consumer = make_engine(kv_role="kv_consumer")
    consumer.kv_connector.cfg.load_failure_policy = "fail"
    consumer.kv_connector.cfg.lease_ms = 500  # short pull-wait deadline
    try:
        _, pre = _run(
            producer, PROMPT, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        producer.kv_connector.server.close()  # crash before any pull
        with pytest.raises(KVLoadError):
            consumer.kv_connector.import_for_prompt(list(PROMPT), params)
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_lease_expiry_reclaims_export_and_consumer_recomputes():
    """An export whose lease expires (decode never arrived / heartbeat
    died) is reaped; a late consumer degrades to recompute with exact
    numerics."""
    ref_tokens, _ = _run(make_engine(), PROMPT, max_tokens=4)
    producer = make_engine(kv_role="kv_producer")
    producer.kv_connector.cfg.lease_ms = 200
    consumer = make_engine(kv_role="kv_consumer")
    consumer.kv_connector.cfg.lease_ms = 500  # short pull-wait deadline
    try:
        _, pre = _run(
            producer, PROMPT, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        deadline = time.time() + 5
        while time.time() < deadline and (
            producer.kv_connector.server.registered_count == 0
        ):
            time.sleep(0.02)
        # expire: the reaper reclaims the entry
        deadline = time.time() + 5
        while time.time() < deadline and (
            producer.kv_connector.server.registered_count > 0
        ):
            time.sleep(0.05)
        assert producer.kv_connector.server.registered_count == 0
        assert producer.kv_connector.server.expired_count >= 1
        toks, _ = _run(
            consumer, PROMPT, max_tokens=4,
            kv_transfer_params=pre.kv_transfer_params,
        )
        assert toks == ref_tokens
        assert consumer.kv_connector.import_failures == 1
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_lease_renewal_keeps_chunked_export_alive():
    """The sidecar-heartbeat seam at the wire level: renewing EVERY chunk
    key (transfer_keys) holds a queued transfer past several base leases;
    the pull then still succeeds."""
    from llmd_tpu.kvtransfer.connector import transfer_keys

    producer = make_engine(kv_role="kv_producer")
    producer.kv_connector.cfg.lease_ms = 300
    consumer = make_engine(kv_role="kv_consumer")
    try:
        prompt = list(range(1, 45))  # 2 chunks
        _, pre = _run(
            producer, prompt, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        host, port = params["remote_host"], int(params["remote_port"])
        # v3 group framing: one shipper entry per (layer-group, chunk)
        # cell — transfer_keys is the single source of the key scheme.
        n_cells = len(transfer_keys(params))
        deadline = time.time() + 5
        while time.time() < deadline and (
            producer.kv_connector.server.registered_count < n_cells
        ):
            time.sleep(0.02)
        # hold for 4 base leases, renewing at ~1/3 lease cadence; EVERY
        # chunk key must be renewed each cycle (a short-circuiting any()
        # over a generator would let later chunks expire — the sidecar
        # heartbeat bug class)
        for _ in range(12):
            time.sleep(0.1)
            renewed = [
                shipper_mod.renew(host, port, k, lease_ms=300)
                for k in transfer_keys(params)
            ]
            assert all(renewed), renewed
        assert producer.kv_connector.server.registered_count == n_cells
        # The pull adopts pages through JAX: on a loaded host (tier-1 runs
        # six workers) it can outlast one 300 ms lease, so the last renewal
        # before it is a long one. What is under test is the hold above.
        for k in transfer_keys(params):
            assert shipper_mod.renew(host, port, k, lease_ms=10_000)
        n = consumer.kv_connector.import_for_prompt(prompt, params)
        assert n == 11  # every transferred page adopted
        assert consumer.kv_connector.import_failures == 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_consumer_recompute_fallback():
    consumer = make_engine(kv_role="kv_consumer")
    try:
        # Bogus remote: pull fails, policy=recompute => local prefill.
        toks, final = _run(
            consumer, PROMPT, max_tokens=4,
            kv_transfer_params={
                "remote_host": "127.0.0.1", "remote_port": 1,
                "remote_key": "nope", "num_full_pages": 4, "page_size": 4,
            },
        )
        assert len(toks) == 4
        assert consumer.kv_connector.import_failures == 1
    finally:
        consumer.kv_connector.close()


def test_q8_wire_roundtrip():
    """int8q wire form: header carries 'int8q:<orig>', scales ride the
    header blob, payload decodes to the exact quantized values."""
    from llmd_tpu.kvtransfer.connector import (
        pack_header_q8, unpack_pages_any,
    )

    rng = np.random.default_rng(3)
    pages = rng.standard_normal((2, 3, 2, 4, 8)).astype(np.float32)
    halves = pages.reshape(2, 3, 2, 4, 2, 4)
    amax = np.abs(halves).max(axis=-1, keepdims=True)
    scale = np.maximum(amax, 1e-30) / 127.0
    q8 = np.clip(np.round(halves / scale), -127, 127).astype(np.int8)
    q8 = q8.reshape(2, 3, 2, 4, 8)
    scales = scale[..., 0].astype(np.float16)  # [..., 2] K/V half scales
    blob = pack_header_q8(q8, "float32") + scales.tobytes() + q8.tobytes()
    kind, got_q8, got_scales, orig = unpack_pages_any(blob)
    assert kind == "q8" and orig == "float32"
    np.testing.assert_array_equal(got_q8, q8)
    np.testing.assert_array_equal(got_scales, scales)
    # exact form still decodes through the same entry point
    from llmd_tpu.kvtransfer.connector import pack_pages

    kind, got = unpack_pages_any(pack_pages(pages))
    assert kind == "exact"
    np.testing.assert_array_equal(got, pages)


def test_pd_int8_transfer_end_to_end():
    """kv_transfer_dtype='int8': the transfer moves half the bytes and the
    consumer's imported pages match the producer's within the per-row
    quantization error; generation completes via the cache-seeded path."""
    from llmd_tpu.config import EngineConfig

    prompt = list(range(1, 45))  # 11 full pages -> 2 chunks

    def mk(role, dtype_):
        cfg = EngineConfig(
            model=tiny_model_config(dtype="float32"),
            cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64),
            kv_role=role,
            kv_transfer_port=0,
            kv_transfer_dtype=dtype_,
            kv_local_fastpath=False,
        )
        return LLMEngine(cfg)

    producer = mk("kv_producer", "int8")
    consumer = mk("kv_consumer", "auto")  # producer-driven encoding
    try:
        _, pre = _run(
            producer, prompt, max_tokens=1,
            kv_transfer_params={"do_remote_decode": True},
        )
        params = pre.kv_transfer_params
        toks, final = _run(
            consumer, prompt, max_tokens=5, kv_transfer_params=params
        )
        assert len(toks) == 5
        assert consumer.kv_connector.imported_requests == 1
        assert consumer.kv_connector.import_failures == 0
        # 10 of 11 transferred pages hit (the last page keeps >= 1 token
        # to compute for the first logits)
        assert final.num_cached_tokens == 40
        # wire bytes well under half the exact f32 encoding (int8 payload
        # + f16 row scales vs 4-byte elements)
        cfgm = tiny_model_config()
        rows = cfgm.num_layers * 16 * cfgm.num_kv_heads * 4  # 2 chunks x 8 pages
        exact = rows * 2 * cfgm.head_dim * 4
        assert consumer.kv_connector.imported_bytes < exact * 0.6
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_int8_transfer_page_accuracy():
    """Direct accuracy check: export with int8 encoding, fetch the bundle,
    and compare the dequantized pages to the producer's exact pages."""
    producer = make_engine(kv_role="kv_producer")
    producer.kv_connector.cfg.transfer_dtype = "int8"
    # Monolithic v2 wire: this test inspects the fetched bundle's HOST
    # view directly, which a group-streamed fetch never materializes
    # (cells scatter straight into pool pages). Grouped int8 accuracy
    # is covered by the streamed-parity tests in test_kv_stream.py.
    producer.kv_connector.cfg.stream_groups = 1
    consumer = make_engine(kv_role="kv_consumer")
    try:
        prompt = list(range(1, 30))  # 7 full pages
        rid = producer.add_request(
            list(prompt),
            SamplingParams(temperature=0.0, max_tokens=1),
            kv_transfer_params={"do_remote_decode": True},
        )
        final = None
        block_ids = None
        orig_hook = producer.scheduler.finish_hook

        def capture_hook(req):
            nonlocal block_ids
            block_ids = list(req.block_ids)
            orig_hook(req)

        producer.scheduler.finish_hook = capture_hook
        while producer.has_work():
            for out in producer.step():
                if out.finished:
                    final = out
        params = final.kv_transfer_params
        exact = producer.kv_connector.runner.gather_pages(block_ids[:7])
        bundle = consumer.kv_connector.fetch_remote(list(prompt), params)
        got = bundle.host_pages(7)
        rel = np.linalg.norm(
            got.astype(np.float32) - exact.astype(np.float32)
        ) / np.linalg.norm(exact.astype(np.float32))
        assert rel < 0.01, rel
        # each K/V half must be accurate INDEPENDENTLY (separate scales:
        # a large K half must not crush the V half's resolution)
        D = exact.shape[-1] // 2
        for half in (slice(0, D), slice(D, None)):
            e = exact[..., half].astype(np.float32)
            g = got[..., half].astype(np.float32)
            rel_h = np.linalg.norm(g - e) / max(np.linalg.norm(e), 1e-9)
            assert rel_h < 0.01, rel_h
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()


def test_pd_int8_transfer_rejects_mla():
    """MLA latent rows don't fit the K|V half-split scale layout: int8
    transfer must refuse at startup, not silently degrade accuracy."""
    from llmd_tpu.config import EngineConfig

    with pytest.raises(ValueError, match="MLA"):
        LLMEngine(EngineConfig(
            model=tiny_model_config(
                kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16,
            ),
            cache=CacheConfig(page_size=4, num_blocks=32, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=2, max_num_batched_tokens=32),
            kv_role="kv_producer",
            kv_transfer_port=0,
            kv_transfer_dtype="int8",
        ))


def test_adaptive_encoding_decision_logic():
    """transfer_dtype='adaptive': the picker alternates while cold,
    converges to the measured-faster encoding, and re-probes the loser
    periodically so a drifting link can flip the choice."""
    conn = TPUConnector.__new__(TPUConnector)
    conn._local_lock = threading.Lock()  # pick/observe run under it
    conn._enc_rate = {"exact": None, "q8": None}
    conn._adaptive_exports = 0

    # Cold: alternates so both forms get measured.
    picks = [conn._adaptive_pick_q8() for _ in range(4)]
    assert True in picks and False in picks

    # Link where the exact form stages faster per ORIGINAL byte
    # (q8's quantize overhead dominates the byte saving).
    conn._observe_encoding(False, 100 << 20, 1.0)  # exact: 100 MB/s
    conn._observe_encoding(True, 100 << 20, 2.0)   # q8:     50 MB/s
    conn._adaptive_exports = 0
    picks = [conn._adaptive_pick_q8() for _ in range(7)]
    assert picks.count(False) == 7  # exact wins every non-probe turn
    assert conn._adaptive_pick_q8() is True  # 8th = re-probe the loser

    # Slow link: halved bytes dominate -> q8 flips to winner. EWMA must
    # actually move on repeated observations.
    for _ in range(12):
        conn._observe_encoding(False, 10 << 20, 4.0)  # exact: 2.5 MB/s
        conn._observe_encoding(True, 10 << 20, 1.0)   # q8:   10 MB/s
    conn._adaptive_exports = 0
    assert all(conn._adaptive_pick_q8() for _ in range(7))


def test_pd_adaptive_transfer_end_to_end():
    """transfer_dtype='adaptive' serves transfers correctly from the
    first (cold, alternating) exports on, and learns per-encoding
    staging rates as it goes."""
    from llmd_tpu.config import EngineConfig

    prompt = list(range(1, 45))

    def mk(role, dtype_):
        cfg = EngineConfig(
            model=tiny_model_config(dtype="float32"),
            cache=CacheConfig(page_size=4, num_blocks=64, dtype="float32"),
            scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=64),
            kv_role=role,
            kv_transfer_port=0,
            kv_transfer_dtype=dtype_,
            kv_local_fastpath=False,
        )
        return LLMEngine(cfg)

    producer = mk("kv_producer", "adaptive")
    consumer = mk("kv_consumer", "auto")
    try:
        for i in range(3):  # both encodings get exercised while cold
            p = [t + i for t in prompt]
            _, pre = _run(
                producer, p, max_tokens=1,
                kv_transfer_params={"do_remote_decode": True},
            )
            toks, final = _run(
                consumer, p, max_tokens=4,
                kv_transfer_params=pre.kv_transfer_params,
            )
            assert len(toks) == 4
        assert consumer.kv_connector.imported_requests == 3
        assert consumer.kv_connector.import_failures == 0
        st = producer.kv_connector.stats()
        assert st["enc_rate_exact_mbps"] > 0
        assert st["enc_rate_q8_mbps"] > 0
    finally:
        producer.kv_connector.close()
        consumer.kv_connector.close()
