#!/usr/bin/env python3
"""The quickest proof that llmd-tpu still starts on the chip.

    python3 chip_smoke.py              one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4    the paths that exist only across chips
    python3 chip_smoke.py --rehearse   control flow only: tiny model, CPU,
                                       exits 3 and prints no result line

Default, one chip. The serving path end to end through the entry points a
user calls (README quick start): ``python -m llmd_tpu.serve --model
llama-3.2-3b`` — bf16, all 28 layers, every published width, seeded random
weights, warm-up on — behind ``python -m llmd_tpu.epp``. A few requests go
through the router (sequential, concurrent so a step mixes prefill chunks
with decode rows, one streamed, one sampled, one prompt longer than a step's
token budget, one prompt repeated), and the answers are checked. After the
server has exited a second process checks each main-path kernel on the chip
against the XLA reference in ``llmd_tpu/ops`` at the same widths.

One process uses the chip at a time: this parent never imports JAX, and
every child is stopped before the next one needs the device. Anything that
fails raises; nothing is caught and carried on from. Lines before the last
are one JSON object each (what was measured); the last line is the result.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import http.client
import json
import os
import pathlib
import random
import signal
import statistics
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
OUT = HERE / "chiprun_out" / "chip_smoke"
HOST = "127.0.0.1"
SERVE_PORT, ROUTER_PORT, DP_PORT_BASE, DP_HEALTH_PORT = 8200, 8800, 8300, 8308
DEADLINE_S = 1150  # the contract is 1200 s, compilation included

# What is served. "real" is the smoke; "rehearse" keeps every code path of
# this script and shrinks the model so it runs on a CPU in a minute.
PROFILES = {
    "real": dict(
        model="llama-3.2-3b",
        serve_args=["--max-model-len", "8192", "--max-num-seqs", "64",
                    "--max-num-batched-tokens", "2048"],
        chunk_tokens=2048, long_prompt=2300, prompts=(120, 330, 700),
        vocab=128256,
        # kernel phase: llama-3.2-3b widths (q heads, kv heads, head dim),
        # the serving table (96 flat rows x 512 pages), a two-layer pool.
        kernels=dict(L=2, pages=2048, H=24, K=8, D=128, rows=96,
                     max_pages=512, batch=64, ctx=1000,
                     mla=dict(L=2, H=16, Dl=640, rank=512),
                     gmm=dict(tokens=1536, hidden=2048, ffn=1408, experts=64),
                     # learned sparse attention at Keye-VL-2.0-30B-A3B's widths:
                     # a 16 x 64 indexer, top-2,048 of 8k-24k paged tokens
                     sparse=dict(J=16, Di=64, topk=2048, H=32, K=4, pages=3200,
                                 max_pages=1536, contexts=(8192, 24000, 16000), chunk=40)),
        tp_blocks=512,
    ),
    "rehearse": dict(
        model="tiny",
        serve_args=["--platform", "cpu", "--max-model-len", "1024",
                    "--max-num-seqs", "8", "--max-num-batched-tokens", "128"],
        chunk_tokens=128, long_prompt=200, prompts=(40, 70, 100),
        vocab=256,
        kernels=dict(L=2, pages=64, H=4, K=2, D=128, rows=8, max_pages=16,
                     batch=4, ctx=100,
                     mla=dict(L=2, H=4, Dl=256, rank=128),
                     gmm=dict(tokens=64, hidden=128, ffn=128, experts=4),
                     sparse=dict(J=2, Di=64, topk=64, H=4, K=2, pages=64,
                                 max_pages=32, contexts=(100, 300, 200), chunk=20)),
        tp_blocks=64,
    ),
}

# Attention is compared, not bit-matched: both sides take bf16 q/k/v and
# accumulate in f32, but the kernel folds 256-token blocks into a running
# softmax where the reference normalises once and rounds its probabilities
# to bf16 before the value product, and both round the result to bf16
# (2**-8 relative each). Queries are scaled so scores have a spread near 2
# and outputs are O(value spread) (rms ~0.5 for N(0,1) values), not averages
# that shrink to nothing: a relative 2e-2 covers the two roundings, the
# absolute term covers elements near zero. An int8 pool holds values up to
# 12.7 (rms ~3 out); its reference dequantises every key and value to bf16
# first (2**-9 of 12.7 each) where the kernel keeps them exact, so its
# absolute term is wider. A wrong page, scale or mask is off by the rms.
ATTN_RTOL = 2e-2
ATTN_ATOL = {"bfloat16": 2e-2, "int8": 1e-1}
# The grouped GEMM contracts 2048 bf16 products per output in f32 on both
# sides; the kernel's result is rounded to bf16, the reference kept in f32.
GMM_ATOL, GMM_RTOL = 2e-2, 2e-2
# tp=4 against tp=1: the same bf16 weights, but every row-parallel matmul is
# four partial sums added by an all-reduce instead of one dot, through 28
# layers of bf16 activations. Logits of the seeded model have a spread near
# 1, so a first-token log-probability may move by a few hundredths; 0.15
# would be a different model.
TP_LOGPROB_ATOL = 0.15


class SmokeFailure(Exception):
    pass


def emit(**line) -> None:
    print(json.dumps(line), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


_T0 = time.monotonic()


def remaining() -> float:
    left = DEADLINE_S - (time.monotonic() - _T0)
    check(left > 0, f"over the {DEADLINE_S} s this script allows itself")
    return left


# --------------------------------------------------------------------- #
# children


@contextlib.contextmanager
def child(name: str, cmd: list[str], env: dict[str, str] | None = None):
    """Run ``cmd`` in its own process group with its output in a log under
    chiprun_out/, and stop the whole group on the way out, whatever
    happened."""
    OUT.mkdir(parents=True, exist_ok=True)
    log_path = OUT / f"{name}.log"
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            cmd, cwd=HERE, stdout=log, stderr=subprocess.STDOUT,
            env={**os.environ, **(env or {})}, start_new_session=True,
        )
        try:
            yield proc
        except BaseException:
            _show_tail(name, log_path)
            raise
        finally:
            _stop(proc)


def _group_alive(pgid: int) -> bool:
    """Whether any process of the group still runs (zombies do not count:
    nothing may be there to reap a grandchild)."""
    for stat in pathlib.Path("/proc").glob("[0-9]*/stat"):
        try:
            fields = stat.read_text().rsplit(")", 1)[1].split()
        except (FileNotFoundError, ProcessLookupError):  # gone while listing
            continue
        if int(fields[2]) == pgid and fields[0] != "Z":
            return True
    return False


def _stop(proc: subprocess.Popen) -> None:
    """SIGTERM the child's whole process group and wait until every member
    is gone — a rank that is still shutting down still holds its chip —
    then SIGKILL whatever 30 s did not persuade."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, sig)
        deadline = time.monotonic() + 30
        while _group_alive(proc.pid) and time.monotonic() < deadline:
            proc.poll()  # reap the leader
            time.sleep(0.2)
        if not _group_alive(proc.pid):
            break
    proc.wait(timeout=30)


def _show_tail(name: str, log_path: pathlib.Path, n: int = 6000) -> None:
    data = log_path.read_bytes()[-n:].decode("utf-8", "replace")
    print(f"--- last of {name}.log ---\n{data}\n--- end ---", file=sys.stderr)


def run_phase(name: str, args: list[str], env: dict[str, str] | None = None) -> dict:
    """Run one phase of this script in a process of its own (it needs the
    chip, and this parent must not hold it), relay its JSON lines, and
    return the last one. A phase that fails exits non-zero and fails this."""
    cmd = [sys.executable, str(HERE / "chip_smoke.py"), "--phase", name, *args]
    with child(f"phase-{name}", cmd, env) as proc:
        try:
            rc = proc.wait(timeout=remaining())
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"phase {name} ran out of time") from None
        log_path = OUT / f"phase-{name}.log"
        lines = []
        for raw in log_path.read_text().splitlines():
            if raw.startswith("{"):
                print(raw, flush=True)
                lines.append(json.loads(raw))
        check(rc == 0, f"phase {name} exited {rc}")
        check(bool(lines), f"phase {name} printed nothing")
        return lines[-1]


# --------------------------------------------------------------------- #
# HTTP, from the standard library (the parent stays light and off JAX)


def http_json(method: str, port: int, path: str, body=None, timeout=600.0):
    conn = http.client.HTTPConnection(HOST, port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body)
        headers = {"content-type": "application/json"} if body is not None else {}
        conn.request(method, path, body=payload, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
    finally:
        conn.close()
    ctype = resp.getheader("content-type", "")
    parsed = json.loads(data) if "json" in ctype else data.decode()
    return resp.status, parsed


def up(port: int, path: str) -> bool:
    """One readiness probe: refused connections and 5xx mean "not yet"."""
    try:
        status, _ = http_json("GET", port, path, timeout=5)
    except (ConnectionError, TimeoutError, http.client.HTTPException):
        return False
    return status == 200


def wait_until(cond, what: str, procs: list[subprocess.Popen], limit: float = 900.0):
    t0 = time.monotonic()
    while not cond():
        for p in procs:
            check(p.poll() is None, f"a process exited ({p.returncode}) while waiting for {what}")
        check(time.monotonic() - t0 < limit and remaining() > 0, f"timed out waiting for {what}")
        time.sleep(1.0)
    return time.monotonic() - t0


def metric(text: str, name: str) -> float:
    """The value of one un-suffixed sample ``name{...} value``."""
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            return float(line.rsplit(" ", 1)[1])
    raise SmokeFailure(f"/metrics has no {name}")


def stream_completion(port: int, body: dict, first_token) -> dict:
    """POST a streamed completion; call ``first_token()`` when the first
    content frame arrives; return {"frames", "usage", "finish_reason"}."""
    conn = http.client.HTTPConnection(HOST, port, timeout=600)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"content-type": "application/json"})
        resp = conn.getresponse()
        check(resp.status == 200, f"streamed request answered {resp.status}")
        frames, usage, finish, done = 0, None, None, False
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data:"):
                continue
            data = line[5:].strip()
            if data == "[DONE]":
                done = True
                break
            frame = json.loads(data)
            check("error" not in frame, f"stream carried an error: {frame}")
            frames += 1
            if frames == 1:
                first_token()
            usage = frame.get("usage", usage)
            for choice in frame.get("choices", ()):
                finish = choice.get("finish_reason") or finish
    finally:
        conn.close()
    check(done, "stream ended without [DONE]")
    return {"frames": frames, "usage": usage, "finish_reason": finish}


# --------------------------------------------------------------------- #
# the serve phase (parent: drives children over HTTP)


def make_prompt(rng: random.Random, n_tokens: int) -> str:
    """Seeded ASCII text that the byte tokenizer turns into exactly
    ``n_tokens`` ids (one per byte, plus BOS)."""
    words = ("route", "cache", "page", "token", "prefill", "decode", "mesh",
             "shard", "kernel", "tile", "queue", "batch", "stream", "chip")
    text = ""
    while len(text) < n_tokens:
        text += rng.choice(words) + " "
    return text[: n_tokens - 1]


def completion(prompt: str, max_tokens: int, model: str, **extra) -> dict:
    body = {"model": model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "ignore_eos": True, **extra}
    t0 = time.monotonic()
    status, out = http_json("POST", ROUTER_PORT, "/v1/completions", body)
    check(status == 200, f"/v1/completions answered {status}: {out}")
    usage = out["usage"]
    check(usage["completion_tokens"] == max_tokens,
          f"asked for {max_tokens} tokens, got {usage['completion_tokens']}")
    check(usage["prompt_tokens"] == len(prompt) + 1,
          f"prompt of {len(prompt) + 1} tokens counted as {usage['prompt_tokens']}")
    return {"latency_s": round(time.monotonic() - t0, 3),
            "prompt_tokens": usage["prompt_tokens"], "max_tokens": max_tokens,
            "cached_tokens": usage.get("prompt_tokens_details", {}).get("cached_tokens", 0),
            "text": out["choices"][0]["text"]}


def token_generate(ids: list[int], max_tokens: int, model: str, vocab: int) -> list[int]:
    """The token-in/token-out surface, through the router: the only one
    that returns token ids, which is what "identical" is checked on (a
    random-weight model's text through a byte tokenizer is mostly empty)."""
    body = {"model": model, "prompt_token_ids": ids,
            "sampling_params": {"max_tokens": max_tokens, "temperature": 0.0,
                                "ignore_eos": True}}
    status, out = http_json("POST", ROUTER_PORT, "/vllm.Generation/Generate", body)
    check(status == 200, f"/vllm.Generation/Generate answered {status}: {out}")
    toks = out["token_ids"]
    check(len(toks) == max_tokens, f"asked for {max_tokens} token ids, got {len(toks)}")
    check(all(isinstance(t, int) and 0 <= t < vocab for t in toks),
          f"token ids outside the vocabulary: {toks}")
    return toks


def start_router(endpoints: list[int]):
    OUT.mkdir(parents=True, exist_ok=True)
    path = OUT / "endpoints.json"
    path.write_text(json.dumps({"endpoints": [
        {"address": f"{HOST}:{p}", "labels": {"llm-d.ai/engine-type": "llmd"}}
        for p in endpoints
    ]}))
    return child("epp", [sys.executable, "-m", "llmd_tpu.epp", "--endpoints-file",
                         str(path), "--host", HOST, "--port", str(ROUTER_PORT)])


def router_sees(n: int) -> bool:
    try:
        status, out = http_json("GET", ROUTER_PORT, "/endpoints", timeout=5)
    except (ConnectionError, TimeoutError, http.client.HTTPException):
        return False
    return status == 200 and sum(e["healthy"] for e in out["endpoints"]) == n


def phase_serve(profile: dict, seed: int, rehearse: bool) -> dict:
    model = profile["model"]
    rng = random.Random(seed)
    serve = [sys.executable, "-m", "llmd_tpu.serve", "--model", model,
             "--host", HOST, "--port", str(SERVE_PORT), "--seed", str(seed),
             *profile["serve_args"]]
    t_start = time.monotonic()
    with child("serve", serve) as server, start_router([SERVE_PORT]) as router:
        procs = [server, router]
        ready_s = wait_until(lambda: up(SERVE_PORT, "/ready"), "the server's /ready", procs)
        wait_until(lambda: router_sees(1), "the router to see the server healthy", procs, 120)
        status, st = http_json("GET", SERVE_PORT, "/admin/status")
        check(status == 200, f"/admin/status answered {status}")
        device = st["device"]
        emit(phase="serve", event="ready", model=model, device=device,
             seconds_to_ready=round(ready_s, 1),
             model_load_s=st["startup"]["model_load_s"],
             warmup_programs=st["startup"]["warmup_programs"],
             warmup_s=st["startup"]["warmup_s"],
             compile_at_ready=st["startup"]["compile"],
             compile_cache_dir=st["compile_cache_dir"],
             visible_chips=st["visible_chips"], device_files=st["device_files"])
        check(st["startup"]["warmup_programs"] > 0, "the server skipped warm-up")
        check(st["pallas_mode"] == "auto", f"server ran with LLMD_PALLAS={st['pallas_mode']}")
        if not rehearse:
            check(device["platform"] == "tpu", f"the server is on {device}, not a TPU")

        requests = []
        # 1-2. A prompt that crosses a page (16 tokens), then the same again:
        # the second must hit the prefix cache and say the same thing.
        p_short = make_prompt(rng, 40)
        first = completion(p_short, 16, model)
        again = completion(p_short, 16, model)
        check(again["cached_tokens"] >= 16, f"the repeated prompt hit {again['cached_tokens']} cached tokens")
        check(again["text"] == first["text"], "the repeated greedy prompt answered differently")
        requests += [dict(first, name="short"), dict(again, name="short-repeated")]
        # 3. The same, on token ids.
        ids = [1] + [3 + b for b in make_prompt(rng, 50).encode()]
        toks_a = token_generate(ids, 12, model, profile["vocab"])
        toks_b = token_generate(ids, 12, model, profile["vocab"])
        check(toks_a == toks_b, f"greedy token ids differ on repeat: {toks_a} vs {toks_b}")
        # 4. A prompt longer than one step's token budget: chunked prefill.
        check(profile["long_prompt"] > profile["chunk_tokens"], "long prompt must cross a chunk")
        requests.append(dict(completion(make_prompt(rng, profile["long_prompt"]), 8, model), name="long"))
        # 5. One streamed request decoding while three more arrive together,
        # one of them sampled: steps that mix prefill chunks and decode rows.
        lens = profile["prompts"]
        pool = concurrent.futures.ThreadPoolExecutor(max_workers=4)
        with pool:
            started = concurrent.futures.Future()
            t0 = time.monotonic()
            streamed = pool.submit(
                stream_completion, ROUTER_PORT,
                {"model": model, "prompt": make_prompt(rng, 200), "max_tokens": 48,
                 "temperature": 0.0, "ignore_eos": True, "stream": True},
                lambda: started.set_result(time.monotonic() - t0),
            )
            done, _ = concurrent.futures.wait([started, streamed], timeout=remaining(),
                                              return_when=concurrent.futures.FIRST_COMPLETED)
            check(started in done, f"the stream ended before a first token: {streamed.result()}")
            others = [
                pool.submit(completion, make_prompt(rng, lens[0]), 24, model),
                pool.submit(completion, make_prompt(rng, lens[1]), 16, model,
                            temperature=0.8, top_p=0.9, seed=seed + 1),
                pool.submit(completion, make_prompt(rng, lens[2]), 8, model),
            ]
            s = streamed.result(timeout=remaining())
            check(s["usage"] is not None and s["usage"]["completion_tokens"] == 48,
                  f"streamed request: {s}")
            check(s["frames"] >= 2, f"streamed request came in {s['frames']} frame(s)")
            requests.append({"name": "streamed", "latency_s": round(time.monotonic() - t0, 3),
                             "first_token_s": round(started.result(), 3), "frames": s["frames"],
                             "prompt_tokens": s["usage"]["prompt_tokens"], "max_tokens": 48})
            for name, fut in zip(("concurrent", "concurrent-sampled", "concurrent-late"), others):
                requests.append(dict(fut.result(timeout=remaining()), name=name))
        for r in requests:
            r.pop("text", None)
            emit(phase="serve", event="request", **r)

        # What the server says about itself afterwards.
        check(up(SERVE_PORT, "/ready"), "/ready went away")
        status, metrics = http_json("GET", SERVE_PORT, "/metrics")
        check(status == 200, f"/metrics answered {status}")
        asked = sum(r["max_tokens"] for r in requests) + 2 * 12
        generated = metric(metrics, "vllm:generation_tokens_total")
        hit_rate = metric(metrics, "vllm:prefix_cache_hit_rate")
        check(generated == asked, f"/metrics counts {generated} generated tokens, {asked} were asked for")
        check(hit_rate > 0, "/metrics shows no prefix-cache hit")
        status, st = http_json("GET", SERVE_PORT, "/admin/status")
        check(status == 200, f"/admin/status answered {status}")
        plans = st["kernel_plans"]
        during = st["compile"]["programs"] - st["startup"]["compile"]["programs"]
        emit(phase="serve", event="after", generated_tokens=generated,
             prefix_cache_hit_rate=hit_rate, kernel_plans=plans,
             compilations_during_requests=during, compile_total=st["compile"],
             compile_cache_hits=st["compile"]["cache_hits"],
             peak_bytes_in_use=st["peak_bytes_in_use"],
             serve_phase_s=round(time.monotonic() - t_start, 1))
        platform_fallback = {op: p for op, p in plans.items() if "xla:platform" in p}
        check(not platform_fallback, f"ops fell back to XLA for the platform: {platform_fallback}")
        if not rehearse:
            for op in ("flat_attention", "flat_kv_write"):
                check(plans.get(op) == ["pallas"], f"{op} took {plans.get(op)}, not the Pallas kernel")
    return device


# --------------------------------------------------------------------- #
# the kernel phase (own process, on the chip)


def _touch_jax(rehearse: bool) -> dict:
    """First JAX use of a phase process: cache, platform, device report."""
    from llmd_tpu import jaxrt

    requested = "cpu" if rehearse else None
    jaxrt.pin_platform(requested)
    cache_dir = jaxrt.enable_compile_cache()
    device = jaxrt.serving_device(requested)  # refuses anything but a TPU
    return {"device": device, "compile_cache_dir": cache_dir}


def _page_table(pages, last_positions, table_rows, max_pages, page):
    """A [table_rows, max_pages] table in which each row owns distinct
    physical pages up to its last position, and 0 elsewhere — what the
    allocator hands the runner. ``pages`` yields unused page ids."""
    import numpy as np

    table = np.zeros((table_rows, max_pages), np.int32)
    for r, last in enumerate(last_positions):
        for j in range(last // page + 1):
            table[r, j] = next(pages)
    return table


def _flat_plan(pages, rows, page, max_pages, table_rows):
    """Token stream + KV-write run plan for ``rows`` = [(pos0, qlen)], the
    way engine/runner.py::_fill_flat_runs lays it out."""
    import numpy as np

    total = sum(w for _, w in rows)
    T = -(-total // 16) * 16
    tok_rows = np.zeros(T, np.int32)
    positions = np.zeros(T, np.int32)
    live = np.zeros(T, bool)
    table = _page_table(pages, [p0 + w - 1 for p0, w in rows], table_rows, max_pages, page)
    src, phys, off, cnt = [], [], [], []
    t = 0
    for r, (p0, w) in enumerate(rows):
        tok_rows[t:t + w] = r
        positions[t:t + w] = p0 + np.arange(w)
        live[t:t + w] = True
        consumed = 0
        while consumed < w:
            p = p0 + consumed
            o = p % page
            take = min(page - o, w - consumed)
            src.append(page + t + consumed - o)
            phys.append(table[r, p // page])
            off.append(o)
            cnt.append(take)
            consumed += take
        t += w
    n_runs = 2 * table_rows + -(-T // page)
    pad = n_runs - len(src)
    as_i32 = lambda xs: np.asarray(xs + [0] * pad, np.int32)  # noqa: E731
    return dict(T=T, rows=tok_rows, positions=positions, live=live, table=table,
                src=as_i32(src), phys=as_i32(phys), off=as_i32(off), cnt=as_i32(cnt))


def phase_kernels(profile: dict, seed: int, rehearse: bool) -> None:
    info = _touch_jax(rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmd_tpu import jaxrt, ops
    from llmd_tpu.ops import sparse_attention
    from llmd_tpu.ops.grouped_gemm import grouped_matmul
    from llmd_tpu.ops.kv_write import write_kv_pages_decode_full, write_kv_pages_flat_full
    from llmd_tpu.ops.mla_attention import mla_paged_attention_xla
    from llmd_tpu.ops.mla_decode import mla_decode_paged_attention_full
    from llmd_tpu.ops.paged_attention import (
        paged_attention_xla, paged_attention_xla_blocked, write_kv_pages,
    )
    from llmd_tpu.ops.ragged_paged_attention import (
        decode_paged_attention_full, flat_paged_attention_full,
    )

    interpret = rehearse  # the CPU has no Mosaic; the chip never interprets
    k = profile["kernels"]
    L, P, H, K, D, page = k["L"], k["pages"], k["H"], k["K"], k["D"], 16
    rows, max_pages, B, ctx = k["rows"], k["max_pages"], k["batch"], k["ctx"]
    rng = np.random.default_rng(seed)
    layer = jnp.int32(L - 1)
    results = []

    # Dispatch round trip first (ROADMAP S0(e)): ~50 trivial jitted calls,
    # each ended by block_until_ready.
    bump = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8, 128), jnp.float32)
    bump(x).block_until_ready()
    rtts = []
    for _ in range(50):
        t0 = time.perf_counter()
        bump(x).block_until_ready()
        rtts.append((time.perf_counter() - t0) * 1e3)
    emit(phase="kernels", event="dispatch_rtt", median_ms=statistics.median(rtts),
         min_ms=min(rtts), max_ms=max(rtts), calls=len(rtts), **info)

    def bits(a):
        a = np.asarray(a)
        return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])

    def exact(name, got, want):
        same = np.array_equal(bits(got), bits(want))
        results.append((name, same))
        emit(phase="kernels", kernel=name, check="bit-exact", ok=bool(same))

    def close(name, got, want, mask, atol, rtol):
        got = np.asarray(got, np.float32)[mask]
        want = np.asarray(want, np.float32)[mask]
        err = float(np.max(np.abs(got - want)))
        ok = bool(np.all(np.isfinite(got)) and np.allclose(got, want, atol=atol, rtol=rtol))
        results.append((name, ok))
        emit(phase="kernels", kernel=name, check="allclose", ok=ok, max_abs_err=err,
             reference_rms=float(np.sqrt(np.mean(want**2))), atol=atol, rtol=rtol)

    def normal(shape, dtype, scale=1.0):
        nonlocal key
        key, sub = jax.random.split(key)
        return (jax.random.normal(sub, shape, jnp.float32) * scale).astype(dtype)

    def int8s(shape):
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.randint(sub, shape, -127, 128, jnp.int8)

    key = jax.random.key(seed)
    # A step like the server's: two prefill chunks that start and end inside
    # pages, and decode rows at odd offsets; then a decode batch, one row
    # of it padding. Each table row owns its pages, as the allocator's do.
    step = [(3, ctx // 7 + 7), (ctx - 67, 67), (17, 1), (ctx - 1, 1), (ctx // 4 + 5, 1), (0, 34)]
    plan = _flat_plan(iter(rng.permutation(P).tolist()), step, page, max_pages, rows)
    T, ctx_pages = plan["T"], -(-ctx // page)
    tok_table = jnp.asarray(plan["table"][plan["rows"]])
    pos2 = jnp.asarray(plan["positions"][:, None])
    live2 = jnp.asarray(plan["live"][:, None])
    dec_pos = rng.integers(0, ctx // 2, size=B).astype(np.int32)
    dec_table = _page_table(iter(rng.permutation(P).tolist()), dec_pos, B, max_pages, page)
    dec_valid = np.ones(B, bool)
    dec_valid[B // 2] = False  # a pad row must leave its page alone
    dec_phys = dec_table[np.arange(B), dec_pos // page]

    for dtype in (jnp.bfloat16, jnp.int8):
        tag = jnp.dtype(dtype).name
        if dtype == jnp.int8:
            pool, new = int8s((L, P, K, page, 2 * D)), int8s((T, K, 2 * D))
            key, sub = jax.random.split(key)
            # f32 values on the f16 grid: the pool's scale contract (ops/quant_kv.py)
            scales = jax.random.uniform(sub, (L, P, K, page, 2), jnp.float32, 0.01, 0.1
                                        ).astype(jnp.float16).astype(jnp.float32)
            spread = 73.0 * 0.055  # std of uniform int8 x the mean scale
        else:
            pool, new = normal((L, P, K, page, 2 * D), dtype), normal((T, K, 2 * D), dtype)
            scales, spread = None, 1.0
        lscales = None if scales is None else scales[L - 1]
        atol = ATTN_ATOL[tag]

        # flat write (the default step's KV write) against the XLA scatter
        wrote = jax.jit(write_kv_pages_flat_full, static_argnames="interpret")(
            pool + 0, new, layer, jnp.asarray(plan["src"]), jnp.asarray(plan["phys"]),
            jnp.asarray(plan["off"]), jnp.asarray(plan["cnt"]), interpret=interpret)
        want = jax.jit(write_kv_pages)(
            pool[L - 1], new[:, None, :, :D], new[:, None, :, D:], tok_table, pos2, live2)
        exact(f"flat_write-{tag}", wrote[L - 1], want)
        exact(f"flat_write-{tag}-other-layers", wrote[0], pool[0])
        check(not np.array_equal(bits(pool[L - 1]), bits(want)), "the write reference wrote nothing")

        # decode write against the same scatter
        dnew = new[:B]
        dwrote = jax.jit(write_kv_pages_decode_full, static_argnames="interpret")(
            pool + 0, dnew, layer, jnp.asarray(dec_phys), jnp.asarray(dec_pos % page),
            jnp.asarray(dec_valid), interpret=interpret)
        dwant = jax.jit(write_kv_pages)(
            pool[L - 1], dnew[:, None, :, :D], dnew[:, None, :, D:], jnp.asarray(dec_table),
            jnp.asarray(dec_pos[:, None]), jnp.asarray(dec_valid[:, None]))
        exact(f"decode_write-{tag}", dwrote[L - 1], dwant)

        # flat attention over the pool just written, per-token causal horizon
        q = normal((T, 1, H, D), jnp.bfloat16, 2.0 / spread)
        kv_lens = jnp.asarray(np.where(plan["live"], plan["positions"] + 1, 0).astype(np.int32))
        got = jax.jit(flat_paged_attention_full, static_argnames="interpret")(
            q, wrote, layer, jnp.asarray(plan["rows"]), jnp.asarray(plan["table"]),
            kv_lens, interpret=interpret, scales=scales)
        ref = jax.jit(paged_attention_xla)(
            q, wrote[L - 1], tok_table[:, :ctx_pages], kv_lens, pos2, scales=lscales)
        close(f"flat_attention-{tag}", got, ref, plan["live"], atol, ATTN_RTOL)
        # ... and under a sliding window that starts inside a page: the same
        # tiles, a shared one reading from its first token's window start
        window = jnp.int32(ctx // 3 + 5)
        wgot = jax.jit(flat_paged_attention_full, static_argnames="interpret")(
            q, wrote, layer, jnp.asarray(plan["rows"]), jnp.asarray(plan["table"]),
            kv_lens, interpret=interpret, scales=scales, window=window)
        wref = jax.jit(paged_attention_xla)(
            q, wrote[L - 1], tok_table[:, :ctx_pages], kv_lens, pos2, scales=lscales,
            window=window)
        close(f"window_attention-{tag}", wgot, wref, plan["live"], atol, ATTN_RTOL)

        # decode attention
        dq = normal((B, 1, H, D), jnp.bfloat16, 2.0 / spread)
        dlens = jnp.asarray(dec_pos + 1)
        dgot = jax.jit(decode_paged_attention_full, static_argnames="interpret")(
            dq, dwrote, layer, jnp.asarray(dec_table), dlens, interpret=interpret, scales=scales)
        dref = jax.jit(paged_attention_xla)(
            dq, dwrote[L - 1], jnp.asarray(dec_table[:, :ctx_pages]), dlens,
            jnp.asarray(dec_pos[:, None]), scales=lscales)
        close(f"decode_attention-{tag}", dgot, dref, np.ones(B, bool), atol, ATTN_RTOL)
        del pool, wrote, dwrote

    # The kernels the first benchmark configuration (ROADMAP R1) needs.
    # (XLA's CPU backend has no bf16 x bf16 -> f32 dot for these references.)
    act = jnp.float32 if rehearse else jnp.bfloat16
    m = k["mla"]
    lat = normal((m["L"], P, 1, page, m["Dl"]), act)
    sm_scale = m["Dl"] ** -0.5
    qe = normal((B, 1, m["H"], m["Dl"]), act, 2.0)  # score spread near 2
    mlens = jnp.asarray(dec_pos + 1)
    mgot = jax.jit(mla_decode_paged_attention_full, static_argnames=("rank", "sm_scale", "interpret"))(
        qe, lat, jnp.int32(1), jnp.asarray(dec_table), mlens, rank=m["rank"],
        sm_scale=sm_scale, interpret=interpret)
    mref = jax.jit(mla_paged_attention_xla, static_argnames=("rank", "sm_scale"))(
        qe, lat[1], jnp.asarray(dec_table[:, :ctx_pages]), mlens,
        jnp.asarray(dec_pos[:, None]), rank=m["rank"], sm_scale=sm_scale)
    close("mla_decode", mgot, mref, np.ones(B, bool), ATTN_ATOL["bfloat16"], ATTN_RTOL)

    # Learned sparse attention (ops/sparse_attention.py): the selection
    # itself against plain jax.numpy, then the Pallas flat attention under the
    # mask against the XLA attention under the reference's sets.
    sp = k["sparse"]
    topk, c0, c1, c2 = sp["topk"], *sp["contexts"]
    srows = [(c0 - 1, 1), (c1 - 1, 1), (c2 - sp["chunk"], sp["chunk"])]  # two decode rows, one chunk
    splan = _flat_plan(iter(rng.permutation(sp["pages"]).tolist()), srows, page, sp["max_pages"], 8)
    sT, S = splan["T"], sp["max_pages"] * page
    spool = normal((L, sp["pages"], sp["K"], page, 2 * D), jnp.bfloat16)
    plane = normal((L, sp["pages"], page, sp["Di"]), jnp.bfloat16)
    iq, iw = normal((sT, sp["J"], sp["Di"]), jnp.bfloat16), normal((sT, sp["J"]), jnp.bfloat16)
    srow_of, stable = jnp.asarray(splan["rows"]), jnp.asarray(splan["table"])
    slens = jnp.asarray(np.where(splan["live"], splan["positions"] + 1, 0).astype(np.int32))
    sel = jax.jit(lambda *a: sparse_attention.select_topk(sparse_attention.index_scores(*a), topk))(
        iq, iw, plane[L - 1], stable, srow_of, slens)

    @jax.jit
    def plain_scores(iq, iw, plane, table, lens):
        keys = plane[table].reshape(table.shape[0], S, -1).astype(jnp.float32)  # [T, S, Di]
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("tjd,tsd->tjs", iq.astype(jnp.float32), keys)
        s = jnp.sum(jax.nn.relu(s) * iw.astype(jnp.float32)[:, :, None], axis=1)
        return jnp.where(jnp.arange(S)[None, :] < lens[:, None], s, -jnp.inf)

    scores = np.asarray(plain_scores(iq, iw, plane[L - 1], stable[srow_of], slens))
    causal = np.arange(S)[None, :] < np.asarray(slens)[:, None]
    order = np.argsort(-scores, axis=1, kind="stable")[:, :topk]
    want = np.zeros_like(causal)
    np.put_along_axis(want, order, True, axis=1)
    want &= causal
    got_sel = np.asarray(sel) & causal
    overlap = float((got_sel & want).sum() / want.sum())
    # What differs sits at the threshold: within a rounding of the topk-th score.
    kth = np.take_along_axis(scores, order[:, -1:], axis=1)
    near = np.abs(np.where(causal, scores, 0.0) - kth) <= 1e-3 * np.abs(kth) + 1e-4
    off = (got_sel ^ want) & ~near
    ok = bool(overlap >= 0.999 and not off.any()
              and (got_sel.sum(1) == np.minimum(np.asarray(slens), topk)).all())
    results.append(("sparse_select", ok))
    emit(phase="kernels", kernel="sparse_select", check="selected sets", ok=ok, overlap=overlap,
         differing=int((got_sel ^ want).sum()), beyond_a_rounding=int(off.sum()),
         tokens=int(splan["live"].sum()), topk=topk, contexts=list(sp["contexts"]))
    sq = normal((sT, 1, sp["H"], D), jnp.bfloat16, 2.0)
    with jax.named_scope("llmd.sparse_attention"):
        sgot = jax.jit(flat_paged_attention_full, static_argnames="interpret")(
            sq, spool, layer, srow_of, stable, slens, interpret=interpret, sel=sel)
    sref = jax.jit(paged_attention_xla_blocked)(
        sq, spool[L - 1], stable[srow_of], slens, jnp.asarray(splan["positions"][:, None]),
        sel=jnp.asarray(want)[:, None, :])
    close("sparse_attention", sgot, sref, splan["live"], ATTN_ATOL["bfloat16"], ATTN_RTOL)
    del spool, plane, sgot, sref

    g = k["gmm"]
    xs = normal((g["tokens"], g["hidden"]), act)
    w = normal((g["experts"], g["hidden"], g["ffn"]), act, g["hidden"] ** -0.5)
    sizes = jnp.asarray(rng.multinomial(g["tokens"], np.ones(g["experts"]) / g["experts"]).astype(np.int32))
    if rehearse:
        os.environ["LLMD_PALLAS"] = "interpret"  # the only switch the kernel has
    gplans: dict = {}
    with ops.record_plans(gplans):
        ggot = jax.jit(grouped_matmul)(xs, w, sizes)
    check(gplans == {"grouped_gemm": {"pallas"}}, f"the grouped GEMM took {gplans}")
    gref = jax.lax.ragged_dot(xs, w, sizes, preferred_element_type=jnp.float32)
    close("grouped_gemm", ggot, gref, np.ones(g["tokens"], bool), GMM_ATOL, GMM_RTOL)

    failed = [name for name, ok in results if not ok]
    emit(phase="kernels", event="done", kernels=len(results), failed=failed,
         peak_bytes_in_use=jaxrt.peak_bytes_in_use(), **info)
    check(not failed, f"kernels disagree with their XLA reference: {failed}")


# --------------------------------------------------------------------- #
# --chips 4, part (a): one process, a tp=4 mesh against tp=1 on chip 0


def phase_tp4(profile: dict, seed: int, rehearse: bool) -> None:
    info = _touch_jax(rehearse)
    import jax
    import numpy as np

    from llmd_tpu.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
    from llmd_tpu.engine import LLMEngine, SamplingParams
    from llmd_tpu.models.registry import get_model_config

    check(len(jax.devices()) >= 4, f"--chips 4 needs four devices, JAX shows {jax.devices()}")
    rng = random.Random(seed)
    prompts = [[1] + [3 + b for b in make_prompt(rng, n).encode()] for n in (40, 150, 300)]
    sampling = SamplingParams(temperature=0.0, max_tokens=16, ignore_eos=True, logprobs=True)

    def run(tp: int):
        cfg = EngineConfig(
            model=get_model_config(profile["model"], max_model_len=1024),
            cache=CacheConfig(page_size=16, num_blocks=profile["tp_blocks"]),
            scheduler=SchedulerConfig(max_num_seqs=8, max_num_batched_tokens=512),
            parallel=ParallelConfig(tensor_parallel_size=tp), seed=seed,
        )
        eng = LLMEngine(cfg)
        placed = {d.id for leaf in jax.tree.leaves(eng.runner.params) for d in leaf.devices()}
        pooled = {d.id for d in eng.runner.kv_cache.devices()}
        for p in prompts:
            eng.add_request(p, sampling)
        reqs = list(eng.scheduler.waiting)
        while eng.has_work():
            eng.step()
        out = [(list(r.output_token_ids), list(r.output_logprobs)) for r in reqs]
        plans = {op: sorted(p) for op, p in eng.runner.kernel_plans.items()}
        eng.close()
        return out, placed, pooled, plans

    # tp=4 first: its full-size initial weights pass through chip 0 before
    # they are sharded, and must be gone before tp=1's arrive.
    out4, placed4, pooled4, plans4 = run(4)
    out1, placed1, pooled1, plans1 = run(1)
    check(len(placed4) == 4 and len(pooled4) == 4,
          f"tp=4 parameters on devices {sorted(placed4)}, KV pool on {sorted(pooled4)}")
    check(len(placed1) == 1, f"tp=1 parameters on devices {sorted(placed1)}")
    rows = []
    for (t4, l4), (t1, l1) in zip(out4, out1):
        agree = 0
        while agree < len(t1) and t4[agree] == t1[agree]:
            agree += 1
        rows.append({"first_logprob_tp4": l4[0], "first_logprob_tp1": l1[0],
                     "agreeing_greedy_prefix": agree, "of": len(t1)})
        check(np.isfinite(l4[0]) and np.isfinite(l1[0]), "a first-token log-probability is not finite")
    worst = max(abs(r["first_logprob_tp4"] - r["first_logprob_tp1"]) for r in rows)
    emit(phase="tp4", prompts=rows, max_first_logprob_diff=worst, atol=TP_LOGPROB_ATOL,
         param_devices_tp4=sorted(placed4), kv_pool_devices_tp4=sorted(pooled4),
         param_devices_tp1=sorted(placed1), kernel_plans_tp4=plans4, kernel_plans_tp1=plans1,
         **info)
    check(worst <= TP_LOGPROB_ATOL, f"tp=4 and tp=1 first-token log-probabilities differ by {worst}")
    if not rehearse:
        for plans, want in ((plans4, "pallas_shard"), (plans1, "pallas")):
            for op in ("flat_attention", "flat_kv_write"):
                check(plans.get(op) == [want], f"{op} took {plans.get(op)}, expected {want}")


# --------------------------------------------------------------------- #
# --chips 4, part (b): four one-chip replicas under the DP supervisor


def phase_dp4(profile: dict, seed: int, rehearse: bool) -> None:
    model = profile["model"]
    ports = [DP_PORT_BASE + i for i in range(4)]
    supervisor = [sys.executable, "-m", "llmd_tpu.serve.dp_supervisor",
                  "--data-parallel-size", "4", "--port-base", str(DP_PORT_BASE),
                  "--health-port", str(DP_HEALTH_PORT), "--",
                  "--model", model, "--host", HOST, "--seed", str(seed), *profile["serve_args"]]
    rng = random.Random(seed)
    with child("dp_supervisor", supervisor) as sup, start_router(ports) as router:
        procs = [sup, router]
        wait_until(lambda: all(up(p, "/ready") for p in ports), "four ranks' /ready", procs)
        wait_until(lambda: router_sees(4), "the router to see four ranks healthy", procs, 120)
        ranks = []
        for p in ports:
            status, st = http_json("GET", p, "/admin/status")
            check(status == 200, f"rank :{p} /admin/status answered {status}")
            ranks.append(st)
            emit(phase="dp4", event="rank", port=p, device=st["device"],
                 visible_chips=st["visible_chips"], device_files=st["device_files"],
                 warmup_s=st["startup"]["warmup_s"], kernel_plans=st["kernel_plans"])
            check(st["device"]["count"] == 1, f"rank :{p} sees {st['device']['count']} devices, not one")
            if not rehearse:
                check(st["device"]["platform"] == "tpu", f"rank :{p} is on {st['device']}")
        chips = [st["visible_chips"] for st in ranks]
        check(len(set(chips)) == 4 and None not in chips, f"ranks were given chips {chips}")
        files = [tuple(st["device_files"]) for st in ranks]
        if any(files):
            check(all(files) and len({f for fs in files for f in fs}) == sum(map(len, files)),
                  f"ranks share an accelerator device file: {files}")
        # Distinct prompts, eight at a time, until every rank has answered.
        sent, served = 0, [0.0] * 4
        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
            while min(served) < 1:
                check(sent < 64, f"after {sent} requests the ranks had served {served}")
                batch = [pool.submit(completion, make_prompt(rng, 60 + 7 * (sent + i)), 8, model)
                         for i in range(8)]
                for fut in batch:
                    fut.result(timeout=remaining())
                sent += len(batch)
                for i, p in enumerate(ports):
                    status, metrics = http_json("GET", p, "/metrics")
                    check(status == 200, f"rank :{p} /metrics answered {status}")
                    served[i] = metric(metrics, "vllm:request_success_total")
        check(sup.poll() is None, "the supervisor exited")
        emit(phase="dp4", event="done", requests_through_router=sent,
             served_per_rank=served, chips=chips, device_files=files)


# --------------------------------------------------------------------- #


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on the CPU: checks this script's control "
                    "flow, prints no result line and exits 3")
    ap.add_argument("--phase", choices=("kernels", "tp4"), help=argparse.SUPPRESS)
    args = ap.parse_args()
    profile = PROFILES["rehearse" if args.rehearse else "real"]
    # The kernels are chosen by the platform alone here; the env lever that
    # forces interpret mode or the XLA path would make this a different run.
    check("LLMD_PALLAS" not in os.environ, "LLMD_PALLAS is set; unset it")

    if args.phase == "kernels":
        phase_kernels(profile, args.seed, args.rehearse)
        return 0
    if args.phase == "tp4":
        phase_tp4(profile, args.seed, args.rehearse)
        return 0

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))  # run the finallys
    emit(event="environment", python=sys.version.split()[0], cpus=os.cpu_count(),
         env={k: v for k, v in sorted(os.environ.items())
              if k.startswith(("TPU_", "JAX_", "XLA_", "LIBTPU", "PJRT_"))})
    flags = ["--seed", str(args.seed)] + (["--rehearse"] if args.rehearse else [])
    t0 = time.monotonic()
    if args.chips == 4:
        env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"} if args.rehearse else None
        device = run_phase("tp4", flags, env)["device"]
        phase_dp4(profile, args.seed, args.rehearse)
    else:
        device = phase_serve(profile, args.seed, args.rehearse)
        kernels = run_phase("kernels", flags)
        check(kernels["device"] == device, f"kernel phase ran on {kernels['device']}, server on {device}")
        emit(leg="deepseek-v2-lite", status="not run")
    emit(event="wall", seconds=round(time.monotonic() - t0, 1), chips=args.chips)
    if args.rehearse:
        print("rehearsal complete on the CPU at tiny size: not a chip result", file=sys.stderr)
        return 3
    check(device["platform"] == "tpu" and device["count"] == args.chips,
          f"ran on {device}, wanted {args.chips} TPU chip(s)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
