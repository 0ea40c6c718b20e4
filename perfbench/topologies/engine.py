"""Topology ``engine``: one process, one chip, the engine in process.

``LLMEngine`` under ``serve/async_engine.py::AsyncEngine`` — the scheduler,
runner and step path that ``python -m llmd_tpu.serve`` serves from, without
the HTTP front end. The only file of the benchmark that knows the program's
Python API; everything it reads is a public attribute or method.

Set-up, in order (all of it counted in ``setup_s``):
  1. weights on the device in ONE jitted call from the seed, in the served
     dtype (``models/llama.py::init_params`` under ``jax.jit``);
  2. the engine's own warm-up (``runner.warmup()``);
  3. the shape ladder: every step shape the cell's traffic can reach is run
     once through ``add_request``/``step`` (flat engines: one prompt per
     16-token T bucket; bucketed engines: the config file's ``warm_plan``);
  4. the reference comparison that decides ``correct`` (perfbench/correctness.py).
The generator's own warm replay follows, from the generator.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time

import numpy as np

# HF config.json key -> llmd_tpu ModelConfig field. A configuration file
# holds the published keys; this is how they reach the program.
HF_TO_MODEL = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden_size",
    "intermediate_size": "intermediate_size",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling",
    "rms_norm_eps": "rms_norm_eps",
    "tie_word_embeddings": "tie_word_embeddings",
    "attention_bias": "attention_bias",
    "num_experts": "num_experts",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_group": "n_group",
    "topk_group": "topk_group",
    "first_k_dense_replace": "first_dense_layers",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
}
_TOPK_METHOD = {"greedy": "greedy", "group_limited_greedy": "group_max", "noaux_tc": "group_top2"}


def model_overrides(conf: dict) -> dict:
    """ModelConfig overrides from the published keys of a configuration."""
    out = {}
    for hf, field in HF_TO_MODEL.items():
        if hf in conf:
            out[field] = conf[hf]
    if out.get("q_lora_rank") is None and "q_lora_rank" in out:
        out["q_lora_rank"] = 0
    if "scoring_func" in conf:
        out["router_scoring"] = conf["scoring_func"]
    if "topk_method" in conf:
        out["topk_method"] = _TOPK_METHOD[conf["topk_method"]]
    if conf.get("n_shared_experts"):
        out["shared_expert_intermediate_size"] = (
            conf["n_shared_experts"] * conf["moe_intermediate_size"]
        )
    return out


def engine_config(conf: dict, seed: int, rehearse: bool):
    """The EngineConfig a configuration file describes."""
    from llmd_tpu.config import CacheConfig, EngineConfig, ParallelConfig, SchedulerConfig
    from llmd_tpu.models.registry import get_model_config

    geo = dict(conf["engine"])
    if rehearse:
        geo.update(conf["rehearse"]["engine"])
        model = get_model_config(
            conf["rehearse"]["registry"], max_model_len=geo["max_model_len"],
            **conf["rehearse"].get("overrides", {}),
        )
    else:
        model = get_model_config(
            conf["registry"], max_model_len=geo["max_model_len"],
            dtype=conf["dtype"], **model_overrides(conf),
        )
    sched = {k: geo[k] for k in ("max_num_seqs", "max_num_batched_tokens")}
    for k in ("prefill_token_buckets", "prefill_batch_buckets", "decode_batch_buckets"):
        if k in geo:
            sched[k] = tuple(geo[k])
    return EngineConfig(
        model=model,
        cache=CacheConfig(page_size=geo["page_size"], num_blocks=geo["num_pages"], dtype=geo["kv_dtype"]),
        scheduler=SchedulerConfig(**sched),
        parallel=ParallelConfig(),
        seed=seed % (2**31 - 1),
    )


def make_params(model_cfg, seed: int):
    """Weights on the device, one jitted call, in the served dtype. Under
    jit the f32 draw and the cast fuse, so no leaf exists in f32."""
    import jax

    from llmd_tpu.models import llama

    key = jax.random.fold_in(jax.random.key(seed % (2**31 - 1)), seed >> 31)
    return jax.jit(lambda k: llama.init_params(model_cfg, k))(key)


def reference_params(runner_params: dict, model_cfg) -> dict:
    """The served parameter tree under the unfused names the references
    use. ``runner._maybe_fuse`` concatenates q|k|v (lossless); split it
    back. Expert leaves are passed through by reference, not copied."""
    out = dict(runner_params)
    for group in ("layers", "dense_layers"):
        if group not in out:
            continue
        d = dict(out[group])
        if "wqkv" in d:
            nq = model_cfg.num_heads * model_cfg.head_dim
            nk = model_cfg.num_kv_heads * model_cfg.head_dim
            w = d.pop("wqkv")
            d["wq"], d["wk"], d["wv"] = w[..., :nq], w[..., nq:nq + nk], w[..., nq + nk:]
        out[group] = d
    return out


@dataclasses.dataclass
class Output:
    new_token_ids: list
    finished: bool
    num_cached_tokens: int


class System:
    """What a generator and the harness see of the system under test."""

    def __init__(self, ctx) -> None:
        import jax

        from llmd_tpu import jaxrt
        from llmd_tpu.engine import LLMEngine, SamplingParams

        self.ctx = ctx
        self._SamplingParams = SamplingParams
        self.setup_log: list = []  # (what, seconds)
        t = time.monotonic()
        if not ctx.rehearse:  # fixed path in the checkout, or JAX_COMPILATION_CACHE_DIR
            jaxrt.enable_compile_cache()
        self.compiles = jaxrt.CompileCounters().install()
        dev = jax.devices()
        self.device = {"platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev)}
        if not ctx.rehearse and (self.device["platform"] != "tpu" or len(dev) < ctx.chips):
            raise SystemExit(
                f"perfbench: needs {ctx.chips} TPU chip(s), JAX shows {self.device}; "
                "--rehearse runs the tiny preset on the CPU"
            )
        self.geo = dict(ctx.config["engine"])
        if ctx.rehearse:
            self.geo.update(ctx.config["rehearse"]["engine"])
        self.config = engine_config(ctx.config, ctx.seed, ctx.rehearse)
        self.model_cfg = self.config.model
        self.vocab_size = self.model_cfg.vocab_size
        self.max_model_len = self.model_cfg.max_model_len
        params = make_params(self.model_cfg, ctx.seed)
        jax.block_until_ready(params)
        self._mark("weights", t)
        t = time.monotonic()
        self.engine = LLMEngine(self.config, params=params)
        del params
        self._mark("engine", t)
        self._n = 0
        self.steps: list | None = None  # (start, end, outputs) per step, traced runs
        self._async = None

    def _mark(self, what: str, t: float) -> None:
        self.setup_log.append((what, round(time.monotonic() - t, 3)))

    # -- set-up --------------------------------------------------------- #

    def warm_up(self) -> None:
        t = time.monotonic()
        self.engine.runner.warmup()
        self._mark("engine_warmup", t)
        t = time.monotonic()
        rng = np.random.default_rng(self.ctx.seed ^ 0x1ADDE7)
        for shape in self._ladder():
            self._run_shape(rng, *shape)
        self._mark("shape_ladder", t)

    def _ladder(self) -> list:
        """(decode rows, prefill length) steps that reach every step shape."""
        runner = self.engine.runner
        if runner.flat_t_buckets:
            budget = self.config.scheduler.max_num_batched_tokens
            return [(0, T) for T in runner.flat_t_buckets if T <= budget]
        return [tuple(s) for s in self.geo.get("warm_plan", [])]

    def _run_shape(self, rng, n_decode: int, prefill_len: int) -> None:
        """One step with ``n_decode`` one-token rows and one prefill chunk of
        ``prefill_len`` tokens (0: none), through the public API."""
        eng = self.engine
        ids = []
        while len(ids) < n_decode:  # a few rows at a time: few prefill rows a step
            group = [
                eng.add_request(self._tokens(rng, 8), self._sampling(self.max_model_len))
                for _ in range(min(8, n_decode - len(ids)))
            ]
            ids += group
            started: set = set()
            while len(started) < len(group):  # until these rows decode
                started.update(o.request_id for o in eng.step() if o.request_id in group)
        if prefill_len:
            ids.append(eng.add_request(self._tokens(rng, prefill_len), self._sampling(1)))
        eng.step()
        for rid in ids:
            eng.abort_request(rid)
        while eng.has_work():
            eng.step()

    def _tokens(self, rng, n: int) -> list:
        return rng.integers(0, self.vocab_size, size=n).tolist()

    def _sampling(self, max_tokens: int, logprobs: bool = False):
        return self._SamplingParams(
            max_tokens=max_tokens, temperature=0.0, ignore_eos=True, logprobs=logprobs)

    def greedy_with_logprobs(self, prompts: list, max_tokens: int) -> list:
        """[(token ids, log-probabilities)] per prompt, greedy, through
        prefill and then decode through the cache."""
        eng = self.engine
        for p in prompts:
            eng.add_request(list(p), self._sampling(max_tokens, logprobs=True))
        reqs = list(eng.scheduler.waiting)
        while eng.has_work():
            eng.step()
        return [(list(r.output_token_ids), list(r.output_logprobs)) for r in reqs]

    def reference_params(self) -> dict:
        return reference_params(self.engine.runner.params, self.model_cfg)

    # -- serving -------------------------------------------------------- #

    def start(self, record_steps: bool) -> None:
        """Serve from the calling event loop (one AsyncEngine per loop)."""
        from llmd_tpu.serve.async_engine import AsyncEngine

        if record_steps and self.steps is None:
            import jax

            span = jax.profiler.TraceAnnotation

            def spanned(fn, name):
                def call(*a, **kw):
                    with span(name):
                        return fn(*a, **kw)
                return call

            # Host spans on the profiler's clock, so that an idle gap of the
            # device can be put down to what the host was doing in it.
            self.engine.step = spanned(self.engine.step, "pb.step")
            self.engine.scheduler.schedule = spanned(self.engine.scheduler.schedule, "pb.schedule")
            self.engine.runner.wait_step = spanned(self.engine.runner.wait_step, "pb.wait_step")
            self.time_steps()
        self._async = AsyncEngine(self.engine, watchdog_s=0)
        self._async.start(asyncio.get_running_loop())

    async def stream(self, prompt: list, max_tokens: int):
        self._n += 1
        agen = self._async.generate(f"pb-{self._n}", prompt, self._sampling(max_tokens))
        try:
            async for out in agen:
                yield Output(out.new_token_ids, out.finished, out.num_cached_tokens)
        finally:
            await agen.aclose()

    def counters(self) -> dict:
        s = self.engine.stats
        out = {
            f.name: getattr(s, f.name) for f in dataclasses.fields(s)
            if isinstance(getattr(s, f.name), (int, float))
        }
        c = self.compiles.snapshot()
        out.update(compile_programs=c["programs"], compile_seconds=c["seconds"],
                   compile_cache_hits=c["cache_hits"])
        return out

    def time_steps(self) -> None:
        """From now on, the host clock around every ``engine.step`` (into
        ``self.steps``): ``--trace 1`` from the start, around its ``pb.step``
        span; ``--trace 2`` once the window is closed (the serving thread
        looks ``engine.step`` up at every call)."""
        if self.steps is not None:
            return
        self.steps = []
        inner, log = self.engine.step, self.steps

        def step():
            t0 = time.monotonic()
            outs = inner()
            log.append((t0, time.monotonic(), len(outs)))
            return outs

        self.engine.step = step

    def trace_start(self, trace_dir) -> None:
        """Open a profiler session through the program's own control, in
        this process, which holds the chip (``--trace 2``)."""
        from llmd_tpu.obs import profiling

        profiling.start(trace_dir)

    def trace_stop(self) -> None:
        from llmd_tpu.obs import profiling

        profiling.stop()

    def traced_programs(self) -> list:
        """The step programs the runner traced, newest last: (unix time,
        family, shape)."""
        return [list(p) for p in self.engine.runner.traced_programs]

    def kernel_plans(self) -> dict:
        return {op: sorted(p) for op, p in self.engine.runner.kernel_plans.items()}

    def peak_bytes(self):
        from llmd_tpu import jaxrt

        return jaxrt.peak_bytes_in_use()

    def pause(self) -> None:
        """Stop the serving thread; the engine and its caches stay."""
        if self._async is not None:
            self._async.stop()
            self._async = None
        sched = self.engine.scheduler  # requests cut at the window's end
        for r in [*sched.running, *sched.waiting]:
            self.engine.abort_request(r.request_id)
        while self.engine.has_work():
            self.engine.step()

    def stop(self) -> None:
        self.pause()
        self.engine.close()


def start(ctx) -> System:
    return System(ctx)
