"""The invariant linter (llmd_tpu/analysis): every rule fires on a bad
fixture AND stays quiet on a good one, pragma/allowlist behavior, and
the tree-is-clean gate (docs/architecture/static-analysis.md).

The acceptance-critical pins: deleting any follower dispatch arm for an
_OP_* opcode makes the suite exit nonzero, and adding an unlisted
jax.device_get in engine/ makes it exit nonzero.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from llmd_tpu.analysis import run_analysis

REPO = Path(__file__).resolve().parent.parent
RUNNER = REPO / "llmd_tpu/engine/runner.py"


def check(tmp_path: Path, files: dict[str, str], rules: list[str]):
    """Write a fixture tree and run the selected rules over it."""
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    findings, _ = run_analysis(tmp_path, [str(tmp_path)], rules)
    return findings


def codes(findings) -> set[str]:
    return {f.code for f in findings}


# ------------------------------------------------------------------ #
# host-sync


class TestHostSync:
    def test_device_get_in_engine_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import jax

                def read(x):
                    return jax.device_get(x)
            """,
        }, ["host-sync"])
        assert codes(fs) == {"HS001"}

    def test_block_until_ready_and_item_fire(self, tmp_path):
        fs = check(tmp_path, {
            "ops/bad.py": """
                def f(x):
                    x.block_until_ready()
                    return x.item()
            """,
        }, ["host-sync"])
        assert codes(fs) == {"HS002", "HS003"}

    def test_module_level_block_until_ready_fires(self, tmp_path):
        # The function-form spelling, jax.block_until_ready(x).
        fs = check(tmp_path, {
            "engine/bad.py": """
                import jax

                def f(x):
                    return jax.block_until_ready(x)
            """,
        }, ["host-sync"])
        assert codes(fs) == {"HS002"}

    def test_coercion_of_device_array_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import jax
                import jax.numpy as jnp
                import numpy as np

                def f(arr: jax.Array):
                    y = jnp.exp(arr)
                    a = np.asarray(y)       # device result
                    b = int(arr)            # annotated device param
                    c = float(y[0])         # subscript of device name
                    return a, b, c
            """,
        }, ["host-sync"])
        assert [f.code for f in fs] == ["HS004", "HS004", "HS004"]

    def test_host_coercions_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "engine/good.py": """
                import jax
                import numpy as np

                def f(ids, n):
                    pt = np.asarray(ids, np.int32)   # host list
                    devs = np.asarray(jax.devices()[:n])  # host metadata
                    return pt, devs, int(n)
            """,
        }, ["host-sync"])
        assert fs == []

    def test_outside_hot_path_stays_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "serve/fine.py": """
                import jax

                def read(x):
                    return jax.device_get(x)
            """,
        }, ["host-sync"])
        assert fs == []

    def test_declared_readback_site_allowlisted(self, tmp_path):
        fs = check(tmp_path, {
            "engine/runner.py": """
                import jax

                class ModelRunner:
                    def wait_step(self, packs):
                        return jax.device_get(packs)

                    def other(self, packs):
                        return jax.device_get(packs)
            """,
        }, ["host-sync"])
        # Two identical device_gets; only the one OUTSIDE wait_step fires.
        assert len(fs) == 1 and fs[0].code == "HS001"
        assert fs[0].line == 9  # the `other` method's call, not wait_step's

    def test_the_early_dispatch_is_no_second_readback_site(self, tmp_path):
        """The pipelined step dispatches the next program from
        ``wait_step``'s ``at_ready`` hook, between the wait and the readback.
        The hook is the engine's code: a sync there would put the readback
        back in front of the dispatch, and is flagged like any other; the one
        declared site of a step's readback is still ``wait_step`` itself."""
        from llmd_tpu.analysis.checkers.host_sync import ALLOWED_SITES

        fs = check(tmp_path, {
            "engine/runner.py": """
                import jax

                class ModelRunner:
                    def wait_step(self, packs, at_ready=None):
                        jax.block_until_ready(packs)
                        if at_ready is not None:
                            at_ready()
                        return jax.device_get(packs)
            """,
            "engine/engine.py": """
                import jax

                class LLMEngine:
                    def _dispatch_early(self, slot):
                        return jax.device_get(slot)
            """,
        }, ["host-sync"])
        assert [(f.code, f.path.rsplit("/", 1)[-1]) for f in fs] == [("HS001", "engine.py")]
        assert {q for f, q in ALLOWED_SITES if f == "runner.py"} == {
            "ModelRunner.wait_step", "ModelRunner.download_pages"}

    def test_pragma_suppresses_with_reason(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import jax

                def read(x):
                    # llmd: allow(host-sync) -- admin surface, off the step loop
                    return jax.device_get(x)
            """,
        }, ["host-sync"])
        assert fs == []

    def test_pragma_without_reason_is_a_finding(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import jax

                def read(x):
                    # llmd: allow(host-sync)
                    return jax.device_get(x)
            """,
        }, ["host-sync", "pragma"])
        assert codes(fs) == {"PRAGMA001"}

    def test_pragma_unknown_rule_is_a_finding(self, tmp_path):
        fs = check(tmp_path, {
            "engine/x.py": """
                # llmd: allow(no-such-rule) -- because
                X = 1
            """,
        }, ["host-sync", "pragma"])
        assert codes(fs) == {"PRAGMA002"}


# ------------------------------------------------------------------ #
# trace-discipline


class TestTraceDiscipline:
    def test_per_call_jit_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import jax

                class R:
                    def step(self, f, x):
                        return jax.jit(f)(x)
            """,
        }, ["trace-discipline"])
        assert codes(fs) == {"TD001"}

    def test_construction_contexts_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "engine/good.py": """
                import functools
                import jax

                @jax.jit
                def top(x):
                    return x

                class R:
                    def __init__(self):
                        self._fwd = self._build_forward()

                    def _build_forward(self):
                        return jax.jit(lambda x: x)

                    def _alloc_pool(self):
                        return jax.jit(lambda: 0)()

                    @functools.cached_property
                    def _gather(self):
                        return jax.jit(lambda kv: kv)
            """,
        }, ["trace-discipline"])
        assert fs == []

    def test_static_argnames_mismatch_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import functools
                import jax

                @functools.partial(jax.jit, static_argnames=("no_such_arg",))
                def f(x, flag=False):
                    return x
            """,
        }, ["trace-discipline"])
        assert codes(fs) == {"TD002"}

    def test_donate_argnums_out_of_range_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                import functools
                import jax

                @functools.partial(jax.jit, donate_argnums=(3,))
                def f(x, y):
                    return x + y
            """,
        }, ["trace-discipline"])
        assert codes(fs) == {"TD003"}

    def test_valid_static_and_donate_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "engine/good.py": """
                import functools
                import jax

                @functools.partial(
                    jax.jit, donate_argnums=(1, 2) if True else (1,),
                    static_argnames=("all_greedy",),
                )
                def f(params, kv, swa, all_greedy=False):
                    return kv
            """,
        }, ["trace-discipline"])
        assert fs == []

    def test_kwargs_only_partial_call_form_does_not_crash(self, tmp_path):
        # partial(jax.jit, donate_argnums=0) as a call expression has no
        # positional target to cross-check; must not IndexError.
        fs = check(tmp_path, {
            "engine/good.py": """
                from functools import partial
                import jax

                class R:
                    def _build_step(self, f):
                        step = partial(jax.jit, donate_argnums=0)
                        return step(f)
            """,
        }, ["trace-discipline"])
        assert fs == []

    def test_unbucketed_dispatch_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                _OP_PREFILL = 1

                class R:
                    def dispatch(self, seqs):
                        B = len(seqs)   # ad-hoc shape
                        return self._sync(_OP_PREFILL, B, 1, False, {})
            """,
        }, ["trace-discipline"])
        assert codes(fs) == {"TD004"}

    def test_unbucketed_async_dispatch_fires(self, tmp_path):
        fs = check(tmp_path, {
            "engine/bad.py": """
                _OP_DECODE = 2

                class R:
                    async def dispatch(self, seqs):
                        B = len(seqs)   # ad-hoc shape, async path
                        return self._sync(_OP_DECODE, B, 1, False, {})
            """,
        }, ["trace-discipline"])
        assert codes(fs) == {"TD004"}

    def test_bucketed_staged_and_warm_dispatches_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "engine/good.py": """
                _OP_PREFILL, _OP_DECODE = 1, 2

                def pad_to_bucket(n, buckets):
                    return n

                class StagedDecode:
                    pass

                class R:
                    def dispatch(self, seqs):
                        B = pad_to_bucket(len(seqs), (8,))
                        return self._sync(_OP_PREFILL, B, 1, False, {})

                    def dispatch_staged(self, staged: StagedDecode):
                        return self._sync(_OP_DECODE, staged.B, 1, False, {})

                    def _warm_decode(self, B):
                        return self._sync(_OP_DECODE, B, 1, False, {})
            """,
        }, ["trace-discipline"])
        assert fs == []


# ------------------------------------------------------------------ #
# lockstep

_MINI_RUNNER = """
    _OP_STOP, _OP_PREFILL, _OP_DECODE = 0, 1, 2

    class ModelRunner:
        def __init__(self):
            self._forward = self._build_forward()

        def _build_forward(self):
            return lambda: None

        def _sync(self, op, B, QK, greedy, arrays):
            return arrays

        def dispatch_prefill(self):
            return self._sync(_OP_PREFILL, 8, 1, False, {})

        def dispatch_decode(self):
            return self._sync(_OP_DECODE, 8, 1, False, {})

        def _exec_prefill(self, arrays):
            return self._forward()

        def _exec_decode(self, arrays):
            return self._forward()

        def follower_loop(self):
            while True:
                op = self._recv()
                if op == _OP_STOP:
                    return
                if op == _OP_PREFILL:
                    self._exec_prefill({})
                elif op == _OP_DECODE:
                    self._exec_decode({})
                else:
                    raise RuntimeError(f"unknown opcode {op}")
"""


class TestLockstep:
    def test_clean_mini_runner(self, tmp_path):
        fs = check(tmp_path, {"engine/runner.py": _MINI_RUNNER}, ["lockstep"])
        assert fs == []

    def test_missing_follower_arm_fires(self, tmp_path):
        src = _MINI_RUNNER.replace(
            "                elif op == _OP_DECODE:\n"
            "                    self._exec_decode({})\n", "")
        fs = check(tmp_path, {"engine/runner.py": src}, ["lockstep"])
        assert "LS001" in codes(fs)

    def test_fallthrough_else_fires(self, tmp_path):
        src = _MINI_RUNNER.replace(
            "                else:\n"
            '                    raise RuntimeError(f"unknown opcode {op}")\n',
            "                else:\n"
            "                    self._exec_decode({})\n")
        fs = check(tmp_path, {"engine/runner.py": src}, ["lockstep"])
        assert "LS002" in codes(fs)

    def test_unbroadcast_opcode_fires(self, tmp_path):
        src = _MINI_RUNNER.replace(
            "    _OP_STOP, _OP_PREFILL, _OP_DECODE = 0, 1, 2",
            "    _OP_STOP, _OP_PREFILL, _OP_DECODE = 0, 1, 2\n"
            "    _OP_GHOST = 9",
        )
        fs = check(tmp_path, {"engine/runner.py": src}, ["lockstep"])
        # No follower arm AND never broadcast.
        assert codes(fs) == {"LS001", "LS003"}

    def test_magic_number_sync_fires(self, tmp_path):
        src = _MINI_RUNNER.replace(
            "return self._sync(_OP_DECODE, 8, 1, False, {})",
            "return self._sync(2, 8, 1, False, {})",
        )
        fs = check(tmp_path, {"engine/runner.py": src}, ["lockstep"])
        assert "LS004" in codes(fs)
        assert "LS003" in codes(fs)  # _OP_DECODE no longer broadcast

    def test_step_callable_outside_exec_fires(self, tmp_path):
        src = _MINI_RUNNER.replace(
            "        def dispatch_decode(self):\n"
            "            return self._sync(_OP_DECODE, 8, 1, False, {})",
            "        def dispatch_decode(self):\n"
            "            self._forward()  # bypasses the broadcast\n"
            "            return self._sync(_OP_DECODE, 8, 1, False, {})",
        )
        fs = check(tmp_path, {"engine/runner.py": src}, ["lockstep"])
        assert "LS005" in codes(fs)

    def test_step_callables_bind_to_follower_loop_class(self, tmp_path):
        # A helper class with its own __init__ ABOVE the runner must not
        # hijack the _build_* attribute search LS005 depends on.
        src = "    class Helper:\n        def __init__(self):\n" \
              "            self.x = 1\n\n" + _MINI_RUNNER
        bad = src.replace(
            "        def dispatch_decode(self):\n"
            "            return self._sync(_OP_DECODE, 8, 1, False, {})",
            "        def dispatch_decode(self):\n"
            "            self._forward()  # bypasses the broadcast\n"
            "            return self._sync(_OP_DECODE, 8, 1, False, {})",
        )
        fs = check(tmp_path, {"engine/runner.py": bad}, ["lockstep"])
        assert "LS005" in codes(fs)

    def test_real_runner_missing_verify_arm_fails(self, tmp_path):
        """Acceptance pin: deleting one follower dispatch arm from the
        REAL runner makes the suite exit nonzero."""
        src = RUNNER.read_text()
        arm = (
            "            elif op == _OP_VERIFY:\n"
            "                self._exec_verify(arrays, bool(greedy))\n"
        )
        assert arm in src, "follower_loop layout changed; update this pin"
        mutated = src.replace(arm, "")
        (tmp_path / "engine").mkdir(parents=True)
        (tmp_path / "engine/runner.py").write_text(mutated)
        findings, _ = run_analysis(tmp_path, [str(tmp_path)], ["lockstep"])
        assert any(
            f.code == "LS001" and "_OP_VERIFY" in f.message for f in findings
        )

    def test_real_runner_verify_never_broadcast_fails(self, tmp_path):
        """The leader's side of the _OP_VERIFY arm: a REAL runner whose
        verify dispatches broadcast another family's opcode must fail
        the build (followers would mirror the prefill program while the
        leader runs verify — the lockstep collective stream
        desynchronizes)."""
        src = RUNNER.read_text()
        sites = ("                _OP_VERIFY, staged.B, staged.q,",
                 "self._sync_locked(_OP_VERIFY, B, Q,")
        assert all(src.count(site) == 1 for site in sites), (
            "verify dispatch layout changed; update this pin"
        )
        for site in sites:
            src = src.replace(site, site.replace("_OP_VERIFY", "_OP_PREFILL"))
        (tmp_path / "engine").mkdir(parents=True)
        (tmp_path / "engine/runner.py").write_text(src)
        findings, _ = run_analysis(tmp_path, [str(tmp_path)], ["lockstep"])
        assert any(
            f.code == "LS003" and "_OP_VERIFY" in f.message for f in findings
        )

    def test_real_runner_missing_unified_arm_fails(self, tmp_path):
        """Acceptance pin for the unified single-dispatch step's opcode:
        deleting the _OP_UNIFIED follower arm from the REAL runner must
        fail the build — on a multi-host engine every mixed step rides
        this opcode, so a follower without the arm desynchronizes the
        lockstep collective stream on the FIRST mixed step."""
        src = RUNNER.read_text()
        arm = "            elif op == _OP_UNIFIED:\n"
        assert arm in src, "follower_loop layout changed; update this pin"
        lines = src.splitlines(keepends=True)
        i = lines.index(arm)
        # Drop the arm plus its body (comment + exec call).
        del lines[i : i + 4]
        (tmp_path / "engine").mkdir(parents=True)
        (tmp_path / "engine/runner.py").write_text("".join(lines))
        findings, _ = run_analysis(tmp_path, [str(tmp_path)], ["lockstep"])
        assert any(
            f.code == "LS001" and "_OP_UNIFIED" in f.message
            for f in findings
        )

    def test_real_runner_missing_flat_arm_fails(self, tmp_path):
        """Acceptance pin for the flattened-token step's opcode: with
        --ragged-qlens on (the default) EVERY window=1 step rides
        _OP_FLAT, so deleting its follower arm from the REAL runner must
        fail the build — a follower without the arm desynchronizes the
        lockstep collective stream on the first step."""
        src = RUNNER.read_text()
        arm = (
            "            elif op == _OP_FLAT:\n"
            "                self._exec_flat(arrays, bool(greedy))\n"
        )
        assert arm in src, "follower_loop layout changed; update this pin"
        mutated = src.replace(arm, "")
        (tmp_path / "engine").mkdir(parents=True)
        (tmp_path / "engine/runner.py").write_text(mutated)
        findings, _ = run_analysis(tmp_path, [str(tmp_path)], ["lockstep"])
        assert any(
            f.code == "LS001" and "_OP_FLAT" in f.message for f in findings
        )

    def test_real_runner_is_clean(self):
        findings, _ = run_analysis(REPO, [str(RUNNER)], ["lockstep"])
        assert findings == []


# ------------------------------------------------------------------ #
# metrics-parity

_METRICS_GOOD = {
    "llmd_tpu/serve/metrics.py": """
        def render_metrics(stats, model_name):
            gauges = {"queue_depth": stats.queue_depth}
            counters = {}
            counters["steps_total"] = stats.steps_total
            return gauges, counters
    """,
    "llmd_tpu/engine/stats.py": """
        class EngineStats:
            queue_depth: int = 0
            steps_total: int = 0
    """,
    "observability/dash.json": json.dumps({
        "panels": [{"targets": [
            {"expr": "vllm:queue_depth"},
            {"expr": "rate(llmd:steps_total[5m])"},
        ]}],
    }),
    "docs/architecture/observability.md":
        "`queue_depth` and `steps_total` are emitted.\n",
}


class TestMetricsParity:
    def test_aligned_surfaces_stay_quiet(self, tmp_path):
        fs = check(tmp_path, dict(_METRICS_GOOD), ["metrics-parity"])
        assert fs == []

    def test_emitted_but_no_dashboard_fires(self, tmp_path):
        files = dict(_METRICS_GOOD)
        files["observability/dash.json"] = json.dumps({
            "panels": [{"targets": [{"expr": "vllm:queue_depth"}]}],
        })
        fs = check(tmp_path, files, ["metrics-parity"])
        assert codes(fs) == {"MP001"}

    def test_emitted_but_undocumented_fires(self, tmp_path):
        files = dict(_METRICS_GOOD)
        files["docs/architecture/observability.md"] = "`queue_depth` only.\n"
        fs = check(tmp_path, files, ["metrics-parity"])
        assert codes(fs) == {"MP002"}

    def test_dashboard_references_unemitted_fires(self, tmp_path):
        files = dict(_METRICS_GOOD)
        files["observability/dash.json"] = json.dumps({
            "panels": [{"targets": [
                {"expr": "vllm:queue_depth"},
                {"expr": "rate(llmd:steps_total[5m])"},
                {"expr": "vllm:renamed_away_total"},
            ]}],
        })
        fs = check(tmp_path, files, ["metrics-parity"])
        assert codes(fs) == {"MP003"}

    def test_stats_field_never_exposed_fires(self, tmp_path):
        files = dict(_METRICS_GOOD)
        files["llmd_tpu/engine/stats.py"] = """
            class EngineStats:
                queue_depth: int = 0
                steps_total: int = 0
                silent_stat: int = 0
        """
        fs = check(tmp_path, files, ["metrics-parity"])
        assert codes(fs) == {"MP004"}
        assert any("silent_stat" in f.message for f in fs)

    def test_histogram_suffixes_canonicalize(self, tmp_path):
        files = dict(_METRICS_GOOD)
        files["observability/dash.json"] = json.dumps({
            "panels": [{"targets": [
                {"expr": "vllm:queue_depth"},
                # _sum/_count fold onto the emitted base name
                {"expr": "llmd:steps_total_sum / llmd:steps_total_count"},
            ]}],
        })
        fs = check(tmp_path, files, ["metrics-parity"])
        assert fs == []


# ------------------------------------------------------------------ #
# config-parity

_CONFIG_GOOD = {
    "llmd_tpu/config.py": """
        import dataclasses

        @dataclasses.dataclass
        class SchedulerConfig:
            max_num_seqs: int = 64
            page_size: int = 16

        @dataclasses.dataclass
        class EngineConfig:
            seed: int = 0
    """,
    "llmd_tpu/serve/__main__.py": """
        import argparse

        def build_parser():  # EngineConfig consumer
            p = argparse.ArgumentParser()
            p.add_argument("--max-num-seqs", type=int, default=64)
            p.add_argument("--block-size", type=int, default=16)
            p.add_argument("--host", default="0.0.0.0")
            return p
    """,
    "docs/flags.md": "`--max-num-seqs`, `--block-size`, `--host`.\n",
}


class TestConfigParity:
    def test_aligned_stays_quiet(self, tmp_path):
        fs = check(tmp_path, dict(_CONFIG_GOOD), ["config-parity"])
        assert fs == []

    def test_flag_without_field_fires(self, tmp_path):
        files = dict(_CONFIG_GOOD)
        files["llmd_tpu/serve/__main__.py"] = files[
            "llmd_tpu/serve/__main__.py"
        ].replace(
            'p.add_argument("--max-num-seqs", type=int, default=64)',
            'p.add_argument("--max-num-seqs", type=int, default=64)\n'
            '            p.add_argument("--renamed-knob", type=int)',
        )
        files["docs/flags.md"] += "`--renamed-knob`.\n"
        fs = check(tmp_path, files, ["config-parity"])
        assert codes(fs) == {"CP001"}

    def test_undocumented_flag_fires(self, tmp_path):
        files = dict(_CONFIG_GOOD)
        files["docs/flags.md"] = "`--max-num-seqs`, `--host` only.\n"
        fs = check(tmp_path, files, ["config-parity"])
        assert codes(fs) == {"CP003"}
        assert any("--block-size" in f.message for f in fs)

    def test_real_flag_map_targets_exist(self):
        """CP002 guard on the live tree: every FLAG_FIELD_MAP target is
        a real config.py field (a rename there must update the map)."""
        findings, _ = run_analysis(
            REPO,
            [str(REPO / "llmd_tpu/serve/__main__.py"),
             str(REPO / "llmd_tpu/config.py"),
             str(REPO / "docs"), str(REPO / "README.md")],
            ["config-parity"],
        )
        assert findings == []


# ------------------------------------------------------------------ #
# envvars (framework checker; the scripts/lint-envvars.py shim is
# covered by tests/test_deploy.py::test_envvar_lint)


class TestEnvvars:
    def test_undeclared_use_fires(self, tmp_path):
        fs = check(tmp_path, {
            "deploy/bad.sh": """
                #!/bin/bash
                echo "$UNDECLARED_THING"
            """,
        }, ["envvars"])
        assert codes(fs) == {"EV001"}

    def test_declared_uses_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "deploy/good.sh": """
                #!/bin/bash
                # env: EXTRA_VAR
                : "${REQUIRED:?usage}"
                DEFAULTED="${DEFAULTED:-x}"
                ASSIGNED=1
                echo "$REQUIRED $DEFAULTED $ASSIGNED $EXTRA_VAR $HOME"
            """,
        }, ["envvars"])
        assert fs == []

    def test_pragma_in_markdown_is_inert(self, tmp_path):
        # Docs may quote pragma examples (even malformed ones) without
        # tripping the hygiene rules — `#` is not a comment in markdown.
        fs = check(tmp_path, {
            "docs/example.md":
                "Bad form (missing reason):\n"
                "`# llmd: allow(host-sync)`\n"
                "`# llmd: allow(imaginary-rule) -- why`\n",
        }, ["pragma"])
        assert fs == []

    def test_pragma_suppresses_in_shell(self, tmp_path):
        fs = check(tmp_path, {
            "deploy/bad.sh": """
                #!/bin/bash
                # llmd: allow(envvars) -- injected by the operator docs
                echo "$OPERATOR_PROVIDED"
            """,
        }, ["envvars"])
        assert fs == []


# ------------------------------------------------------------------ #
# the standing gate + CLI surface


class TestBroadExcept:
    """faults discipline (PR 7): broad excepts on the serving stack must
    re-raise, leave a failure-counter trail, or carry a pragma."""

    def test_silent_swallow_fires(self, tmp_path):
        fs = check(tmp_path, {
            "kvtransfer/bad.py": """
                import logging

                def stage(x):
                    try:
                        return x.download()
                    except Exception:
                        logging.getLogger(__name__).exception("oops")
            """,
        }, ["broad-except"])
        assert codes(fs) == {"FD001"}

    def test_bare_except_and_tuple_forms_fire(self, tmp_path):
        fs = check(tmp_path, {
            "serve/bad.py": """
                def a(x):
                    try:
                        return x()
                    except:
                        pass

                def b(x):
                    try:
                        return x()
                    except (ValueError, Exception):
                        pass
            """,
        }, ["broad-except"])
        assert [f.code for f in fs] == ["FD001", "FD001"]

    def test_reraise_counter_and_pragma_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "epp/good.py": """
                class C:
                    def reraises(self, x):
                        try:
                            return x()
                        except Exception:
                            self.cleanup()
                            raise

                    def counted(self, x):
                        try:
                            return x()
                        except Exception:
                            self.pull_failures += 1
                            return None

                    def counted_subscript(self, x):
                        try:
                            return x()
                        except Exception:
                            self.transfer_failures[("a", "b")] += 1
                            return None

                    def blessed(self, x):
                        try:
                            return x()
                        # llmd: allow(broad-except) -- best-effort test path
                        except Exception:
                            return None
            """,
        }, ["broad-except"])
        assert fs == []

    def test_named_tuples_and_out_of_scope_dirs_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "kvstore/good.py": """
                def f(x):
                    try:
                        return x()
                    except (ValueError, OSError, TimeoutError):
                        return None
            """,
            # autoscale/ is NOT a serving-stack scope dir
            "autoscale/fine.py": """
                def f(x):
                    try:
                        return x()
                    except Exception:
                        return None
            """,
        }, ["broad-except"])
        assert fs == []

    def test_real_serving_tree_is_clean(self):
        findings, _ = run_analysis(REPO, [
            str(REPO / "llmd_tpu/serve"), str(REPO / "llmd_tpu/engine"),
            str(REPO / "llmd_tpu/kvtransfer"), str(REPO / "llmd_tpu/epp"),
            str(REPO / "llmd_tpu/kvstore"),
        ], ["broad-except"])
        assert findings == []


class TestDirectClock:
    """clock discipline (fleet soak): the control stack reads time via
    the llmd_tpu.clock seam so the simulator can drive it on virtual
    time — direct time.time()/time.monotonic() in scope dirs fires."""

    def test_direct_calls_fire(self, tmp_path):
        fs = check(tmp_path, {
            "epp/bad.py": """
                import time

                def deadline():
                    return time.monotonic() + 10.0

                def stamp():
                    return time.time()
            """,
        }, ["direct-clock"])
        assert [f.code for f in fs] == ["CK001", "CK001"]

    def test_alias_and_reference_forms_fire(self, tmp_path):
        fs = check(tmp_path, {
            # an aliased import and a bare function REFERENCE (e.g. a
            # dataclass default_factory) both split the clock plane
            "autoscale/bad.py": """
                import time as _time
                import dataclasses

                @dataclasses.dataclass
                class S:
                    t: float = dataclasses.field(default_factory=_time.monotonic)
            """,
            "predictor/bad.py": """
                from time import monotonic

                def now():
                    return monotonic()
            """,
        }, ["direct-clock"])
        assert [f.code for f in fs] == ["CK001", "CK001"]

    def test_seam_sleep_and_out_of_scope_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "epp/good.py": """
                import time

                from llmd_tpu import clock

                def deadline():
                    return clock.monotonic() + 10.0

                def backoff():
                    time.sleep(0.1)  # blocking is visible, not a clock read
            """,
            # engine/ is hot-path scope, not control-plane scope
            "engine/fine.py": """
                import time

                def stamp():
                    return time.monotonic()
            """,
            "fleetsim/blessed.py": """
                import time

                def wall():
                    # llmd: allow(direct-clock) -- wall time of the run itself
                    return time.monotonic()
            """,
        }, ["direct-clock"])
        assert fs == []

    def test_real_control_tree_is_clean(self):
        findings, _ = run_analysis(REPO, [
            str(REPO / "llmd_tpu/epp"), str(REPO / "llmd_tpu/autoscale"),
            str(REPO / "llmd_tpu/predictor"),
            str(REPO / "llmd_tpu/fleetsim"),
        ], ["direct-clock"])
        assert findings == []


class TestTreeGate:
    def test_tree_is_clean(self):
        """THE gate: the repo's own invariants hold. A finding here means
        either fix the violation or pragma it with a written reason."""
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--json"],
            capture_output=True, text=True, cwd=REPO,
        )
        payload = json.loads(out.stdout)
        assert out.returncode == 0, out.stdout + out.stderr
        assert payload["findings"] == []
        assert payload["files"] > 100  # the scan actually covered the tree

    def test_cli_nonzero_on_findings(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/bad.py").write_text(
            "import jax\n\ndef f(x):\n    return jax.device_get(x)\n"
        )
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--json",
             "--root", str(tmp_path), str(tmp_path)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 1
        payload = json.loads(out.stdout)
        assert [f["code"] for f in payload["findings"]] == ["HS001"]

    def test_cli_list_rules(self):
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--list-rules"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        for rule in (
            "host-sync", "trace-discipline", "lockstep", "metrics-parity",
            "config-parity", "envvars", "broad-except", "direct-clock",
            "pragma",
        ):
            assert rule in out.stdout

    def test_paths_outside_root_are_scanned_not_crashed(self, tmp_path):
        outside = tmp_path / "elsewhere/engine"
        outside.mkdir(parents=True)
        (outside / "bad.py").write_text(
            "import jax\n\ndef f(x):\n    return jax.device_get(x)\n"
        )
        root = tmp_path / "root"
        root.mkdir()
        findings, _ = run_analysis(root, [str(outside)], ["host-sync"])
        assert [f.code for f in findings] == ["HS001"]
        assert findings[0].path.startswith("/")  # reported absolute

    def test_cli_unknown_rule_is_usage_error(self):
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--rules", "nope"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 2
        assert "unknown rule" in out.stderr

    def test_cli_empty_scan_set_is_an_error(self, tmp_path):
        """0 files scanned = 0 invariants enforced: a wrong cwd/--root
        must fail loudly, not hand CI a green exit."""
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--root",
             str(tmp_path)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 2
        assert "scan set is empty" in out.stderr

    def test_analysis_imports_without_jax(self):
        """The CI lint job runs the suite with NO third-party packages:
        importing the analyzer must not pull in jax/numpy/yaml."""
        out = subprocess.run(
            [sys.executable, "-c", (
                "import sys\n"
                "import llmd_tpu.analysis.checkers\n"
                "bad = [m for m in ('jax', 'numpy', 'yaml', 'aiohttp')\n"
                "       if m in sys.modules]\n"
                "assert not bad, bad\n"
            )],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0, out.stdout + out.stderr


# ------------------------------------------------------------------ #
# concurrency (CC001-CC004) — docs/architecture/static-analysis.md


class TestGuardedBy:
    """CC001: annotated attrs only under their guard."""

    def test_unlocked_access_fires(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._buf = []  # llmd: guarded_by(_lock)

                    def bad(self):
                        return len(self._buf)
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC001"}

    def test_locked_access_and_init_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._buf = []  # llmd: guarded_by(_lock)
                        self._buf.append(0)  # __init__ is exempt

                    def good(self):
                        with self._lock:
                            return len(self._buf)
            """,
        }, ["concurrency"])
        assert fs == []

    def test_annotation_on_comment_line_above(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        # llmd: guarded_by(_lock)
                        self._big = {}

                    def bad(self):
                        return self._big
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC001"}

    def test_trailing_annotation_does_not_leak_to_next_line(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._buf = []  # llmd: guarded_by(_lock)
                        self._free = 0  # NOT annotated

                    def fine(self):
                        return self._free
            """,
        }, ["concurrency"])
        assert fs == []

    def test_annassign_annotation_registers(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._m: dict[str, int] = {}  # llmd: guarded_by(_lock)

                    def bad(self):
                        return self._m.get("x")
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC001"}

    def test_condition_over_lock_satisfies_guard(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._cond = threading.Condition(self._lock)
                        self._buf = []  # llmd: guarded_by(_lock)

                    def good(self):
                        with self._cond:
                            self._buf.append(1)
                            self._cond.notify_all()
            """,
        }, ["concurrency"])
        assert fs == []

    def test_locked_suffix_method_body_is_exempt(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._buf = []  # llmd: guarded_by(_lock)

                    def _drain_locked(self):
                        out, self._buf = self._buf, []
                        return out

                    def good(self):
                        with self._lock:
                            return self._drain_locked()
            """,
        }, ["concurrency"])
        assert fs == []

    def test_unlocked_call_to_locked_helper_fires(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._buf = []  # llmd: guarded_by(_lock)

                    def _drain_locked(self):
                        out, self._buf = self._buf, []
                        return out

                    def bad(self):
                        return self._drain_locked()
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC001"}

    def test_locked_decorator_counts_as_holding(self, tmp_path):
        fs = check(tmp_path, {
            "engine/m.py": """
                import functools
                import threading

                def _locked(fn):
                    @functools.wraps(fn)
                    def inner(self, *a, **k):
                        with self._lock:
                            return fn(self, *a, **k)
                    return inner

                class C:
                    def __init__(self):
                        self._lock = threading.RLock()
                        self._free = {}  # llmd: guarded_by(_lock)

                    @_locked
                    def good(self):
                        return len(self._free)
            """,
        }, ["concurrency"])
        assert fs == []

    def test_pragma_suppresses_with_reason(self, tmp_path):
        fs = check(tmp_path, {
            "events/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._hot = False  # llmd: guarded_by(_lock)

                    def peek(self):
                        # llmd: allow(concurrency) -- single atomic bool read for a probe
                        return self._hot
            """,
        }, ["concurrency"])
        assert fs == []


class TestLockOrder:
    """CC002: the whole-tree lock-acquisition graph stays acyclic."""

    def test_inverted_nesting_fires(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def ab(self):
                        with self._a_lock:
                            with self._b_lock:
                                pass

                    def ba(self):
                        with self._b_lock:
                            with self._a_lock:
                                pass
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC002"}
        assert len(fs) == 2  # every edge of the cycle attributed

    def test_consistent_nesting_stays_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def ab(self):
                        with self._a_lock:
                            with self._b_lock:
                                pass

                    def ab2(self):
                        with self._a_lock:
                            with self._b_lock:
                                pass
            """,
        }, ["concurrency"])
        assert fs == []

    def test_call_edge_cycle_fires(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._a_lock = threading.Lock()
                        self._b_lock = threading.Lock()

                    def holds_a_then_calls(self):
                        with self._a_lock:
                            self.takes_b()

                    def takes_b(self):
                        with self._b_lock:
                            pass

                    def holds_b_then_a(self):
                        with self._b_lock:
                            with self._a_lock:
                                pass
            """,
        }, ["concurrency"])
        assert "CC002" in codes(fs)

    def test_rlock_reentry_is_not_an_edge(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.RLock()

                    def reenter(self):
                        with self._lock:
                            with self._lock:
                                pass
            """,
        }, ["concurrency"])
        assert fs == []

    def test_same_attr_in_different_classes_is_not_a_cycle(self, tmp_path):
        """Node identity is (module, class, attr): two classes nesting
        their OWN _lock under each other's naming twin share no lock."""
        fs = check(tmp_path, {
            "serve/m.py": """
                import threading

                class A:
                    def __init__(self):
                        self._x_lock = threading.Lock()
                        self._y_lock = threading.Lock()

                    def xy(self):
                        with self._x_lock:
                            with self._y_lock:
                                pass

                class B:
                    def __init__(self):
                        self._x_lock = threading.Lock()
                        self._y_lock = threading.Lock()

                    def yx(self):
                        with self._y_lock:
                            with self._x_lock:
                                pass
            """,
        }, ["concurrency"])
        assert fs == []


class TestAsyncBlocking:
    """CC003: event-loop coroutines never block or await under a lock."""

    def test_await_under_lock_fires(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import asyncio
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()

                    async def bad(self):
                        with self._lock:
                            await asyncio.sleep(0.1)
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC003"}

    def test_time_sleep_and_bare_acquire_fire(self, tmp_path):
        fs = check(tmp_path, {
            "epp/m.py": """
                import time
                import threading

                _lock = threading.Lock()

                async def bad():
                    time.sleep(0.5)
                    _lock.acquire()
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC003"}
        assert len(fs) == 2

    def test_asyncio_sleep_and_lock_outside_await_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import asyncio
                import threading

                class C:
                    def __init__(self):
                        self._lock = threading.Lock()

                    async def good(self):
                        with self._lock:
                            x = 1
                        await asyncio.sleep(0.1)
                        return x
            """,
        }, ["concurrency"])
        assert fs == []

    def test_outside_async_scope_stays_quiet(self, tmp_path):
        """kvstore/ async defs are client-side helpers, not serving
        event loops — out of CC003 scope."""
        fs = check(tmp_path, {
            "kvstore/m.py": """
                import time

                async def tolerated():
                    time.sleep(0.01)
            """,
        }, ["concurrency"])
        assert fs == []

    def test_nested_def_body_is_exempt(self, tmp_path):
        """A def nested in an async def runs elsewhere (executor
        thread, callback) — its blocking is not the loop's."""
        fs = check(tmp_path, {
            "serve/m.py": """
                import time

                async def good(loop):
                    def blocking_worker():
                        time.sleep(1.0)
                    await loop.run_in_executor(None, blocking_worker)
            """,
        }, ["concurrency"])
        assert fs == []


class TestLoopCalls:
    """CC004: thread-target functions use only *_threadsafe loop entry."""

    def test_call_soon_from_thread_target_fires(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import threading

                class C:
                    def start(self):
                        self._t = threading.Thread(target=self._run)
                        self._t.start()

                    def _run(self):
                        self._loop.call_soon(print, "hi")
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC004"}

    def test_threadsafe_entry_points_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import asyncio
                import threading

                class C:
                    def start(self):
                        self._t = threading.Thread(target=self._run)
                        self._t.start()

                    def _run(self):
                        self._loop.call_soon_threadsafe(print, "hi")
                        asyncio.run_coroutine_threadsafe(self._coro(), self._loop)
            """,
        }, ["concurrency"])
        assert fs == []

    def test_helper_called_from_thread_target_fires(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import asyncio
                import threading

                class C:
                    def start(self):
                        self._t = threading.Thread(target=self._run)
                        self._t.start()

                    def _run(self):
                        self._emit()

                    def _emit(self):
                        asyncio.ensure_future(self._coro())
            """,
        }, ["concurrency"])
        assert codes(fs) == {"CC004"}

    def test_loop_calls_outside_thread_targets_stay_quiet(self, tmp_path):
        fs = check(tmp_path, {
            "serve/m.py": """
                import asyncio

                class C:
                    async def serve(self):
                        loop = asyncio.get_running_loop()
                        loop.create_task(self._coro())
            """,
        }, ["concurrency"])
        assert fs == []


class TestConcurrencyRealTree:
    def test_real_tree_is_clean(self):
        findings, _ = run_analysis(
            REPO, [str(REPO / "llmd_tpu")], ["concurrency"]
        )
        assert findings == []

    def test_stripping_a_lock_from_annotated_site_fails(self, tmp_path):
        """Mutation pin: removing `with self._lock:` from a guarded-by
        annotated site in the REAL tree must turn the build red."""
        src = (REPO / "llmd_tpu/events/index.py").read_text()
        mutated = src.replace(
            "    def remove_pod(self, pod: str) -> None:\n"
            '        """Endpoint left the pool: drop everything it held."""\n'
            "        with self._lock:\n"
            "            self._clear_pod_locked(pod)\n",
            "    def remove_pod(self, pod: str) -> None:\n"
            '        """Endpoint left the pool: drop everything it held."""\n'
            "        self._clear_pod_locked(pod)\n",
        )
        assert mutated != src, "mutation target drifted; update the pin"
        (tmp_path / "events").mkdir()
        (tmp_path / "events/index.py").write_text(mutated)
        findings, _ = run_analysis(
            tmp_path, [str(tmp_path)], ["concurrency"]
        )
        assert "CC001" in {f.code for f in findings}


class TestSarifOutput:
    def test_sarif_written_alongside_stdout(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/bad.py").write_text(
            "import jax\n\ndef f(x):\n    return jax.device_get(x)\n"
        )
        sarif_path = tmp_path / "out.sarif"
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--json",
             "--sarif", str(sarif_path),
             "--root", str(tmp_path), str(tmp_path / "engine")],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 1
        doc = json.loads(sarif_path.read_text())
        assert doc["version"] == "2.1.0"
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "llmd-analysis"
        assert [r["id"] for r in run["tool"]["driver"]["rules"]] == ["HS001"]
        res = run["results"][0]
        assert res["ruleId"] == "HS001"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("engine/bad.py")
        assert loc["region"]["startLine"] >= 1
        # stdout stays the normal surface
        assert json.loads(out.stdout)["findings"]

    def test_clean_run_writes_empty_sarif(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/ok.py").write_text("x = 1\n")
        sarif_path = tmp_path / "out.sarif"
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--sarif", str(sarif_path),
             "--root", str(tmp_path), str(tmp_path / "engine")],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        doc = json.loads(sarif_path.read_text())
        assert doc["runs"][0]["results"] == []


class TestChangedOnly:
    def _git(self, cwd, *args):
        subprocess.run(
            ["git", *args], cwd=cwd, check=True, capture_output=True,
        )

    def _repo_with_clean_commit(self, tmp_path):
        self._git(tmp_path, "init", "-q")
        self._git(tmp_path, "config", "user.email", "t@t")
        self._git(tmp_path, "config", "user.name", "t")
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/committed.py").write_text("x = 1\n")
        self._git(tmp_path, "add", "-A")
        self._git(tmp_path, "commit", "-qm", "seed")
        return tmp_path

    def test_scans_only_changed_paths(self, tmp_path):
        root = self._repo_with_clean_commit(tmp_path)
        # Committed file becomes bad but UNCHANGED vs HEAD after commit;
        # a new untracked bad file must be the only thing scanned.
        (root / "engine/new_bad.py").write_text(
            "import jax\n\ndef f(x):\n    return jax.device_get(x)\n"
        )
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--json",
             "--changed-only", "--root", str(root)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 1
        payload = json.loads(out.stdout)
        assert payload["files"] == 1
        assert [f["code"] for f in payload["findings"]] == ["HS001"]

    def test_empty_diff_exits_green(self, tmp_path):
        root = self._repo_with_clean_commit(tmp_path)
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--changed-only", "--root", str(root)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        assert "no changed files" in out.stdout

    def test_changed_only_with_paths_is_usage_error(self, tmp_path):
        root = self._repo_with_clean_commit(tmp_path)
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--changed-only", "--root", str(root), "engine"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 2

    def test_not_a_repo_is_usage_error(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/x.py").write_text("x = 1\n")
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--changed-only", "--root", str(tmp_path)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 2


class TestUnusedPragmas:
    def test_stale_pragma_listed_used_pragma_not(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/m.py").write_text(
            "import jax\n"
            "\n"
            "def f(x):\n"
            "    # llmd: allow(host-sync) -- measured readback\n"
            "    return jax.device_get(x)\n"
            "\n"
            "def g(x):\n"
            "    # llmd: allow(host-sync) -- nothing here needs it\n"
            "    return x\n"
        )
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--report-unused-pragmas",
             "--root", str(tmp_path), str(tmp_path / "engine")],
            capture_output=True, text=True, cwd=REPO,
        )
        # Non-blocking surface: exit 0 even though a stale pragma exists.
        assert out.returncode == 0
        assert "m.py:8" in out.stdout
        assert "1 unused pragma(s)" in out.stdout

    def test_pragma_for_rule_not_run_is_not_reported(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/m.py").write_text(
            "def g(x):\n"
            "    # llmd: allow(host-sync) -- suppresses nothing\n"
            "    return x\n"
        )
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--report-unused-pragmas", "--rules", "concurrency",
             "--root", str(tmp_path), str(tmp_path / "engine")],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        assert "0 unused pragma(s)" in out.stdout

    def test_real_tree_has_no_unused_pragmas(self):
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--report-unused-pragmas"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        assert "0 unused pragma(s)" in out.stdout, out.stdout


# ------------------------------------------------------------------ #
# runtime lock sanitizer (llmd_tpu/analysis/sanitize.py)


class TestLockSanitizer:
    @pytest.fixture
    def san(self):
        """Arm the sanitizer for one test; leave a session-level arming
        (LLMD_LOCKSAN=1 conftest) in place but never our own."""
        from llmd_tpu.analysis import sanitize

        was_armed = sanitize.armed()
        if not was_armed:
            sanitize.arm()
        sanitize.drain_violations()
        try:
            yield sanitize
        finally:
            sanitize.drain_violations()
            if not was_armed:
                sanitize.disarm()

    def test_seeded_two_lock_inversion_caught(self, san):
        import threading

        a = threading.Lock()
        b = threading.Lock()

        def establish_ab():
            with a:
                with b:
                    pass

        t = threading.Thread(target=establish_ab)
        t.start()
        t.join()
        # The inversion: b held, then a — closes the a->b cycle.
        with b:
            with pytest.raises(san.LockOrderError, match="lock-order"):
                with a:
                    pass
        # The raising acquire released its lock: a is free afterwards
        # (and with nothing held, taking it is no new violation).
        assert a.acquire(blocking=False)
        a.release()
        vs = san.drain_violations()
        assert [v["kind"] for v in vs] == ["lock-order-cycle"]

    def test_consistent_order_stays_quiet(self, san):
        import threading

        a = threading.Lock()
        b = threading.Lock()
        for _ in range(3):
            with a:
                with b:
                    pass
        t = threading.Thread(target=lambda: a.acquire() or b.acquire())
        t.start()
        t.join()
        assert san.drain_violations() == []

    def test_rlock_reentry_is_not_an_edge(self, san):
        import threading

        r = threading.RLock()
        with r:
            with r:
                pass
        assert san.drain_violations() == []

    def test_seeded_await_under_lock_caught(self, san):
        import asyncio
        import threading

        lock = threading.Lock()

        async def bad():
            lock.acquire()  # held across the await: the seeded bug
            try:
                await asyncio.sleep(0)
            finally:
                lock.release()

        asyncio.run(bad())
        kinds = [v["kind"] for v in san.drain_violations()]
        assert "held-across-await" in kinds

    def test_lock_released_before_await_stays_quiet(self, san):
        import asyncio
        import threading

        lock = threading.Lock()

        async def good():
            with lock:
                x = 1
            await asyncio.sleep(0)
            return x

        asyncio.run(good())
        assert san.drain_violations() == []

    def test_condition_wait_keeps_held_bookkeeping(self, san):
        import threading

        cond = threading.Condition()
        done = []

        def waiter():
            with cond:
                cond.wait(timeout=5)
                done.append(True)

        t = threading.Thread(target=waiter)
        t.start()
        # Give the waiter time to park, then notify under the lock.
        import time

        time.sleep(0.05)
        with cond:
            cond.notify_all()
        t.join(timeout=5)
        assert done == [True]
        assert san.drain_violations() == []

    def test_report_shape(self, san):
        import threading

        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass
        rep = san.report()
        assert rep["armed"] is True
        assert rep["locks_created"] >= 2
        assert rep["acquisitions"] >= 2
        assert rep["max_held_depth"] >= 2
        assert any(
            e["outer"].startswith("Lock@") and e["inner"].startswith("Lock@")
            for e in rep["edges"]
        )

    def test_write_report(self, san, tmp_path):
        path = tmp_path / "locksan.json"
        out = san.write_report(str(path))
        assert out == str(path)
        assert json.loads(path.read_text())["armed"] is True

    def test_background_thread_violation_is_recorded(self, san):
        """A cycle closed on a worker thread must land in the record
        even though the raise happens (and dies) on that thread."""
        import threading

        a = threading.Lock()
        b = threading.Lock()
        with a:
            with b:
                pass

        def invert():
            with b:
                try:
                    with a:
                        pass
                except san.LockOrderError:
                    pass  # swallowed on purpose: the record must survive

        t = threading.Thread(target=invert)
        t.start()
        t.join()
        assert [v["kind"] for v in san.drain_violations()] == [
            "lock-order-cycle"
        ]


# ------------------------------------------------------------------ #
# resource lifecycle (RL001-RL003)


LIFECYCLE_RULES = [
    "release-on-all-paths", "release-pairing", "escaping-handle",
]

# A minimal declared protocol every fixture below shares: mirrors the
# real PageAllocator annotation shape (ret-handle acquire, arg-handle
# touch, arg release) plus an owns-annotated request field.
PROTO = """
    # llmd: resource(pages, recv=alloc, acquire=allocate|touch:arg, release=free, transfer=commit_page)
    class PageAllocator:
        def allocate(self, n): ...
        def touch(self, ids): ...
        def free(self, ids): ...
        def commit_page(self, pid, h): ...
        def peek(self, h): ...


    class Req:
        def __init__(self):
            self.block_ids = []  # llmd: owns(pages)
"""


class TestLifecycleRules:
    def _run(self, tmp_path, body: str):
        return check(
            tmp_path, {"engine/m.py": PROTO + body}, LIFECYCLE_RULES
        )

    def test_leak_on_return_fires_at_acquire_line(self, tmp_path):
        fs = self._run(tmp_path, """

    def f(alloc, n):
        pages = alloc.allocate(n)
        if n > 2:
            return None
        alloc.free(pages)
""")
        assert codes(fs) == {"RL001"}
        # Reported AT the acquisition so one pragma covers the site.
        assert "alloc.allocate" in (
            "\\n".join(open(str(tmp_path / "engine/m.py")).readlines()[
                fs[0].line - 1 : fs[0].line
            ])
        )

    def test_exception_edge_without_finally_fires(self, tmp_path):
        fs = self._run(tmp_path, """

    def f(alloc, runner, n):
        pages = alloc.allocate(n)
        runner.scatter(pages)
        alloc.free(pages)
""")
        assert codes(fs) == {"RL001"}
        assert "exception-capable call" in fs[0].message

    def test_try_finally_and_except_refund_stay_quiet(self, tmp_path):
        fs = self._run(tmp_path, """

    def f(alloc, runner, n):
        pages = alloc.allocate(n)
        try:
            runner.scatter(pages)
        finally:
            alloc.free(pages)

    def g(alloc, runner, n):
        slot = alloc.allocate(n)
        try:
            runner.install(slot)
        except BaseException:
            alloc.free(slot)
            raise
        alloc.commit_page(slot, n)
""")
        assert fs == []

    def test_handoff_into_owns_state_stays_quiet(self, tmp_path):
        fs = self._run(tmp_path, """

    def assign(alloc, req, n):
        req.block_ids = alloc.allocate(n)

    def extend(alloc, req, n):
        req.block_ids.extend(alloc.allocate(n))

    def kwarg(alloc, n):
        return Req(block_ids=alloc.allocate(n))
""")
        assert fs == []

    def test_transfers_marked_return_and_callee_stay_quiet(self, tmp_path):
        fs = self._run(tmp_path, """

    # llmd: transfers(pages)
    def mint(alloc, n):
        return alloc.allocate(n)

    def consume(alloc, n):
        pages = alloc.allocate(n)
        mint_sink(pages)

    # llmd: transfers(pages)
    def mint_sink(pages): ...
""")
        assert fs == []

    def test_discarded_result_and_loop_leak_fire(self, tmp_path):
        fs = self._run(tmp_path, """

    def discard(alloc, n):
        alloc.allocate(n)

    def loop(alloc, items):
        for it in items:
            pages = alloc.allocate(it)
""")
        assert [f.code for f in fs] == ["RL001", "RL001"]

    def test_guard_narrowing_stays_quiet(self, tmp_path):
        # acquire:arg protocols returning None/False mean NOT acquired:
        # the failure branch owes no release.
        fs = self._run(tmp_path, """

    def f(alloc, ids, ok):
        alloc.touch(ids)
        if not ok:
            release_elsewhere(ids)
            return None
        alloc.free(ids)
""")
        assert codes(fs) == {"RL001"}  # release_elsewhere is not a release

    def test_double_release_fires_disjoint_branches_quiet(self, tmp_path):
        fs = self._run(tmp_path, """

    def bad(alloc, n):
        pages = alloc.allocate(n)
        alloc.free(pages)
        alloc.free(pages)

    def good(alloc, n, cond):
        pages = alloc.allocate(n)
        if cond:
            alloc.free(pages)
        else:
            alloc.free(pages)
""")
        assert codes(fs) == {"RL002"}
        assert len(fs) == 1

    def test_peeked_release_fires(self, tmp_path):
        fs = self._run(tmp_path, """

    def bad(alloc, h):
        pages = alloc.peek(h)
        alloc.free(pages)
""")
        assert codes(fs) == {"RL002"}
        assert "peeked" in fs[0].message

    def test_escape_to_unannotated_state_fires(self, tmp_path):
        fs = self._run(tmp_path, """

    def stash(alloc, obj, n):
        obj.scratch = alloc.allocate(n)

    def ret(alloc, n):
        return alloc.allocate(n)
""")
        assert [f.code for f in fs] == ["RL003", "RL003"]

    def test_recv_filter_keeps_foreign_free_quiet(self, tmp_path):
        # encode/worker.py-style: store.free() is a different protocol's
        # name on a receiver the recv= hint rejects.
        fs = self._run(tmp_path, """

    def f(store, digest):
        return store.free(digest)

    def g(federation, h):
        federation.touch(h)
""")
        assert fs == []

    def test_pragma_suppresses_with_reason(self, tmp_path):
        fs = self._run(tmp_path, """

    def f(alloc, n):
        # llmd: allow(release-on-all-paths) -- resolved by the response path
        pages = alloc.allocate(n)
        send(pages)
""")
        assert fs == []

    def test_wrapped_multiline_declaration_parses(self, tmp_path):
        # The docs' grammar examples wrap the declaration across
        # comment lines; a wrapped form must enforce identically to the
        # single-line form (a silently-unparsed protocol is zero
        # enforcement with no signal).
        fs = check(tmp_path, {"engine/m.py": """
            # llmd: resource(pages, recv=alloc, acquire=allocate|touch:arg,
            #                release=free, transfer=commit_page)
            class PageAllocator:
                def allocate(self, n): ...
                def touch(self, ids): ...
                def free(self, ids): ...
                def commit_page(self, pid): ...


            def leak(alloc, n):
                pages = alloc.allocate(n)
                return None
        """}, LIFECYCLE_RULES)
        assert codes(fs) == {"RL001"}

    def test_protocol_without_acquire_is_a_finding(self, tmp_path):
        fs = check(tmp_path, {"engine/m.py": """
            # llmd: resource(widgets, release=free)
            class W:
                def free(self, x): ...
        """}, LIFECYCLE_RULES)
        assert codes(fs) == {"RL001"}
        assert "unenforceable" in fs[0].message


class TestLifecycleRealTree:
    def test_real_tree_is_clean(self):
        findings, _ = run_analysis(
            REPO, [str(REPO / "llmd_tpu")], LIFECYCLE_RULES
        )
        assert findings == []

    def test_pr13_slot_leak_mutation_fails_statically(self, tmp_path):
        """THE mutation pin: re-introducing the PR 13 AdapterPool slot
        leak — the duplicate-install loser keeping the winner's mapping
        but never refunding its own slot — must turn the build red."""
        src = (REPO / "llmd_tpu/lora/pool.py").read_text()
        mutated = src.replace(
            "                self._refund_slot_locked(slot)\n"
            "                self._lru.move_to_end(name)\n"
            "                return existing\n",
            "                self._lru.move_to_end(name)\n"
            "                return existing\n",
        )
        assert mutated != src, "mutation target drifted; update the pin"
        (tmp_path / "lora").mkdir()
        # Strip the import-time leaksan registration: the mutated copy
        # is static-analysis input, not an importable module.
        mutated = mutated[: mutated.index(
            "from llmd_tpu.analysis import sanitize"
        )]
        (tmp_path / "lora/pool.py").write_text(mutated)
        findings, _ = run_analysis(
            tmp_path, [str(tmp_path)], LIFECYCLE_RULES
        )
        assert "RL001" in {f.code for f in findings}

    def test_stale_lifecycle_pragma_is_reported(self, tmp_path):
        """--report-unused-pragmas covers the RL rules: an allow() whose
        violation was fixed shows up in the hygiene report."""
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/m.py").write_text(textwrap.dedent("""
            def f(x):
                # llmd: allow(release-on-all-paths) -- nothing here needs it
                return x
        """))
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis",
             "--report-unused-pragmas",
             "--root", str(tmp_path), str(tmp_path / "engine")],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 0
        assert "unused pragma `allow(release-on-all-paths)`" in out.stdout

    def test_rl_rules_carry_pragma_keys_in_sarif(self, tmp_path):
        (tmp_path / "engine").mkdir()
        (tmp_path / "engine/m.py").write_text(textwrap.dedent("""
            # llmd: resource(pages, recv=alloc, acquire=allocate, release=free)
            class A:
                def allocate(self, n): ...
                def free(self, ids): ...

            def f(alloc, n):
                return alloc.allocate(n)
        """))
        sarif_path = tmp_path / "out.sarif"
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--json",
             "--sarif", str(sarif_path),
             "--root", str(tmp_path), str(tmp_path / "engine")],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 1
        doc = json.loads(sarif_path.read_text())
        rules = {
            r["id"]: r for r in doc["runs"][0]["tool"]["driver"]["rules"]
        }
        assert "RL003" in rules
        assert rules["RL003"]["properties"]["pragma"].startswith(
            "# llmd: allow(escaping-handle)"
        )


# ------------------------------------------------------------------ #
# runtime leak sanitizer (LLMD_LEAKSAN)


class _ToyPool:
    """Minimal counted-protocol manager for sanitizer units."""

    def __init__(self) -> None:
        self.next = 0

    def take(self):
        self.next += 1
        return self.next

    def give(self, h):
        pass

    def publish(self, h):
        pass


class _ToyGate:
    """Minimal anon-protocol manager (flow-token shape)."""

    def grant(self):
        pass

    def release(self):
        pass


_TOYS_REGISTERED = False


def _register_toys(sanitize):
    global _TOYS_REGISTERED
    if _TOYS_REGISTERED:
        return
    _TOYS_REGISTERED = True
    sanitize.leaksan_register(
        _ToyPool, "toys",
        acquire={"take": lambda self, a, k, r: [r]},
        release={"give": lambda self, a, k, r: [a[0]]},
        transfer={"publish": lambda self, a, k, r: [a[0]]},
    )
    sanitize.leaksan_register(
        _ToyGate, "gates", mode="anon",
        acquire={"grant": lambda self, a, k, r: [None]},
        release={"release": lambda self, a, k, r: [None]},
    )


class TestLeakSanitizer:
    @pytest.fixture
    def san(self):
        from llmd_tpu.analysis import sanitize

        _register_toys(sanitize)
        was_armed = sanitize.leaksan_armed()
        if not was_armed:
            sanitize.arm_leaksan()
        sanitize.leaksan_set_test("<unit>")
        sanitize.leaksan_drain_violations()
        try:
            yield sanitize
        finally:
            sanitize.leaksan_drain_violations()
            if not was_armed:
                sanitize.disarm_leaksan()

    def test_leak_detected_with_backtrace(self, san):
        san.leaksan_set_test("t::leak")
        pool = _ToyPool()
        h = pool.take()
        leaks = san.leaksan_check_test("t::leak")
        assert len(leaks) == 1
        rec = leaks[0]
        assert rec["resource"] == "toys"
        assert rec["test"] == "t::leak"
        # the acquisition backtrace points at the take() call above
        assert any("test_static_analysis" in fr for fr in rec["stack"])
        pool.give(h)
        assert san.leaksan_check_test("t::leak") == []

    def test_release_and_transfer_are_quiet(self, san):
        san.leaksan_set_test("t::quiet")
        pool = _ToyPool()
        pool.give(pool.take())      # acquire -> release
        pool.publish(pool.take())   # acquire -> transfer (publish)
        assert san.leaksan_check_test("t::quiet") == []
        assert san.leaksan_drain_violations() == []
        # releasing a previously-published handle (unload of a resident
        # slot) is a legitimate arc, not a double release
        pool.give(2)
        assert san.leaksan_drain_violations() == []

    def test_double_release_caught(self, san):
        pool = _ToyPool()
        h = pool.take()
        pool.give(h)
        pool.give(h)
        vs = san.leaksan_drain_violations()
        assert [v["kind"] for v in vs] == ["double-release"]
        assert vs[0]["resource"] == "toys"

    def test_anon_tokens_pair_and_underflow_is_violation(self, san):
        san.leaksan_set_test("t::anon")
        gate = _ToyGate()
        gate.grant()
        gate.release()
        assert san.leaksan_check_test("t::anon") == []
        gate.release()
        vs = san.leaksan_drain_violations()
        assert [v["kind"] for v in vs] == ["release-without-acquire"]
        gate.grant()
        assert len(san.leaksan_check_test("t::anon")) == 1
        gate.release()

    def test_background_thread_leak_attributed_to_test(self, san):
        import threading

        san.leaksan_set_test("t::bg")
        pool = _ToyPool()
        t = threading.Thread(target=pool.take)
        t.start()
        t.join()
        leaks = san.leaksan_check_test("t::bg")
        assert len(leaks) == 1
        assert leaks[0]["test"] == "t::bg"
        assert leaks[0]["thread"] != "MainThread"
        pool.give(1)

    def test_dead_manager_handles_are_not_leaks(self, san):
        san.leaksan_set_test("t::dead")
        pool = _ToyPool()
        pool.take()
        del pool
        assert san.leaksan_check_test("t::dead") == []

    def test_probe_grant_expiry_is_release_not_leak(self, san):
        from llmd_tpu.epp.breaker import EndpointCircuitBreaker

        san.leaksan_set_test("t::probe")
        now = [0.0]
        b = EndpointCircuitBreaker(
            failure_threshold=1, cooldown_s=5.0, clock=lambda: now[0]
        )
        b.record_failure("a")          # trips open
        now[0] = 6.0                   # half-open
        assert b.take_probe("a")       # grant claimed
        assert len(san.leaksan_check_test("t::probe")) == 1
        now[0] = 20.0                  # grant expired: designed release
        assert san.leaksan_check_test("t::probe") == []

    def test_report_shape_and_session_cumulative(self, san, tmp_path):
        pool = _ToyPool()
        h = pool.take()
        pool.give(h)
        pool.give(h)                      # violation
        san.leaksan_drain_violations()    # per-test drain...
        rep = san.leaksan_report()
        assert rep["armed"] is True
        toys = rep["resources"]["toys"]
        assert toys["acquired"] >= 1 and toys["released"] >= 1
        assert toys["peak_outstanding"] >= 1
        # ...must NOT empty the session-cumulative artifact
        assert any(
            v["kind"] == "double-release" for v in rep["violations"]
        )
        path = tmp_path / "leaksan.json"
        assert san.write_leaksan_report(str(path)) == str(path)
        assert json.loads(path.read_text())["armed"] is True

    def test_pool_duplicate_install_race_stays_leak_free(self, san):
        """The PR 13 seam under the sanitizer: a prefetch racing a cold
        load of the same name must refund the loser's slot — free +
        resident must re-account for every slot, nothing outstanding."""
        import threading

        from llmd_tpu.lora.pool import AdapterPool

        class _Reg:
            def get(self, name):
                class Rec:
                    weights = {}
                return Rec()

        san.leaksan_set_test("t::race")
        barrier = threading.Barrier(2)

        def install(slot, weights):
            try:
                barrier.wait(timeout=5)  # both takers hold a slot here
            except threading.BrokenBarrierError:
                pass

        pool = AdapterPool(_Reg(), install, num_slots=4)
        threads = [
            threading.Thread(target=pool.install_cold, args=("same",))
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert pool.slot_of("same") is not None
        # conservation: every slot is free or resident, none in flight
        assert len(pool._free) + len(pool._slot_of) == 4
        assert san.leaksan_check_test("t::race") == []

    def test_pr13_slot_leak_mutation_caught_at_runtime(self, san):
        """Runtime mutation pin: execute pool.py with the loser-refund
        line deleted and drive the duplicate-install race — the leaked
        slot must surface as an outstanding `slots` handle."""
        import threading

        src = (REPO / "llmd_tpu/lora/pool.py").read_text()
        mutated = src.replace(
            "                self._refund_slot_locked(slot)\n"
            "                self._lru.move_to_end(name)\n"
            "                return existing\n",
            "                self._lru.move_to_end(name)\n"
            "                return existing\n",
        )
        assert mutated != src, "mutation target drifted; update the pin"
        ns: dict = {}
        exec(compile(mutated, "mutated_pool.py", "exec"), ns)  # registers
        MutPool = ns["AdapterPool"]

        class _Reg:
            def get(self, name):
                class Rec:
                    weights = {}
                return Rec()

        san.leaksan_set_test("t::mutated-race")
        barrier = threading.Barrier(2)

        def install(slot, weights):
            try:
                barrier.wait(timeout=5)
            except threading.BrokenBarrierError:
                pass

        pool = MutPool(_Reg(), install, num_slots=4)
        threads = [
            threading.Thread(target=pool.install_cold, args=("same",))
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        # the historical bug: one slot vanished from both books...
        assert len(pool._free) + len(pool._slot_of) == 3
        # ...and the sanitizer names it, with the acquisition backtrace
        leaks = san.leaksan_check_test("t::mutated-race")
        assert len(leaks) == 1
        assert leaks[0]["resource"] == "slots"
        assert leaks[0]["stack"]

    def test_changed_only_sees_protocols_from_unchanged_files(self, tmp_path):
        """--changed-only scopes WHERE findings are reported, not which
        protocol declarations exist: a changed caller of a manager whose
        `# llmd: resource(...)` lives in an UNCHANGED file is still
        checked against it."""
        subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True)
        subprocess.run(
            ["git", "config", "user.email", "t@t"], cwd=tmp_path, check=True
        )
        subprocess.run(
            ["git", "config", "user.name", "t"], cwd=tmp_path, check=True
        )
        (tmp_path / "llmd_tpu").mkdir()
        (tmp_path / "llmd_tpu/mgr.py").write_text(textwrap.dedent("""
            # llmd: resource(pages, recv=alloc, acquire=allocate, release=free)
            class PageAllocator:
                def allocate(self, n): ...
                def free(self, ids): ...
        """))
        subprocess.run(["git", "add", "-A"], cwd=tmp_path, check=True)
        subprocess.run(
            ["git", "commit", "-qm", "seed"], cwd=tmp_path, check=True
        )
        # The NEW (untracked => in the changed set) file leaks a handle.
        (tmp_path / "llmd_tpu/user.py").write_text(textwrap.dedent("""
            def f(alloc, n):
                pages = alloc.allocate(n)
                if n:
                    return None
                alloc.free(pages)
        """))
        out = subprocess.run(
            [sys.executable, "-m", "llmd_tpu.analysis", "--json",
             "--changed-only", "--root", str(tmp_path),
             "--rules", ",".join(LIFECYCLE_RULES)],
            capture_output=True, text=True, cwd=REPO,
        )
        assert out.returncode == 1, out.stdout + out.stderr
        payload = json.loads(out.stdout)
        assert [f["code"] for f in payload["findings"]] == ["RL001"]
        assert payload["findings"][0]["path"] == "llmd_tpu/user.py"
