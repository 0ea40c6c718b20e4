"""config-parity: serve CLI flags ↔ config dataclass fields ↔ docs.

The serve CLI promises vLLM-compatible flag names mapped 1:1 onto the
engine's config dataclasses (``serve/__main__.py`` docstring). Drift is
invisible at runtime: a flag whose dataclass field was renamed keeps
parsing and silently stops configuring anything, and an undocumented
flag is unusable knowledge. This checker pins the mapping.

Inputs (by convention inside the scan set): a ``__main__.py`` calling
``add_argument``, a ``config.py`` defining ``EngineConfig``, and the
markdown docs (``docs/**/*.md`` + ``README.md``).

Rules:

- CP001: a flag whose dest is neither a config dataclass field, nor in
  the declared rename map, nor a declared serving-layer-only flag.
- CP002: a rename-map entry pointing at a field that no longer exists.
- CP003: a flag never mentioned (as ``--flag-name``) anywhere in docs.
"""

from __future__ import annotations

import ast
import re

from llmd_tpu.analysis.core import Checker, Finding, Repo, register

# CLI dest -> config field, where the names intentionally differ (the
# vLLM-compatible flag name vs this engine's field name).
FLAG_FIELD_MAP = {
    "block_size": "page_size",
    "num_gpu_blocks_override": "num_blocks",
    "kv_cache_dtype": "dtype",
    "no_enable_prefix_caching": "enable_prefix_caching",
    "kv_swa_ring": "swa_ring",
    "tokenizer": "tokenizer_path",
    "kv_offload_chunks": "cpu_chunks",
    "kv_offload_fs_dir": "fs_dir",
    "kv_store_master_url": "store_master_url",
    "kv_store_segment_bytes": "store_segment_bytes",
    "kv_store_data_port": "store_data_port",
    "kv_publish_policy": "publish_policy",
    "kv_publish_min_hits": "publish_min_hits",
    "kv_decode_paging": "decode_paging",
    "kv_pager_horizon_tokens": "pager_horizon_tokens",
    "lora_adapters": "num_lora_adapters",
    "lora_pool_slots": "lora_dynamic",
    "kv_transfer_config": "kv_role",
}

# Flags that configure the serving process, not the engine config.
SERVING_ONLY = frozenset({
    "model", "served_model_name", "host", "port", "platform",
    "skip_warmup", "advertised_address", "data_parallel_rank",
    "distributed_coordinator", "distributed_num_processes",
    "distributed_process_id", "otlp_traces_endpoint", "trace_file",
    "trace_sample_ratio", "profile_dir",
})


def _cli_flags(sf) -> dict[str, int]:
    """{--flag-name: lineno} from add_argument calls."""
    flags: dict[str, int] = {}
    if sf.tree is None:
        return flags
    for node in ast.walk(sf.tree):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_argument"
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and node.args[0].value.startswith("--")
        ):
            flags.setdefault(node.args[0].value, node.lineno)
    return flags


def _config_fields(sf) -> set[str]:
    """All dataclass field names across the config module's classes."""
    fields: set[str] = set()
    if sf.tree is None:
        return fields
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.ClassDef):
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) and isinstance(
                    stmt.target, ast.Name
                ):
                    fields.add(stmt.target.id)
    return fields


@register
class ConfigParityChecker(Checker):
    name = "config-parity"
    description = (
        "every serve CLI flag maps to a live config field (or is a "
        "declared serving-layer flag) and is mentioned in the docs"
    )

    def run(self, repo: Repo) -> list[Finding]:
        mains = [
            sf for sf in repo.named("__main__.py")
            if "add_argument" in sf.text and "EngineConfig" in sf.text
        ]
        configs = [
            sf for sf in repo.named("config.py")
            if "class EngineConfig" in sf.text
        ]
        if not mains or not configs:
            return []
        msf, csf = mains[0], configs[0]
        flags = _cli_flags(msf)
        fields = _config_fields(csf)
        doc_files = [
            sf for sf in repo.files
            if sf.path.endswith(".md")
            and (sf.path.startswith("docs/") or sf.path == "README.md")
        ]
        doc_text = "\n".join(sf.text for sf in doc_files)

        findings: list[Finding] = []
        for flag, line in sorted(flags.items()):
            dest = flag[2:].replace("-", "_")
            mapped = FLAG_FIELD_MAP.get(dest)
            if dest in SERVING_ONLY:
                pass
            elif mapped is not None:
                if mapped not in fields:
                    findings.append(Finding(
                        "config-parity", "CP002", msf.path, line,
                        f"flag {flag} maps to config field {mapped!r} "
                        "which no longer exists in config.py — the flag "
                        "parses but configures nothing",
                    ))
            elif dest not in fields:
                findings.append(Finding(
                    "config-parity", "CP001", msf.path, line,
                    f"flag {flag} matches no config dataclass field, no "
                    "FLAG_FIELD_MAP rename, and no declared serving-layer "
                    "flag — if the field was renamed, update the map; if "
                    "the flag is serving-only, declare it",
                ))
            if doc_files and not re.search(
                rf"(?<![\w-]){re.escape(flag)}(?![\w-])", doc_text
            ):
                findings.append(Finding(
                    "config-parity", "CP003", msf.path, line,
                    f"flag {flag} is not mentioned anywhere under docs/ "
                    "or README.md — undocumented flags are unusable "
                    "knowledge",
                ))
        return findings
