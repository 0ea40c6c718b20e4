"""``tolerance_probe_mixer.py`` for ``references/gdn_gqa_gated_moe_share.py``:
where its tolerances come from, and the controls that have to fail them. The
comparison that decides ``correct`` (``perfbench/correctness.py`` through
``topologies/engine_gdn.py``: a state carried over a 4,096-token context, a
snapshot miss and two hits, 64 decoded tokens a prompt among the cell's other
running rows) made for MANY seeds in one process on the chip, then the SAME
system log-probs and states scored against wrong references. A builder's tool,
not part of a run; what it found is in the reference's file and in PERF.md.

    python3 perfbench/tolerance_probe_gdn.py --workload <cell> --seeds 7,2147483999

The seeds' loop, the sampling and the scoring are ``tolerance_probe_mixer``'s
(its ``main`` is run with this module's ``controls`` and ``with_float8`` in the
place of its own: the two names that are this architecture's). Per seed one
line for the sound comparison and one a control, each with ``first16``
(``correctness.py``'s 128 tokens), ``decode`` (the topology's longer decode,
pooled over the eight prompts) and ``states``; a control FAILS when any of the
three is not ``ok``.
Controls (each a reference that differs from the model in ONE way):
  state_bf16        the delta-rule state rounded to bfloat16 after every token
  beta_raw          beta = b, without its sigmoid
  decay_after       the decay applied AFTER the rank-1 update, not before it
  no_attn_gate      the attention's output gate left out
  full_rotation     RoPE over all 256 dimensions of a head, not the first 64
  shared_ungated    the shared expert without its sigmoid gate
  one_expert_fewer  63 of the 64 held experts
  snapshot_stale    behind a context, the recurrent state misses the context's
                    last page (a snapshot taken one page early; the attention
                    layers see every token)
  float8_weights    every weight rounded to float8 (e4m3) before use: the
                    nearest precision below the configuration's bfloat16
"""

from __future__ import annotations

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from perfbench import tolerance_probe_mixer as mixer  # noqa: E402
from perfbench.references import _common  # noqa: E402


def controls(ref, params, conf) -> dict:
    """name -> (params, conf) of each wrong reference."""
    held = params["layers"]["we_gate"].shape[1]
    return {
        "state_bf16": (params, dict(conf, probe_state_dtype="bfloat16")),
        "beta_raw": (params, dict(conf, probe_beta_raw=True)),
        "decay_after": (params, dict(conf, probe_decay_after=True)),
        "no_attn_gate": (params, dict(conf, probe_no_attn_gate=True)),
        "full_rotation": (params, dict(conf, probe_full_rotation=True)),
        "shared_ungated": (params, dict(conf, probe_no_shared_gate=True)),
        "one_expert_fewer": (params, dict(conf, experts_used=held - 1)),
        "snapshot_stale": (params, dict(conf, probe_stale_tokens=16)),
    }


def with_float8(ref, fn):
    """``fn()`` with every value the reference reads from the parameter tree
    rounded to float8 e4m3 first (its jitted layers traced anew)."""
    import jax
    import jax.numpy as jnp

    jitted = [ref._gdn, ref._first_state, ref._attention, ref._sparse_ffn, ref._head]
    plain = _common.f32
    _common.f32 = lambda x: jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=4, mantissa_bits=3)
    try:
        for f in jitted:
            f.clear_cache()
        return fn()
    finally:
        _common.f32 = plain
        for f in jitted:
            f.clear_cache()


def main() -> int:
    mixer.controls, mixer.with_float8 = controls, with_float8
    return mixer.main()


if __name__ == "__main__":
    sys.exit(main())
