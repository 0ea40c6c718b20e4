"""A step program's host inputs as ONE device buffer.

Every step program (prefill, verify, bucketed unified, flat, decode window)
takes a dozen or more small host arrays: the token stream, per-row
metadata, the page tables, the sampling knobs, the seeds, the flat write
plan. Handed over one by one, each is a host-to-device transfer of its own
(~0.27 ms on a v5e host whatever its size), and the sum is most of what a
step's launch costs. Here they travel as one ``int32`` buffer:

- ``step_fields`` is the ONE description of a program kind's inputs
  (name, shape, dtype), a function of the step's shape alone. The lockstep
  broadcast derives its wire format from it too (``ModelRunner.
  _payload_spec``), so leader, followers and the device agree by
  construction.
- ``PayloadLayout`` lays the fields out: each starts at a multiple of
  ``ALIGN`` words; a 4-byte element travels as its bits (a float or a
  ``uint32`` seed is bit-cast, never converted), a 1-byte element (a row
  kind, an active flag, a bool) as one word holding its value.
  ``pack`` writes host arrays through the layout, ``unpack`` — traced
  inside the step program — reads the same offsets back into arrays of the
  described shapes and dtypes.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

# Every field starts on a 128-word (512-byte, one lane row) boundary.
ALIGN = 128

# The program kinds a layout exists for.
KINDS = ("prefill", "verify", "unified", "flat", "decode")


def step_fields(
    kind: str,
    B: int,
    QK: int,
    *,
    max_pages: int,
    page: int,
    sample_cols: int,
    ring: bool,
    lora: bool,
    state: bool = False,
    runs: bool = False,
) -> list[tuple[str, tuple[int, ...], type]]:
    """(name, shape, dtype) of one step program's host inputs.

    ``B`` is the row count; ``QK`` the program kind's second shape: the
    columns a row of a prefill or verify step, the window K of a decode
    step, ``(Q_bucket << 20) | T_bucket`` of a bucketed unified step, the
    stream bucket T of a flat step. ``sample_cols`` is the unified and flat
    steps' sample width S; ``ring`` adds the sliding layers' second page
    table (and the flat step's second write plan), ``lora`` the adapter
    slots, ``state`` (flat only) each row's slot of the state pool, ``runs``
    (flat only) the attention's shared-prefix runs, a token each
    (``engine/prefix_runs.py``).

    ``tok_slot`` is each row's entry of the runner's ``last_tokens`` (the
    token the device sampled last for the row's sequence; an index past the
    array's end: the row keeps none), which every program but the verify
    step writes; ``tok_dev`` flags the rows that READ their first input
    token from there, not from the host's stream
    (``ModelRunner._token_input``).
    """
    mp = max_pages
    if kind in ("prefill", "verify"):
        spec = [
            ("tokens", (B, QK), np.int32),
            ("positions", (B, QK), np.int32),
            ("qlens", (B,), np.int32),
            ("kvlens", (B,), np.int32),
            ("page_table", (B, mp), np.int32),
            ("temp", (B,), np.float32),
            ("top_k", (B,), np.int32),
            ("top_p", (B,), np.float32),
            # Verify samples at every position, so its seeds are per
            # (row, position) — the one difference from the prefill family.
            ("seeds", (B, QK) if kind == "verify" else (B,), np.uint32),
        ]
        if kind == "prefill":
            spec.append(("tok_slot", (B,), np.int32))
    elif kind in ("unified", "flat"):
        # Unified: the per-row column count rides the high bits and only
        # the stream length sizes the payload. Flat: QK is T itself.
        t = QK & 0xFFFFF if kind == "unified" else QK
        spec = [
            ("stream", (t,), np.int32),
            ("row_start", (B,), np.int32),
            ("pos0", (B,), np.int32),
            ("qlens", (B,), np.int32),
            ("kvlens", (B,), np.int32),
            ("kind", (B,), np.uint8),
            ("page_table", (B, mp), np.int32),
            ("temp", (B,), np.float32),
            ("top_k", (B,), np.int32),
            ("top_p", (B,), np.float32),
            ("seeds", (B, sample_cols), np.uint32),
            ("tok_slot", (B,), np.int32),
            ("tok_dev", (B,), np.uint8),
        ]
        if kind == "flat":
            # The run-plan width derives from (B, T, page): a row touching
            # p pages emits p runs, and p <= (w-1)//page + 2 (the +2 covers
            # the first page AND a mid-page start's extra straddle — a
            # 2-token row starting at slot page-1 already touches two
            # pages), so the total is bounded by 2*B + ceil(T / page).
            rn = 2 * B + -(-t // page)
            spec += [
                ("wsrc", (rn,), np.int32),
                ("woff", (rn,), np.int32),
                ("wcnt", (rn,), np.int32),
                ("wphys", (rn,), np.int32),
            ]
            if ring:
                spec.append(("wphys_swa", (rn,), np.int32))
            if runs:
                spec += [
                    ("run_lead", (t,), np.int32),
                    ("run_blocks", (t,), np.int32),
                ]
    elif kind == "decode":
        spec = [
            ("first", (B,), np.int32),
            ("start", (B,), np.int32),
            ("page_table", (B, mp), np.int32),
            ("active", (B,), np.uint8),
            ("temp", (B,), np.float32),
            ("top_k", (B,), np.int32),
            ("top_p", (B,), np.float32),
            ("seeds", (B, QK), np.uint32),
            ("tok_slot", (B,), np.int32),
            ("tok_dev", (B,), np.uint8),
        ]
    else:
        raise ValueError(f"no step program of kind {kind!r}")
    if ring:
        spec.append(("swa_table", (B, mp), np.int32))
    if state and kind == "flat":
        spec.append(("state_slots", (B,), np.int32))
    if lora:
        spec.append(("lora", (B,), np.int32))
    return spec


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    shape: tuple[int, ...]
    dtype: np.dtype
    offset: int  # first word in the buffer
    size: int  # elements == words

    @property
    def as_bits(self) -> bool:
        """A 4-byte element travels as its bits; a 1-byte one as its value
        in a word of its own."""
        return self.dtype.itemsize == 4


class PayloadLayout:
    """Where each field of a step's payload lies in its one buffer."""

    def __init__(self, spec) -> None:
        fields, at = [], 0
        for name, shape, dtype in spec:
            dtype = np.dtype(dtype)
            if dtype.itemsize not in (1, 4):
                raise ValueError(
                    f"payload field {name}: {dtype} is neither one byte "
                    "nor one word wide"
                )
            size = int(np.prod(shape, dtype=np.int64))
            fields.append(Field(name, tuple(shape), dtype, at, size))
            at += -(-size // ALIGN) * ALIGN
        self.fields: tuple[Field, ...] = tuple(fields)
        self.words = at

    def zeros(self) -> dict[str, np.ndarray]:
        """Host arrays of the described shapes and dtypes, all zero."""
        return {f.name: np.zeros(f.shape, f.dtype) for f in self.fields}

    def pack(self, arrays: dict) -> np.ndarray:
        """A fresh ``[words] int32`` buffer holding ``arrays``' fields
        (others are ignored). The pad words between fields are zero."""
        buf = np.zeros(self.words, np.int32)
        for f in self.fields:
            a = arrays[f.name]
            if a.shape != f.shape or a.dtype != f.dtype:
                raise ValueError(
                    f"payload field {f.name}: got {a.dtype}{list(a.shape)}, "
                    f"described as {f.dtype}{list(f.shape)}"
                )
            dst = buf[f.offset : f.offset + f.size]
            if f.as_bits:
                dst = dst.view(f.dtype)
            dst.reshape(f.shape)[...] = a
        return buf

    def unpack(self, buf: jax.Array) -> dict[str, jax.Array]:
        """The fields of a packed buffer (traceable; static slices)."""
        out = {}
        for f in self.fields:
            words = jax.lax.slice(buf, (f.offset,), (f.offset + f.size,))
            if f.dtype == np.int32:
                a = words
            elif f.as_bits:
                a = jax.lax.bitcast_convert_type(words, f.dtype)
            elif f.dtype == np.bool_:
                a = words != 0
            else:
                a = words.astype(f.dtype)
            out[f.name] = a.reshape(f.shape)
        return out
