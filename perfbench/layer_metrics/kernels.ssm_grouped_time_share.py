"""``kernels.ssm_time_share`` (the file beside this one, not this PR's to edit)
for a configuration that names its mixer as ``nemotron_h`` does and reads B
and C in G GROUPS: device time of the WHOLE state-space mixers over device
busy time, from the same two kinds of device event:
  * the Pallas calls by name (``definition["pattern"]``: ``%llmd.ssm.update``,
    ``%llmd.ssm.scan``);
  * the mixers' XLA fusions, copies and convolutions, anonymous in the trace,
    by the configuration's own shapes in each event's HLO text: that reader's
    patterns under this file's keys (``mamba_num_heads`` -> ``mamba_n_heads``,
    ``mamba_head_dim`` -> ``mamba_d_head``, ``ssm_state_size`` ->
    ``mamba_d_state``, ``n_groups`` -> ``mamba_n_groups``): the in-projection's
    width ``2 d_in + 2 G N + heads`` (10,304), the conv's channels ``d_in +
    2 G N`` (6,144), ``[tokens, d_in]`` (4,096), the out-projection ``[d_in,
    hidden]`` (4,096 x 2,688), ``[tokens, heads, d_head]`` (64 x 64), the state
    ``[heads, d_head, N]`` (64 x 64 x 128); and what the GROUPED scan adds to
    them, its head axis split into (group, head of the group): ``[row, G, N]``
    (a row's B or C), ``[row, G, R, d_head]``, ``[row, row, G]`` and ``[row,
    row, G, R]`` (C . B^T and the decay), ``[G, R, d_head, N]`` (the state by
    group), and the update's operands ``[G, rows, N]`` (the decode rows' B and
    C gathered) and ``[rows, head blocks, (2,) 32, d_head]`` (decay and dt x
    in, y out, by head block).
THE ATTENTION BLOCKS SHARE A WIDTH WITH THE MIXER here: 32 q heads x 128 =
4,096 = d_in, so their q- and out-projections carry ``[tokens, 4096]`` and
``[4096, hidden]`` too. They are told apart by their STACK: an event whose
text holds the attention blocks' stacked weights (``[A, hidden, 4096]`` or
``[A, 4096, hidden]``, A the ``*`` blocks of the pattern cut to the depth,
where A is not the number of mixers) is the attention's and is left out.
Not matched, and small: the decode rows' ``[rows, heads]`` gathers of dt and
the decay, the conv's index arithmetic.
``definition["part"]``: "xla" leaves the Pallas calls out (the mixers' XLA
part alone: ``kernels.ssm_grouped_xla_time_share``, the file beside this one).
A configuration without these keys, a run without a trace, or one in which
nothing matched gives None (the line then lacks the metric).
"""

import importlib.util
import pathlib
import re

NAMES = {"mamba_num_heads": "mamba_n_heads", "mamba_head_dim": "mamba_d_head",
         "ssm_state_size": "mamba_d_state", "n_groups": "mamba_n_groups"}


def _stock():
    path = pathlib.Path(__file__).with_name("kernels.ssm_time_share.py")
    spec = importlib.util.spec_from_file_location("perfbench_reader_ssm_time_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def head_block(heads: int, per_group: int) -> int:
    """Heads a block of the update kernel: the largest of 32, 16, 8 that
    divides the heads and is whole groups or lies inside one."""
    for hb in (32, 16, 8):
        if heads % hb == 0 and (hb % per_group == 0 or per_group % hb == 0):
            return hb
    return heads


def grouped_shapes(config: dict):
    """The pattern of the grouped scan's own shapes."""
    nh, p, n, g = (int(config[k]) for k in ("mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups"))
    r = nh // g
    hb = head_block(nh, r)
    return re.compile(
        rf"\[\d+,{g},{n}(?:,1)?\]|\[{g},\d+,{n}\]"   # a row's B or C; the decode rows' gathered
        rf"|\[\d+,{g},{r},{p}\]|\[{g},{r},\d+,\d+\]"  # [row, G, R, d_head] and its transpose
        rf"|\[(\d+),\1,{g}(?:,{r})?\]"               # C . B^T [row, row, G], the decay [row, row, G, R]
        rf"|\[{g},{r},{p},{n}\]"                      # the state by group
        rf"|\[\d+,{nh // hb},(?:2,)?(?:{hb},{p}|{p},{hb})\]"  # the update's decay | dt x and y by head block
    )


def attention_own(config: dict):
    """The pattern of the attention blocks' stacked q- and out-projection
    weights, or None where their count does not tell them from the mixers'."""
    blocks = str(config.get("hybrid_override_pattern", ""))[: int(config.get("num_hidden_layers", 0))]
    a, m = blocks.count("*"), blocks.count("M")
    width = int(config.get("num_attention_heads", 0)) * int(config.get("head_dim", 0))
    if not a or a == m or not width:
        return None
    hidden = int(config["hidden_size"])
    return re.compile(rf"\[{a},(?:{hidden},{width}|{width},{hidden})\]")


def read(ctx, definition):
    config, trace = ctx.get("config") or {}, ctx.get("trace")
    if any(k not in config for k in NAMES) or not trace or not trace.get("op_seconds") or not trace.get("busy_s"):
        return None
    stock = _stock()
    own = stock.shapes({**config, **{theirs: config[ours] for ours, theirs in NAMES.items()}})
    grouped, named = grouped_shapes(config), re.compile(definition["pattern"])
    attention = attention_own(config)
    whole = definition.get("part") != "xla"
    total = 0.0
    for name, seconds in trace["op_seconds"].items():
        short = name.split(" = ", 1)[0]
        if named.search(short):
            total += seconds if whole else 0.0
        elif (not short.startswith(stock.OTHERS) and (own.search(name) or grouped.search(name))
              and not (attention and attention.search(name))):
            total += seconds
    return 100.0 * total / trace["busy_s"] if total > 0.0 else None
