"""Share of a grouped expert layer's groups (experts) that had rows, over the
traced slice: ``reducers.py``'s ``counter_ratio`` with ``"over": "traced"``
and ``"den_config"`` (the definition beside this file holds its inputs, and
``perfbench/tests``'s ``counting_bench`` holds the same arithmetic as data),
plus the one case data cannot say.

``kernels.moe_gmm_roofline`` raises once this metric has a definition and
reads nothing. A check runs the PARENT of the commit that added the counters
under these files too, and that program cannot count: there the answer is
100, every expert charged, the upper bound the roofline charged before any
program counted (exact only where every expert has rows), so a program
without the counters keeps the roofline it had. A program that HAS the
counters never takes that road: a slice in which they stand at 0 reads
nothing, and the roofline raises.
"""


def read(ctx, definition):
    delta = ctx.get("counter_delta_traced")
    if delta is None:  # no traced slice (--trace 0)
        return None
    names = definition["num"] + definition["den"]
    if not any(n in delta for n in names):
        return 100.0  # a program from before the counters: every expert charged
    if not all(n in delta for n in names):
        return None
    num = sum(delta[n] for n in definition["num"])
    den = sum(delta[n] for n in definition["den"])
    den *= next((ctx["config"][k] for k in definition["den_config"] if ctx["config"].get(k)), 0)
    return definition["scale"] * num / den if den else None
