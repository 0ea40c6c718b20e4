"""What the tests of the two state-pool models (``tiny-granite-hybrid``,
``tiny-qwen3-next``) share about the snapshot a sequence leaves behind at the
last page it fills before its end: each file hands in its own engine,
``greedy`` and plain reference."""

import numpy as np

FIRST, ANSWER, MORE = 37, 11, 9  # a prompt whose last full page is 36, an answer that fills 44, a question


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).tolist()


def check_the_snapshot_at_a_sequences_last_page(eng, greedy, ref, published, logprobs_match) -> None:
    """A turn leaves TWO snapshots: at its prompt's last full page and at the
    last page its answer fills. The second holds the first mixer's state the
    plain reference has after exactly that many tokens (the slot was copied
    behind the step that left it there, before the next one moved it on), the
    session's next turn is seeded from it, and reads as the reference does."""
    page = eng.config.cache.page_size
    first = tokens(FIRST, seed=21)
    (answer, _lp, r1), = greedy(eng, [first], max_tokens=ANSWER)
    at = (FIRST + ANSWER - 1) // page * page
    assert r1.finish_capture_at == at > (FIRST - 1) // page * page
    kept = {e.n_pre * page: e for e in eng._swa_sections._entries.values()}
    assert sorted(kept) == [(FIRST - 1) // page * page, at] and not kept[at].shared
    seen = (first + answer)[:at]
    want = ref.first_mixer_state(eng.runner.params, seen + [0] * 7, at, published)
    err = ref.state_error(np.asarray(eng.runner.kv_swa.ssm[0, kept[at].pages[0]]), want)
    assert err["head_max"] < 1e-4, err
    second = first + answer + tokens(MORE, seed=22)
    (toks, lps, r2), = greedy(eng, [second], max_tokens=ANSWER)
    assert r2.num_cached_tokens == at and kept[at].hits == 1 and kept[(FIRST - 1) // page * page].hits == 0
    logprobs_match(eng, second, toks, lps)
    eng._refresh_gauges()
    assert eng.stats.retained_finish_captures_total == 2 == eng.stats.requests_finished
