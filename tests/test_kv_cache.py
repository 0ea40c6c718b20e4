"""Unit tests for the page allocator + prefix cache."""

import pytest

from llmd_tpu.engine.kv_cache import (
    NoFreePagesError,
    PageAllocator,
    page_hashes_for_tokens,
)


def test_alloc_free_roundtrip():
    a = PageAllocator(num_pages=8, page_size=4)
    pages = a.allocate(5)
    assert len(set(pages)) == 5
    assert a.num_free_pages == 3
    a.free(pages)
    assert a.num_free_pages == 8


def test_out_of_pages():
    a = PageAllocator(num_pages=4, page_size=4)
    a.allocate(4)
    with pytest.raises(NoFreePagesError):
        a.allocate(1)


def test_hash_chain_is_positional():
    h1 = page_hashes_for_tokens([1, 2, 3, 4, 5, 6, 7, 8], page_size=4)
    h2 = page_hashes_for_tokens([9, 9, 9, 9, 5, 6, 7, 8], page_size=4)
    assert len(h1) == 2
    # same second-page tokens but different parent => different hash
    assert h1[1] != h2[1]


def test_prefix_reuse_and_refcount():
    a = PageAllocator(num_pages=8, page_size=4)
    tokens = list(range(12))
    pages = a.allocate(3)
    hashes = page_hashes_for_tokens(tokens, 4)
    parent = None
    for pid, h in zip(pages, hashes):
        a.commit_page(pid, h, [], parent)
        parent = h
    a.free(pages)  # refcount 0 but content cached
    hit = a.lookup_cached_prefix(tokens)
    assert hit == pages
    a.touch(hit)
    assert a.num_free_pages == 5
    # partial prefix match
    hit2 = a.lookup_cached_prefix(tokens[:8] + [99, 99, 99, 99])
    assert hit2 == pages[:2]


def test_eviction_drops_cached_content():
    a = PageAllocator(num_pages=2, page_size=4)
    pages = a.allocate(2)
    hashes = page_hashes_for_tokens(list(range(8)), 4)
    a.commit_page(pages[0], hashes[0], [], None)
    a.commit_page(pages[1], hashes[1], [], hashes[0])
    a.free(pages)
    # allocating reuses the cached pages and invalidates their content
    a.allocate(2)
    assert a.lookup_cached_prefix(list(range(8))) == []


def test_a_waiting_request_gives_back_the_pages_the_prefix_cache_lent_it():
    """A request that hits the prefix cache and then fails admission for
    want of fresh pages must not sit in ``waiting`` on the pages it hit: no
    preemption reaches a waiting request, so the one running row that needs
    a page would never get it, and neither would ever move. The pages go
    back (still cached); the attempts that follow walk the same hash chain
    and count no further query or hit."""
    from llmd_tpu.config import CacheConfig, SchedulerConfig
    from llmd_tpu.engine.request import Request, SamplingParams
    from llmd_tpu.engine.scheduler import EngineScheduler

    alloc = PageAllocator(num_pages=8, page_size=4)
    sched = EngineScheduler(
        SchedulerConfig(max_num_seqs=4, max_num_batched_tokens=64),
        CacheConfig(page_size=4, num_blocks=8), alloc, max_model_len=128,
    )

    def request(rid, prompt, max_tokens):
        return Request(request_id=rid, prompt_token_ids=list(prompt),
                       sampling=SamplingParams(max_tokens=max_tokens, ignore_eos=True))

    def step():
        batch = sched.schedule()
        sched.update_after_step(batch, {s.request.request_id: [7] for s in batch.seqs})
        return [s.request.request_id for s in batch.seqs]

    shared = list(range(1, 17))
    sched.add_request(request("a", shared, max_tokens=1))
    assert step() == ["a"] and not sched.has_work()  # four pages, cached and free
    sched.add_request(request("d", range(100, 111), max_tokens=10))
    assert step() == ["d"]  # three fresh pages; the pool's last fresh one is its next
    w = request("w", shared + list(range(200, 212)), max_tokens=2)  # seven pages, four of them cached
    sched.add_request(w)
    assert step() == ["d"]  # w hit four pages and is three short
    assert w.block_ids == [] and w.num_cached_tokens == 0
    counted = (alloc.metrics_queries, alloc.metrics_hits)
    assert counted == (7, 4)  # a miss each for a and d, four hits and a miss for w
    ran = [step() for _ in range(9)]  # d decodes on, through the pages w gave back
    assert all(ids == ["d"] for ids in ran[:8])
    assert (alloc.metrics_queries, alloc.metrics_hits) == counted  # eight more attempts, none counted
    for _ in range(8):
        if not sched.has_work():
            break
        step()
    assert not sched.has_work() and alloc.usage() == 0.0
    assert len(w.output_token_ids) == 2
