"""Paged KV cache: host-side page allocator with automatic prefix caching.

TPU-first design: the device-side pool is ONE stacked jax.Array per engine
(layer-major), so the per-layer cache slice inside ``lax.scan`` over layers is
a cheap dynamic-index, and page writes are scatters with static shapes. The
host side here manages page lifetimes: a free list, per-page refcounts, and a
content-addressed index of full pages (hash-chained over token ids) giving
automatic prefix caching -- the same chained-block-hash scheme the reference's
KV-cache indexer keys on (docs/architecture/advanced/kv-management/
kv-indexer.md:59-151) and vLLM-style APC semantics
(docs/architecture/core/model-servers.md:5-7).

Evicted-but-cached pages live in an LRU so a cache hit can resurrect them
until they are actually reused for new data.
"""

from __future__ import annotations

import collections
import functools
import threading
import dataclasses
import hashlib
from collections.abc import Iterable, Sequence

# Sentinel parent hash for the first page of a sequence.
_ROOT_HASH = b"llmd-root"


def hash_page(parent_hash: bytes, token_ids: Sequence[int], extra: bytes = b"") -> bytes:
    """Chained content hash of one full page.

    ``extra`` folds in LoRA / multimodal / cache-salt identity, mirroring the
    reference indexer's key-folding rules (kv-indexer.md:145-151).
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(parent_hash)
    h.update(b"|")
    h.update(b",".join(str(t).encode() for t in token_ids))
    if extra:
        h.update(b"#")
        h.update(extra)
    return h.digest()


def page_hashes_for_tokens(
    token_ids: Sequence[int], page_size: int, extra: bytes = b""
) -> list[bytes]:
    """Hashes of all *full* pages covering a token prefix."""
    hashes: list[bytes] = []
    parent = _ROOT_HASH
    for start in range(0, len(token_ids) - page_size + 1, page_size):
        parent = hash_page(parent, token_ids[start : start + page_size], extra)
        hashes.append(parent)
    return hashes


@dataclasses.dataclass
class PageMeta:
    ref_count: int = 0
    content_hash: bytes | None = None


class KVEventSink:
    """Interface for KV-event emission (BlockStored/BlockRemoved/Cleared).

    The precise prefix-cache indexer subscribes to these (reference
    kv-indexer.md:59-63). The default sink drops events; the engine installs
    a ZMQ publisher when configured.
    """

    def blocks_stored(self, hashes: list[bytes], parent: bytes | None, token_ids: list[int]) -> None:
        pass

    def blocks_removed(self, hashes: list[bytes]) -> None:
        pass

    def all_cleared(self) -> None:
        pass



def _locked(fn):
    """Serialize an allocator method on the instance mutex (see
    PageAllocator.__init__: the multi-host pipelined import calls in
    from the fetch thread)."""

    @functools.wraps(fn)
    def inner(self, *a, **k):
        with self._lock:
            return fn(self, *a, **k)

    return inner


# The resource-lifecycle contract (static-analysis.md): every page
# reference minted by an acquire method below must be freed, committed
# into annotated owner state (`# llmd: owns(pages)`), or cross a
# declared `# llmd: transfers(pages)` boundary. The runtime twin
# (LLMD_LEAKSAN=1) mirrors the refcounts per page with acquisition
# backtraces and asserts zero outstanding at test teardown.
# llmd: resource(pages, recv=alloc, acquire=allocate|allocate_with_floor|touch:arg|lookup_and_touch_hashes, release=free, transfer=commit_page)
class PageAllocator:
    """Refcounted page allocator with a content-addressed reuse index."""

    def __init__(
        self,
        num_pages: int,
        page_size: int,
        enable_prefix_caching: bool = True,
        event_sink: KVEventSink | None = None,
    ) -> None:
        self.num_pages = num_pages
        self.page_size = page_size
        self.enable_prefix_caching = enable_prefix_caching
        self.event_sink = event_sink or KVEventSink()
        # Coarse mutex: the engine thread owns most calls, but the
        # multi-host pipelined P/D import allocates/frees/scatters
        # from the fetch executor thread (runner._dispatch_lock
        # orders the device ops; this orders the host bookkeeping).
        self._lock = threading.RLock()
        self._meta = [PageMeta() for _ in range(num_pages)]  # llmd: guarded_by(_lock)
        # Pages with ref_count == 0, LRU-ordered: left = oldest = evict first.
        # Freed cached pages are appended right so hot content survives longest.
        # llmd: guarded_by(_lock)
        self._free: collections.OrderedDict[int, None] = collections.OrderedDict(
            (i, None) for i in range(num_pages)
        )
        # content hash -> page id (only pages whose content is intact).
        self._cached: dict[bytes, int] = {}  # llmd: guarded_by(_lock)
        self.metrics_hits = 0  # llmd: guarded_by(_lock)
        self.metrics_queries = 0  # llmd: guarded_by(_lock)
        # Called on each newly registered full page (tiered offload pump).
        self.commit_hook = None

    # ------------------------------------------------------------------ #

    @property
    def num_free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def usage(self) -> float:
        with self._lock:
            return 1.0 - len(self._free) / self.num_pages

    def _cached_run_locked(self, hashes, count: bool = True) -> list[int]:
        """Leading cached run for a hash chain, with hit accounting —
        the ONE walk every lookup variant delegates to (caller holds
        the lock). ``count=False``: the repeat of a lookup that was
        counted already."""
        pages: list[int] = []
        for h in hashes:
            pid = self._cached.get(h)
            self.metrics_queries += count
            if pid is None:
                break
            self.metrics_hits += count
            pages.append(pid)
        return pages

    @_locked
    def lookup_cached_prefix(self, token_ids: Sequence[int], extra: bytes = b"") -> list[int]:
        """Longest run of consecutive cached full pages for this prompt.

        Returns the page ids (not yet referenced). Mirrors the reference
        indexer's longest-consecutive-prefix scoring (kv-indexer.md:120-135).
        """
        if not self.enable_prefix_caching:
            return []
        return self._cached_run_locked(
            page_hashes_for_tokens(token_ids, self.page_size, extra)
        )

    @_locked
    def peek_hash_run(self, hashes) -> int:
        """Length of the leading cached run for a pre-computed hash
        chain — NO touch, NO metrics. Probe-only (hybrid-hit candidate
        scans must not inflate prefix_cache_hit_rate or refresh LRU
        recency of pages they end up not using)."""
        n = 0
        for h in hashes:
            if h not in self._cached:
                break
            n += 1
        return n

    @_locked
    def lookup_and_touch_hashes(self, hashes, count: bool = True) -> list[int]:
        """The leading run of cached pages for a hash chain
        (``page_hashes_for_tokens``), touched ATOMICALLY with the lookup:
        in two calls, a ref-0 cached page found by the lookup can be
        stolen by a concurrent allocate() (e.g. the multi-host
        streamed-import fetch thread) before touch() claims it — touch
        would then ref-bump a page whose content is being overwritten,
        silently attending over another request's KV. ``count=False``:
        an admission tried again, counted the first time."""
        if not self.enable_prefix_caching:
            return []
        pages = self._cached_run_locked(hashes, count)
        if pages:
            self.touch(pages)
        return pages

    @_locked
    def allocate_with_floor(self, n: int, floor: int) -> list[int]:
        """Allocate only if at least ``floor`` free pages REMAIN after —
        atomically, so concurrent reservers (streamed-import fetch
        threads) cannot jointly drain the decode headroom the floor
        protects. Raises NoFreePagesError when the floor would be
        breached."""
        if len(self._free) - n < floor:
            raise NoFreePagesError(n + floor, len(self._free))
        return self.allocate(n)

    @_locked
    def has_cached(self, content_hash: bytes) -> bool:
        return content_hash in self._cached

    @_locked
    def touch(self, page_ids: Iterable[int]) -> None:
        """Take a reference on cached pages (prefix-cache hit path)."""
        for pid in page_ids:
            meta = self._meta[pid]
            if meta.ref_count == 0:
                # Resurrect from the free LRU.
                del self._free[pid]
            meta.ref_count += 1

    @_locked
    def allocate(self, n: int, longest_free: bool = False) -> list[int]:
        """Allocate n fresh pages (ref=1), evicting cached content LRU-first.

        ``longest_free`` (a pool WITHOUT prefix caching, whose free list is
        ordered by release alone): take the pages that have been free
        longest instead of the ones released last."""
        if n > len(self._free):
            raise NoFreePagesError(n, len(self._free))
        out: list[int] = []
        for _ in range(n):
            pid, _ = self._free.popitem(last=longest_free)
            meta = self._meta[pid]
            if meta.content_hash is not None:
                # Evict: the page is being reused for new content.
                self._cached.pop(meta.content_hash, None)
                self.event_sink.blocks_removed([meta.content_hash])
                meta.content_hash = None
            meta.ref_count = 1
            out.append(pid)
        return out

    @_locked
    def commit_page(
        self,
        page_id: int,
        content_hash: bytes,
        token_ids: list[int],
        parent: bytes | None,
    ) -> int:
        """Register a now-full page's content for reuse.

        Returns the canonical page id: if another page already holds this
        content, callers should deduplicate onto it (we keep it simple and
        just register the new page if the hash is absent).
        """
        if not self.enable_prefix_caching:
            return page_id
        existing = self._cached.get(content_hash)
        if existing is not None and existing != page_id:
            return existing
        self._cached[content_hash] = page_id
        self._meta[page_id].content_hash = content_hash
        self.event_sink.blocks_stored([content_hash], parent, token_ids)
        if self.commit_hook is not None:
            self.commit_hook(page_id, content_hash)
        return page_id

    @_locked
    def free(self, page_ids: Iterable[int]) -> None:
        for pid in page_ids:
            meta = self._meta[pid]
            if meta.ref_count <= 0:
                raise AssertionError(f"double free of page {pid}")
            meta.ref_count -= 1
            if meta.ref_count == 0:
                # Cached pages go to the LRU tail (evicted last); uncached
                # pages to the head (reused first).
                self._free[pid] = None
                if meta.content_hash is None:
                    self._free.move_to_end(pid, last=False)

    @_locked
    def clear(self) -> None:
        for h in list(self._cached):
            self._cached.pop(h)
        for meta in self._meta:
            meta.content_hash = None
        self.event_sink.all_cleared()

    @_locked
    def hit_ratio(self) -> float:
        if not self.metrics_queries:
            return 0.0
        return self.metrics_hits / self.metrics_queries


class NoFreePagesError(RuntimeError):
    def __init__(self, wanted: int, available: int) -> None:
        super().__init__(f"wanted {wanted} KV pages, {available} free")
        self.wanted = wanted
        self.available = available


# Runtime twin of the `# llmd: resource(pages, ...)` annotation above:
# with LLMD_LEAKSAN=1 every page reference is mirrored per allocator
# with an acquisition backtrace, and the conftest gate asserts zero
# outstanding refs at test teardown (static-analysis.md).
from llmd_tpu.analysis import sanitize as _sanitize

_sanitize.leaksan_register(
    PageAllocator, "pages",
    acquire={
        "allocate": lambda self, a, k, r: r,
        "touch": lambda self, a, k, r: list(a[0]) if a else [],
    },
    release={"free": lambda self, a, k, r: list(a[0]) if a else []},
)
