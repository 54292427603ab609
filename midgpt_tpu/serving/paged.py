"""Paged KV-cache pool: fixed page arrays + per-request block tables.

The serving replacement for per-request ring caches (midgpt_tpu.sampling):
one shared pool of fixed-size pages per layer, and each live request owns
an ordered list of page ids (its *block table*). Memory scales with the
tokens actually resident — a request holding 37 tokens at page_size=16
pins 3 pages, not a whole ``[B, Hkv, C, block_size]`` ring — which is what
lets the continuous-batching scheduler keep decode slots full under mixed
prompt/generation lengths (vLLM's PagedAttention / the TPU-native Ragged
Paged Attention formulation, PAPERS.md).

Layout: ``[L, num_pages, page_size, Hkv*C]`` — a page is ``page_size``
rows, and a row carries every KV head's C values side by side on the
minor (lane) dim. At 16 bf16 rows the page is whole native (16, 128)
tiles, unpadded in any layout XLA picks whatever C is (heads of 64 share
a lane tile two by two), and one contiguous slab — 64 KB at 16 heads of
128 — that the decode kernel (ops.paged_attn) moves with one DMA. (An
int8 page of 16 rows is half a (32, 128) tile and pads 2x in HBM —
PERF.md section 7.) Device-side reads go through the kernel's live-page
walk, or through a block-table gather (models.gpt.Attention
.decode_paged_at's XLA path, the prefill chunk);
device-side writes are bulk scatters at window/prefill boundaries only
(:func:`flush_recent`, :func:`write_prompt_pages`), so the pool stays
read-only inside the fused decode scan. Out-of-range page ids (== the
dedicated ``num_pages`` sentinel) drop their writes — that is how padded
block-table tails and finished/inactive slots pad harmlessly.

The allocator (:class:`PageAllocator`) is host-side and pure-Python: page
accounting is control flow, not math, and it runs once per scheduler
window, never inside jit.
"""

from __future__ import annotations

import collections
import typing as tp

import jax
import jax.numpy as jnp
import numpy as np

from midgpt_tpu.config import ModelConfig
from midgpt_tpu.parallel.sharding import shard_act
from midgpt_tpu.pytree import module, static
from midgpt_tpu.quant import (
    kv_scale_from_absmax,
    quantize_kv_rows,
    round_kv_rows_to_grid,
)

Array = jax.Array

# mesh layout of the pool arrays [L, NP, PS, Hkv*C] under tensor
# parallelism: WHOLE-KV-HEAD sharding — pages, the in-page time dim and
# each head's C values stay intact per shard, so block-table gathers (an
# index into the replicated page dim) and page scatters are shard-local;
# only the row's run of heads splits. Batch/page index arrays (block
# tables, pooled_len, masks) are replicated.
POOL_SPEC_AXES = (None, None, None, "kv_heads")
# the per-(page, KV-head) scale planes [L, NP, Hkv] of an int8 pool
# shard with their heads, like the payload
SCALE_SPEC_AXES = (None, None, "kv_heads")


@module
class PagedKVPool:
    """The shared page pool; leaves carry a leading n_layer axis like the
    scan-stacked block params (and KVCache).

    ``kv_quant="int8"`` (init) stores the payload int8 with one f32
    power-of-two scale per (page, KV-head) plane (``scale_k`` /
    ``scale_v`` — K and V quantize independently), halving the KV HBM
    stream serving decode pays every step. Scales are fixed at PAGE
    BIRTH from the page's first row and travel with the page through
    copy-on-write duplication, prefix-cache aliasing and cold
    retirement — a page's payload and its scale are one atomic unit
    (a stale scale on an aliased page is silent corruption; see
    :func:`copy_page`). Exactness contract in midgpt_tpu.quant (the KV
    grid section): dequantization is bitwise, so an int8 pool behaves
    like a bf16 pool whose values lie on the grid.

    A LATENT pool (a latent-attention model, ``cfg.latent``): ONE payload a
    page — ``k`` holds a token's pooled row ``[latent | rotary key | 0...]``
    (models.gpt.LatentAttention), ``[L, NP, PS, cfg.latent_row]`` with no
    KV-head axis — and ``v`` is None. Allocator, block tables, prefix index
    and every writer below are the same: none looks inside a page."""

    k: Array  # [L, NP, PS, Hkv*C] (pool dtype; int8 when quantized)
    v: tp.Optional[Array]  # [L, NP, PS, Hkv*C]; None: a latent pool
    page_size: int = static()
    scale_k: tp.Optional[Array] = None  # [L, NP, Hkv] f32 (int8 pools)
    scale_v: tp.Optional[Array] = None

    @staticmethod
    def init(
        cfg: ModelConfig, num_pages: int, page_size: int, dtype=jnp.bfloat16,
        mesh=None, kv_quant: tp.Optional[str] = None,
    ) -> "PagedKVPool":
        """``mesh`` (a serving TP mesh): commit the pool KV-head-sharded
        over the 'tensor' axis — each shard holds every page of its own
        Hkv/tp heads (POOL_SPEC_AXES), which is what keeps the serving
        programs' block-table gathers collective-free. ``kv_quant="int8"``
        stores the payload int8 with per-(page, KV-head) po2 scale
        planes (sharded with their heads)."""
        assert num_pages >= 1 and page_size >= 1, (num_pages, page_size)
        assert kv_quant in (None, "int8"), f"unknown kv_quant {kv_quant!r}"
        # (the layer axis counts the layers that HAVE keys and values: a
        # linear-attention layer's cache is RecurrentState's)
        shape = (
            cfg.kv_layers, num_pages, page_size,
            cfg.pool_heads * cfg.pool_width,
        )
        if kv_quant == "int8":
            dtype = jnp.int8
        k = jnp.zeros(shape, dtype)
        v = None if cfg.latent else jnp.zeros(shape, dtype)
        scale_k = scale_v = None
        if kv_quant == "int8":
            # scale 1.0 on unwritten pages is inert: a page's scale is
            # overwritten by its birth write before pooled_len ever
            # exposes the page to a read
            planes = (cfg.kv_layers, num_pages, cfg.kv_heads)
            scale_k = jnp.ones(planes, jnp.float32)
            scale_v = jnp.ones(planes, jnp.float32)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from midgpt_tpu.parallel.sharding import (
                DEFAULT_LOGICAL_RULES,
            )

            def commit(a, axes):
                spec = P(*[
                    DEFAULT_LOGICAL_RULES.get(x) if x is not None else None
                    for x in axes
                ])
                return jax.device_put(a, NamedSharding(mesh, spec))

            assert not cfg.latent, "a latent page has no head axis to shard"
            k = commit(k, POOL_SPEC_AXES)
            v = commit(v, POOL_SPEC_AXES)
            if scale_k is not None:
                scale_k = commit(scale_k, SCALE_SPEC_AXES)
                scale_v = commit(scale_v, SCALE_SPEC_AXES)
        return PagedKVPool(
            k=k, v=v, page_size=page_size, scale_k=scale_k, scale_v=scale_v
        )

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def quantized(self) -> bool:
        return self.scale_k is not None

    @property
    def row_dtype(self):
        """The dtype K/V ROWS travel in before they land in pages (the
        decode window's recent buffers, chunk/verify row outputs). For a
        float pool this is the pool dtype; for an int8 pool it is bf16 —
        rows are rounded through the page grid in-dispatch, and grid
        values (|code| <= 127 times a po2 scale) are exact in bf16, so
        nothing is lost between the rounding and the page write."""
        return jnp.bfloat16 if self.quantized else self.k.dtype


@module
class RecurrentState:
    """The cache of a model's linear-attention layers (models.gpt
    .GatedDeltaNet), beside the page pool in the same engine: per linear
    layer and SLOT — owned by the slot, not paged, of a fixed size whatever
    the context — a float32 state a head and the convolution's tail. A slot's
    rows mean something from its request's first prefill chunk on (which
    starts from zeros: the chunk program's ``fresh`` flag) and are garbage
    otherwise. No snapshot of it exists at any position but the newest,
    which is what the engine refuses for such a model: a prefix-cache hit,
    a handoff, a rollback."""

    s: Array  # [Ll, S, Hv, dk, dv] float32
    conv: Array  # [Ll, S, taps - 1, 2 Hk dk + Hv dv] the cache dtype

    @staticmethod
    def init(cfg: ModelConfig, slots: int, dtype=jnp.bfloat16):
        ll, hv = cfg.linear_layers, cfg.linear_value_heads
        assert ll >= 1 and slots >= 1, (ll, slots)
        return RecurrentState(
            s=jnp.zeros(
                (ll, slots, hv, cfg.linear_key_dim, cfg.linear_value_dim),
                jnp.float32,
            ),
            conv=jnp.zeros(
                (ll, slots, cfg.linear_conv - 1, cfg.linear_channels), dtype
            ),
        )

    @property
    def nbytes(self) -> int:
        return int(self.s.nbytes + self.conv.nbytes)

    def of_slot(self, slot: Array, fresh: Array):
        """Slot ``slot``'s rows ``([Ll, 1, Hv, dk, dv], [Ll, 1, taps - 1,
        Ch])`` as a prefill chunk finds them: zeros where ``fresh`` (the
        request's first chunk — admission costs no dispatch of its own)."""
        s = jax.lax.dynamic_slice_in_dim(self.s, slot, 1, axis=1)
        conv = jax.lax.dynamic_slice_in_dim(self.conv, slot, 1, axis=1)
        return (jnp.where(fresh, jnp.zeros_like(s), s),
                jnp.where(fresh, jnp.zeros_like(conv), conv))

    def with_slot(self, slot: Array, s: Array, conv: Array):
        """Slot ``slot``'s rows replaced (in place, where donated)."""
        zero = jnp.zeros((), slot.dtype)
        return RecurrentState(
            s=jax.lax.dynamic_update_slice(
                self.s, s.astype(self.s.dtype), (zero, slot, zero, zero, zero)
            ),
            conv=jax.lax.dynamic_update_slice(
                self.conv, conv.astype(self.conv.dtype),
                (zero, slot, zero, zero),
            ),
        )


class PageAllocator:
    """Host-side refcounting allocator over pool page ids.

    A page is in exactly one of three states:

    - **free** — on the free list, contents meaningless;
    - **held** — refcount >= 1: referenced by one or more live requests
      (prefix sharing is an :meth:`incref`, not a second owner);
    - **cached** — refcount 0 but still resident: a cold prefix-cache
      page whose KV is kept for future hits until page pressure reclaims
      it (:meth:`reclaim`). Never written while cached.

    A fourth state exists only under fault injection
    (serving.faults, the ``exhaust`` event): **quarantined** — taken off
    the free list to simulate allocator exhaustion, returned verbatim by
    :meth:`release_quarantined`. Normal operation never quarantines.

    Invariants (tested): ``free + held + cached + quarantined ==
    num_pages`` (quarantined is 0 outside chaos runs, so the classic
    three-way identity holds there); a refcount is never negative
    (decref of a free/cached page raises); double-free and foreign-free
    raise. Allocation is LIFO so a request that frees and re-allocates
    under light load reuses hot pages (better HBM locality than FIFO
    cycling through the whole pool)."""

    def __init__(self, num_pages: int):
        assert num_pages >= 1, num_pages
        self.num_pages = num_pages
        self._free: tp.List[int] = list(range(num_pages - 1, -1, -1))
        self._ref: tp.Dict[int, int] = {}
        self._cached: tp.Set[int] = set()
        self._quarantined: tp.List[int] = []

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def held_pages(self) -> int:
        return len(self._ref)

    @property
    def cached_pages(self) -> int:
        return len(self._cached)

    @property
    def quarantined_pages(self) -> int:
        return len(self._quarantined)

    def quarantine(self, n: int = -1) -> int:
        """Fault injection (serving.faults ``exhaust``): pull up to ``n``
        FREE pages (-1 = all of them) out of circulation — held and
        cached pages are untouched, so live requests keep their pages
        and the prefix cache keeps serving hits; only new allocation
        feels the pressure. Returns the count actually quarantined."""
        if n < 0:
            n = len(self._free)
        n = min(n, len(self._free))
        for _ in range(n):
            self._quarantined.append(self._free.pop())
        return n

    def release_quarantined(self) -> int:
        """Undo :meth:`quarantine`: every quarantined page returns to
        the free list. Returns the count released."""
        n = len(self._quarantined)
        self._free.extend(self._quarantined)
        self._quarantined.clear()
        return n

    def refcount(self, p: int) -> int:
        return self._ref.get(p, 0)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> tp.List[int]:
        """Pop ``n`` pages off the free list at refcount 1; raises
        MemoryError when the pool can't satisfy the request (the
        scheduler's cue to reclaim cold cache pages, then evict)."""
        assert n >= 0, n
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, free {len(self._free)} "
                f"of {self.num_pages}"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._ref.update((p, 1) for p in pages)
        return pages

    def incref(self, p: int) -> None:
        """Share a page: held -> refcount + 1, or revive a cold cached
        page to refcount 1 (a prefix-cache hit)."""
        if p in self._cached:
            self._cached.remove(p)
            self._ref[p] = 1
        elif p in self._ref:
            self._ref[p] += 1
        else:
            raise ValueError(f"incref of free page {p}")

    def decref(self, p: int, cache: bool = False) -> int:
        """Drop one reference; returns the new refcount. At zero the page
        leaves the held set — to the cold cache when ``cache`` (the
        prefix index still maps its contents) else to the free list."""
        if p not in self._ref:
            raise ValueError(f"freeing page {p} that is not held")
        self._ref[p] -= 1
        n = self._ref[p]
        if n == 0:
            del self._ref[p]
            if cache:
                self._cached.add(p)
            else:
                self._free.append(p)
        return n

    def free(self, pages: tp.Iterable[int]) -> None:
        """Decref each page straight to the free list at zero (the
        no-prefix-cache path)."""
        for p in pages:
            self.decref(p, cache=False)

    def reclaim(self, p: int) -> None:
        """Cold cache -> free list (the prefix index evicted ``p``)."""
        if p not in self._cached:
            raise ValueError(f"reclaiming page {p} that is not cached")
        self._cached.remove(p)
        self._free.append(p)

    def check(self) -> None:
        """Assert the structural invariants (tests call this after every
        mutation sequence)."""
        assert (
            len(self._free) + len(self._ref) + len(self._cached)
            + len(self._quarantined)
            == self.num_pages
        )
        assert len(set(self._free)) == len(self._free), "free-list dup"
        held = set(self._ref)
        quarantined = set(self._quarantined)
        assert len(quarantined) == len(self._quarantined), "quarantine dup"
        assert not (set(self._free) & held), "page both free and held"
        assert not (set(self._free) & self._cached), "page both free/cached"
        assert not (held & self._cached), "page both held and cached"
        assert not (
            quarantined & (set(self._free) | held | self._cached)
        ), "quarantined page also free/held/cached"
        assert all(n >= 1 for n in self._ref.values()), "refcount < 1"


class PrefixIndex:
    """Host-side page-granular prefix index: content-addressed lookup of
    resident KV pages by the token prefix they encode.

    A page holding the KV of context positions ``[i*PS, (i+1)*PS)`` is
    keyed by ``(parent_page, chunk)`` where ``chunk`` is that page's PS
    tokens and ``parent_page`` is the indexed page of the preceding chunk
    (-1 at the root) — the chain hash: KV at position j depends on the
    whole prefix 0..j, so two pages are interchangeable iff their entire
    token prefixes match, which the parent link encodes. Only FULL pages
    are indexed (their contents are final: pages are append-only), so an
    indexed page is immutable and safe to alias into any block table.

    Refcounts live in :class:`PageAllocator`; the index only tracks the
    content->page map, the parent/children tree, and an LRU order over
    COLD pages (refcount 0, kept resident by the engine until page
    pressure). Eviction is leaf-first: a page is reclaimable only when no
    indexed child chains through it — ancestors of a held page are held
    (matching shares whole chains from the root), so cold subtrees are
    closed downward and a reclaimable leaf always exists while any cold
    page does."""

    _ROOT = -1

    def __init__(self, page_size: int):
        assert page_size >= 1, page_size
        self.page_size = page_size
        # (parent_page, chunk-tuple) -> page id
        self._by_key: tp.Dict[tp.Tuple[int, tp.Tuple[int, ...]], int] = {}
        # page id -> (parent_page, chunk-tuple)
        self._meta: tp.Dict[int, tp.Tuple[int, tp.Tuple[int, ...]]] = {}
        self._children: tp.Dict[int, tp.Set[int]] = {}
        # cold (refcount-0) pages in LRU order; values unused
        self._lru: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict()
        )
        # spilled nodes (host-RAM resident, no HBM page): VIRTUAL ids
        # <= -2 (the root is -1, real pages are >= 0), in spill order —
        # oldest-first is the discard order under a host budget. A
        # spilled node keeps its (parent, chunk) identity, so match()
        # walks onto and THROUGH it like any resident page and the
        # engine faults it back (import_pages under a fresh id) before
        # use. Spill proceeds deepest-first: a page is spill-eligible
        # once every child is already spilled, so whole cold chains
        # drain to host tail-to-root and spilled SUBTREES are closed
        # downward (every child of a spilled node is spilled — a
        # resident page never chains under a virtual id, because new
        # children only register under a slot's current node, which
        # fault-back keeps resident). check() asserts the closure.
        self._spilled: "collections.OrderedDict[int, None]" = (
            collections.OrderedDict()
        )
        self._next_spill = -2

    def __len__(self) -> int:
        return len(self._meta)

    def __contains__(self, page: int) -> bool:
        return page in self._meta

    @property
    def cold_pages(self) -> int:
        return len(self._lru)

    def lookup(self, parent: int, chunk: tp.Sequence[int]) -> tp.Optional[int]:
        """The indexed page for ``chunk`` under ``parent`` (-1 = root),
        or None."""
        return self._by_key.get((parent, tuple(int(t) for t in chunk)))

    def match(
        self, tokens: tp.Sequence[int]
    ) -> tp.Tuple[tp.List[int], tp.Optional[int], int]:
        """Longest cached prefix of ``tokens``: ``(full_pages, cow_src,
        matched)`` — the chain of fully-matched page ids, an optional
        page whose chunk *extends* the remaining partial tail (the
        copy-on-write candidate), and the total matched token count.
        ``tokens`` should already be capped below the full prompt (the
        engine always recomputes at least the last prompt token, which
        is how the first decode logits are produced)."""
        ps = self.page_size
        toks = [int(t) for t in tokens]
        full: tp.List[int] = []
        parent = self._ROOT
        i = 0
        while i + ps <= len(toks):
            page = self._by_key.get((parent, tuple(toks[i : i + ps])))
            if page is None:
                break
            full.append(page)
            parent = page
            i += ps
        rem = tuple(toks[i:])  # < ps after a full-match walk stops
        cow = None
        if rem:
            for child in self._children.get(parent, ()):
                _, chunk = self._meta[child]
                if chunk[: len(rem)] == rem:
                    cow = child
                    break
        matched = i + (len(rem) if cow is not None else 0)
        return full, cow, matched

    def register(
        self, parent: int, chunk: tp.Sequence[int], page: int
    ) -> int:
        """Index ``page`` as holding ``chunk`` under ``parent``; returns
        the CANONICAL page for that content — ``page`` itself normally,
        or the already-indexed page when another request registered
        identical content first (the duplicate stays private and
        unindexed; callers chain future registrations through the
        canonical id)."""
        key = (parent, tuple(int(t) for t in chunk))
        existing = self._by_key.get(key)
        if existing is not None:
            return existing
        assert page not in self._meta, f"page {page} indexed twice"
        self._by_key[key] = page
        self._meta[page] = key
        self._children.setdefault(parent, set()).add(page)
        return page

    def touch_cold(self, page: int) -> None:
        """Mark an indexed page cold (refcount hit 0) or refresh its LRU
        position."""
        assert page in self._meta, page
        self._lru[page] = None
        self._lru.move_to_end(page)

    def revive(self, page: int) -> None:
        """A cold page got a hit (refcount 0 -> 1): leave the LRU."""
        self._lru.pop(page, None)

    def evict_cold_leaf(self) -> tp.Optional[int]:
        """Drop the least-recently-used cold page that no indexed child
        chains through; returns its id (caller reclaims it in the
        allocator) or None when nothing is reclaimable."""
        for page in self._lru:
            if not self._children.get(page):
                self._drop(page)
                return page
        return None

    # -- host spill (ServingEngine spill="on") ------------------------------

    @property
    def spilled_count(self) -> int:
        return len(self._spilled)

    def is_spilled(self, node: int) -> bool:
        """True for a virtual spilled-node id (match() can return them
        as a suffix of the chain, or as the COW source)."""
        return node in self._spilled

    def coldest_leaf(self) -> tp.Optional[int]:
        """The LRU cold page with no RESIDENT descendants — the next
        spill victim, returned without dropping it (the spill path must
        export the page's contents while the index still maps them).
        Spilled children don't block: chains drain to host
        deepest-first, so a reclaimable victim exists while any cold
        page does."""
        for page in self._lru:
            kids = self._children.get(page)
            if not kids or all(k in self._spilled for k in kids):
                return page
        return None

    def _rekey(self, old: int, new: int) -> None:
        """Move a node between ids, preserving its (parent, chunk)
        identity, its position under the parent, and its CHILDREN's keys
        (a child's content key embeds the parent id, so every child
        re-keys with it)."""
        parent, chunk = self._meta.pop(old)
        self._by_key[(parent, chunk)] = new
        self._meta[new] = (parent, chunk)
        siblings = self._children.get(parent)
        if siblings is not None:
            siblings.discard(old)
            siblings.add(new)
        kids = self._children.pop(old, None)
        if kids:
            self._children[new] = kids
            for c in kids:
                _, cchunk = self._meta[c]
                del self._by_key[(old, cchunk)]
                self._by_key[(new, cchunk)] = c
                self._meta[c] = (new, cchunk)

    def spill(self, page: int) -> int:
        """Re-key a cold page to a fresh virtual spilled-node id: the
        (parent, chunk) identity survives — still matchable — while the
        HBM page id detaches (the caller reclaims it in the allocator
        and stores the exported payload under the returned id). Only
        pages whose children are all already spilled are eligible
        (:meth:`coldest_leaf`), so spilled subtrees stay closed."""
        assert page in self._meta and page in self._lru, page
        kids = self._children.get(page)
        assert not kids or all(k in self._spilled for k in kids), (
            f"spilling page {page} with resident children"
        )
        vid = self._next_spill
        self._next_spill -= 1
        self._rekey(page, vid)
        self._lru.pop(page)
        self._spilled[vid] = None
        return vid

    def unspill(self, vid: int, page: int) -> None:
        """Fault-back re-keying: the spilled node becomes resident page
        ``page`` (freshly allocated, refcount 1 — the caller imported
        the stored payload into it). The inverse of :meth:`spill` up to
        the physical id; any still-spilled children re-key under the
        new page id with it."""
        assert vid in self._spilled, vid
        assert page >= 0 and page not in self._meta, page
        self._rekey(vid, page)
        del self._spilled[vid]

    def discard_spilled_oldest(
        self, protect: tp.Optional[tp.AbstractSet[int]] = None
    ) -> tp.Optional[int]:
        """Forget the oldest CHILDLESS spilled node outright (host
        budget overflow, or a cache clear): returns its virtual id so
        the caller drops the stored payload, or None when nothing is
        discardable. Leaf-first like eviction — dropping a mid-chain
        node would orphan its descendants' keys. True reclaim resumes
        here: the prefix is simply no longer cached anywhere.

        ``protect`` exempts vids from discard: an in-flight fault-back
        reserves pages (which may spill victims past the host budget),
        and budget enforcement must not drop the very chain it is
        materializing — deepest-first spill makes the matched chain's
        childless tail precisely the likely oldest entry."""
        for vid in self._spilled:
            if self._children.get(vid):
                continue
            if protect is not None and vid in protect:
                continue
            parent, chunk = self._meta.pop(vid)
            del self._by_key[(parent, chunk)]
            self._children.get(parent, set()).discard(vid)
            self._children.pop(vid, None)
            del self._spilled[vid]
            return vid
        return None

    def _drop(self, page: int) -> None:
        parent, chunk = self._meta.pop(page)
        del self._by_key[(parent, chunk)]
        self._children.get(parent, set()).discard(page)
        self._children.pop(page, None)
        self._lru.pop(page, None)

    def check(
        self,
        alloc: tp.Optional[PageAllocator] = None,
        spill_store: tp.Optional["HostSpillStore"] = None,
    ) -> None:
        """Structural invariants (property tests call this after every
        scheduler step). With ``spill_store`` the extended spill ledger
        is checked too: every indexed node is EITHER a resident page
        (held or cold-cached in ``alloc`` — the classic
        free+held+cached+quarantined == num_pages identity covers those
        ids) OR a spilled virtual node with exactly one host-store
        payload; the two sets are disjoint and spilled subtrees are
        closed downward (every child of a spilled node is spilled)."""
        assert len(self._by_key) == len(self._meta)
        for page, (parent, chunk) in self._meta.items():
            assert self._by_key[(parent, chunk)] == page
            assert parent == self._ROOT or parent in self._meta, (
                f"page {page} chains through unindexed parent {parent}"
            )
            if parent != self._ROOT:
                assert page in self._children[parent]
        for page in self._lru:
            assert page in self._meta
            assert page >= 0, f"virtual node {page} in the cold LRU"
        for vid in self._spilled:
            assert vid <= -2 and vid in self._meta, vid
            assert all(
                c in self._spilled for c in self._children.get(vid, ())
            ), f"spilled node {vid} has resident children"
        if alloc is not None:
            for page in self._meta:
                if page in self._spilled:
                    continue
                # indexed resident pages: held or cold-cached
                assert page >= 0, f"node {page} neither page nor spilled"
                assert alloc.refcount(page) > 0 or page in alloc._cached
            for page in self._lru:
                assert alloc.refcount(page) == 0, (
                    f"LRU page {page} still referenced"
                )
        if spill_store is not None:
            assert set(self._spilled) == set(spill_store.nodes()), (
                "spill store and index disagree on spilled nodes"
            )


class HostSpillStore:
    """Host-RAM payload store for spilled cold pages (ServingEngine
    ``spill="on"``): one :func:`export_pages` single-page payload —
    ``(k, v, sk, sv)`` numpy arrays, all L layers plus the int8 scale
    planes — per spilled prefix-index node, keyed by the node's virtual
    id (:meth:`PrefixIndex.spill`). Deliberately the same host-array
    wire format as the disaggregated page handoff: the spill-out /
    fault-back round trip is byte-preserving through
    :func:`import_pages`, which is what keeps spilled-then-revived
    streams bitwise identical.

    ``budget_pages`` caps host residency — the engine discards
    oldest-spilled-first past it (true reclaim resumes; the prefix is
    then cached nowhere). None = unbounded (host RAM is the capacity
    the feature buys; a 100k-token prompt's KV at int8 is ~2·L·Hkv·C
    bytes/token, far below typical host memory)."""

    def __init__(self, budget_pages: tp.Optional[int] = None):
        assert budget_pages is None or budget_pages >= 0, budget_pages
        self.budget_pages = budget_pages
        self._store: tp.Dict[int, tp.Tuple] = {}
        self._nbytes = 0

    def __len__(self) -> int:
        return len(self._store)

    def __contains__(self, node: int) -> bool:
        return node in self._store

    def nodes(self) -> tp.Iterable[int]:
        return self._store.keys()

    @staticmethod
    def _payload_nbytes(payload: tp.Tuple) -> int:
        return sum(a.nbytes for a in payload if a is not None)

    def put(self, node: int, payload: tp.Tuple) -> None:
        assert node not in self._store, f"node {node} spilled twice"
        self._store[node] = payload
        self._nbytes += self._payload_nbytes(payload)

    def pop(self, node: int) -> tp.Tuple:
        payload = self._store.pop(node)
        self._nbytes -= self._payload_nbytes(payload)
        return payload

    @property
    def over_budget(self) -> bool:
        return (
            self.budget_pages is not None
            and len(self._store) > self.budget_pages
        )

    @property
    def nbytes(self) -> int:
        """Host bytes resident (payloads + scale planes) — a running
        counter maintained by put/pop, so the per-step telemetry gauge
        stays O(1) instead of walking every payload array of every
        spilled page on each sample."""
        return int(self._nbytes)


def pages_needed(tokens: int, page_size: int) -> int:
    """ceil(tokens / page_size) — pages a request at ``tokens`` resident
    tokens pins."""
    return -(-tokens // page_size)


def kv_row_scales(
    rows_k: Array,  # [S, Hkv, T, C] — contiguous K rows (float dtype)
    rows_v: Array,  # [S, Hkv, T, C]
    base: Array,  # [S] int32 — absolute position of row 0 per slot
    bt: Array,  # [S, Pmax] int32 block tables
    scale_k_l: Array,  # [NP, Hkv] f32 — ONE layer's pool scale planes
    scale_v_l: Array,
    page_size: int,
) -> tp.Tuple[Array, Array]:
    """The per-row page-grid scales for a contiguous run of K/V rows:
    row ``j`` (absolute position ``base + j``) quantizes under the scale
    of its page, which is (a) derived from the page's BIRTH row when
    that row sits inside this very batch (positions fill contiguously,
    so a page entered at ``pos % PS == 0`` was entered by a batch row),
    else (b) the already-recorded pool scale (the page was born by an
    earlier dispatch). Returns ``(sk, sv)`` as ``[S, Hkv, T]`` f32.

    This single lookup rule is what makes int8-KV token streams
    invariant to window size, chunk size, speculation and eviction: a
    page's scale is a pure function of its birth row's values, and
    derivation is ROUNDING-STABLE (quant.py), so deriving from rows
    that were already rounded through their own grid — the state every
    write path sees — reproduces the original scale bit-for-bit."""
    s_, hkv, t, c = rows_k.shape
    ps = page_size
    pmax = bt.shape[1]
    npool = scale_k_l.shape[0]
    pos = base[:, None] + jnp.arange(t, dtype=base.dtype)  # [S, T]
    page_idx = pos // ps
    derived_k = kv_scale_from_absmax(
        jnp.max(jnp.abs(rows_k.astype(jnp.float32)), axis=-1)
    )  # [S, Hkv, T]
    derived_v = kv_scale_from_absmax(
        jnp.max(jnp.abs(rows_v.astype(jnp.float32)), axis=-1)
    )
    # in-batch birth row index of row j's page (negative = pre-batch)
    jb = page_idx * ps - base[:, None]  # [S, T]
    in_batch = (jb >= 0)[:, None, :]  # [S, 1, T]
    jb_idx = jnp.broadcast_to(
        jnp.clip(jb, 0, t - 1)[:, None, :], (s_, hkv, t)
    )
    from_batch_k = jnp.take_along_axis(derived_k, jb_idx, axis=-1)
    from_batch_v = jnp.take_along_axis(derived_v, jb_idx, axis=-1)
    pg = jnp.take_along_axis(bt, jnp.clip(page_idx, 0, pmax - 1), axis=1)
    pg = jnp.clip(pg, 0, npool - 1)  # sentinel pads clip like the gather
    pool_k_s = jnp.transpose(scale_k_l[pg], (0, 2, 1))  # [S, Hkv, T]
    pool_v_s = jnp.transpose(scale_v_l[pg], (0, 2, 1))
    sk = jnp.where(in_batch, from_batch_k, pool_k_s)
    sv = jnp.where(in_batch, from_batch_v, pool_v_s)
    return sk, sv


def _quantize_rows_at_pages(
    rk: Array,  # [L, S, Hkv, T, C] — contiguous rows, slot-batched
    rv: Array,
    scale_k: Array,  # [L, NP, Hkv] f32 — pool scale planes
    scale_v: Array,
    base: Array,  # [S] int32 — absolute position of row 0 per slot
    bt: Array,  # [S, Pmax] int32 block tables
    pos: Array,  # [S, T] int32 — base[:, None] + arange(T)
    valid: Array,  # [S, T] bool — row is a real token
    page_raw: Array,  # [S, T] int32 — row's page (pre sentinel routing)
    sentinel: int,
    ps: int,
) -> tp.Tuple[Array, Array, Array, Array]:
    """The quantized-write core shared by :func:`flush_recent`,
    :func:`write_prompt_pages` and :func:`write_token_rows`: derive each
    row's page-grid scale (``kv_row_scales`` — page-birth rows derive
    their own, rounding-stable, so rows already rounded in-dispatch
    re-derive the identical scale; pages continued from an earlier
    dispatch or a COW copy reuse their recorded pool scale), quantize
    the rows to exact int8 codes, and scatter the scale planes of pages
    BORN by this write atomically with their payload — birth rows
    routed through the same drop sentinel as the payload scatter.
    Returns ``(qk, qv, scale_k, scale_v)``. Single-slot callers pass
    S=1 views. This is THE page-birth scale rule: change it here, not
    in a per-caller copy (a write path quantizing under a divergent
    rule breaks the scheduling-invariance contract)."""
    l, s, hkv, t, c = rk.shape
    sk, sv = jax.vmap(
        lambda a, b, pk, pv: kv_row_scales(a, b, base, bt, pk, pv, ps)
    )(rk, rv, scale_k, scale_v)  # [L, S, Hkv, T]
    qk = quantize_kv_rows(rk, sk)
    qv = quantize_kv_rows(rv, sv)
    birth = jnp.where(
        valid & (pos % ps == 0), page_raw, sentinel
    ).reshape(-1)  # [S*T]
    sk_vals = jnp.transpose(sk, (0, 1, 3, 2)).reshape(l, s * t, hkv)
    sv_vals = jnp.transpose(sv, (0, 1, 3, 2)).reshape(l, s * t, hkv)
    sk_vals = shard_act(sk_vals, None, None, "kv_heads")
    sv_vals = shard_act(sv_vals, None, None, "kv_heads")
    scale_k = shard_act(
        scale_k.at[:, birth].set(sk_vals, mode="drop"), *SCALE_SPEC_AXES
    )
    scale_v = shard_act(
        scale_v.at[:, birth].set(sv_vals, mode="drop"), *SCALE_SPEC_AXES
    )
    return qk, qv, scale_k, scale_v


def _layer_index(n_layer: int) -> Array:
    """The layer axis as an explicit scatter index ``[L, 1]``. The row
    and page writers index (layer, page, row) together, so what one
    index moves is a run of the pool's minor dim and nothing else. Left
    as a ``:`` slice the layer axis becomes part of the scatter's
    update window, and the chip's compiler then wants it next to the
    minor dim: it re-lays the WHOLE pool in front of the scatter and
    back behind it — four pool-sized copies a dispatch (PERF.md
    section 6, PR 26)."""
    return jnp.arange(n_layer, dtype=jnp.int32)[:, None]


def _latent_rows_set(pool, vals, pg, of) -> PagedKVPool:
    """A latent pool (one payload a page) with the rows ``vals`` ``[L, N,
    row]`` set at (page, offset) ``pg``, ``of`` ``[1, N]``: what
    :func:`flush_recent` and :func:`write_token_rows` do to ``k``."""
    li = _layer_index(pool.k.shape[0])
    return PagedKVPool(
        k=pool.k.at[li, pg, of].set(vals.astype(pool.k.dtype), mode="drop"),
        v=None, page_size=pool.page_size,
    )


def flush_recent(
    pool: PagedKVPool,
    rk: Array,  # [L, S, Hkv, K, C] — the window's recent rows (time-major)
    rv: Array,
    bt: Array,  # [S, Pmax] int32 block tables
    start_len: Array,  # [S] int32 — pool-resident tokens at window start
    valid: Array,  # [S, K] bool — row j is a real token for slot s
) -> PagedKVPool:
    """Fold the decode window's recent rows into each slot's pages — one
    bulk scatter per pool array, inside the same compiled window program.

    Row j of slot s holds the K/V of position ``start_len[s] + j`` (valid
    rows form a prefix: the window carries monotone done flags, so a
    finished slot's tail rows are pad). Invalid rows are routed to the
    out-of-range page sentinel and dropped by ``mode="drop"`` — finished
    and empty slots cost nothing and corrupt nothing.

    This valid-prefix mask is also the speculative-decoding WRITE
    WATERMARK (serving.engine.make_verify_program): a verify dispatch
    computes K/V for all ``spec_len + 1`` candidate rows but passes
    ``valid`` rows only up to the accepted count, so a rejected draft's
    K/V is dropped right here — it never reaches a page, the pool's
    resident length (``start_len``) only ever advances over verified
    context, and the prefix index (which registers pages strictly below
    that watermark) can never serve speculative garbage to another
    request."""
    l, s, hkv, kk, c = rk.shape
    ps = pool.page_size
    pmax = bt.shape[1]
    np_sentinel = pool.num_pages
    pos = start_len[:, None] + jnp.arange(kk)[None, :]  # [S, K]
    page_idx = jnp.clip(pos // ps, 0, pmax - 1)
    page_raw = jnp.take_along_axis(bt, page_idx, axis=1)  # [S, K]
    page = jnp.where(valid, page_raw, np_sentinel)
    off = pos % ps
    scale_k, scale_v = pool.scale_k, pool.scale_v
    if pool.quantized:
        rk, rv, scale_k, scale_v = _quantize_rows_at_pages(
            rk, rv, scale_k, scale_v, start_len, bt, pos, valid,
            page_raw, np_sentinel, ps
        )
    # the (page, row) indices are adjacent, so the [S*K] index dim stays
    # in place: vals arrive [L, S*K, Hkv*C], each a whole pool row
    vals_k = jnp.transpose(rk, (0, 1, 3, 2, 4)).reshape(l, s * kk, hkv * c)
    if pool.v is None:
        return _latent_rows_set(
            pool, vals_k, page.reshape(1, -1), off.reshape(1, -1)
        )
    vals_v = jnp.transpose(rv, (0, 1, 3, 2, 4)).reshape(l, s * kk, hkv * c)
    # TP: rows scatter per shard into its own heads' lanes (the head run
    # is untouched by the scatter indices); pin values + result so the
    # donated pool's sharding survives the window (no-op without a mesh)
    vals_k = shard_act(vals_k, None, None, "kv_heads")
    vals_v = shard_act(vals_v, None, None, "kv_heads")
    li, pg, of = _layer_index(l), page.reshape(1, -1), off.reshape(1, -1)
    return PagedKVPool(
        k=shard_act(pool.k.at[li, pg, of].set(
            vals_k.astype(pool.k.dtype), mode="drop"
        ), *POOL_SPEC_AXES),
        v=shard_act(pool.v.at[li, pg, of].set(
            vals_v.astype(pool.v.dtype), mode="drop"
        ), *POOL_SPEC_AXES),
        page_size=ps,
        scale_k=scale_k,
        scale_v=scale_v,
    )


def write_prompt_pages(
    pool: PagedKVPool,
    ks: Array,  # [L, Hkv, P, C] — prompt K from prefill (post-rope)
    vs: Array,  # [L, Hkv, P, C]
    page_rows: Array,  # [P // PS] int32 — target pages (pad = sentinel)
) -> PagedKVPool:
    """Write a prefilled prompt's K/V into its allocated pages — one bulk
    scatter per array, page-granular. P must be a multiple of page_size
    (the engine pads prompts up to the page grid); the pad tail beyond the
    real prompt length lands in the last allocated page as garbage that
    ``pooled_len`` masking never reads, and pages beyond the allocation
    carry the out-of-range sentinel and drop."""
    l, hkv, p, c = ks.shape
    ps = pool.page_size
    assert p % ps == 0, f"prompt length {p} not a multiple of page_size {ps}"
    assert pool.v is not None, (
        "a latent pool is written by rows: write_token_rows"
    )
    n = p // ps
    scale_k, scale_v = pool.scale_k, pool.scale_v
    if pool.quantized:
        # page-aligned writes: every written page's birth row is its row
        # 0, and all births are in-batch (base 0); pages beyond the
        # allocation already carry the sentinel in page_rows and drop
        pos = jnp.arange(p, dtype=jnp.int32)
        page_raw = page_rows[pos // ps]
        qk, qv, scale_k, scale_v = _quantize_rows_at_pages(
            ks[:, None], vs[:, None], scale_k, scale_v,
            jnp.zeros((1,), jnp.int32), page_rows[None], pos[None],
            jnp.ones((1, p), bool), page_raw[None], pool.num_pages, ps
        )
        ks, vs = qk[:, 0], qv[:, 0]

    # [L, Hkv, P, C] -> page blocks [L, n, PS, Hkv*C]
    def to_pages(a):
        return jnp.transpose(a, (0, 2, 1, 3)).reshape(l, n, ps, hkv * c)

    li, pg = _layer_index(l), page_rows[None, :]
    return PagedKVPool(
        k=shard_act(pool.k.at[li, pg].set(
            to_pages(ks).astype(pool.k.dtype), mode="drop"
        ), *POOL_SPEC_AXES),
        v=shard_act(pool.v.at[li, pg].set(
            to_pages(vs).astype(pool.v.dtype), mode="drop"
        ), *POOL_SPEC_AXES),
        page_size=ps,
        scale_k=scale_k,
        scale_v=scale_v,
    )


def write_token_rows(
    pool: PagedKVPool,
    ks: Array,  # [L, Hkv, T, C] — chunk K from a suffix prefill (post-rope)
    vs: Array,  # [L, Hkv, T, C]
    bt_row: Array,  # [Pmax] int32 — the slot's block table (pad = sentinel)
    start: Array,  # [] int32 — absolute position of chunk token 0
    n_valid: Array,  # [] int32 — real tokens in the chunk (rest is pad)
) -> PagedKVPool:
    """Scatter a prefill chunk's K/V rows into the slot's pages at
    positions ``start + j`` — token-granular (chunk boundaries need not
    align to the page grid: a copy-on-write page hands the suffix an
    mid-page start offset). Same (page, row) scatter as
    :func:`flush_recent`; rows ``j >= n_valid`` route to the out-of-range
    sentinel and drop."""
    l, hkv, t, c = ks.shape
    ps = pool.page_size
    pmax = bt_row.shape[0]
    pos = start + jnp.arange(t)  # [T]
    valid = jnp.arange(t) < n_valid
    page_idx = jnp.clip(pos // ps, 0, pmax - 1)
    page_raw = bt_row[page_idx]
    page = jnp.where(valid, page_raw, pool.num_pages)
    off = pos % ps
    scale_k, scale_v = pool.scale_k, pool.scale_v
    if pool.quantized:
        # chunk boundaries need not page-align: a page born mid-chunk
        # derives from its in-batch birth row, a page continued from an
        # earlier chunk (or a COW copy) reuses its recorded pool scale
        qk, qv, scale_k, scale_v = _quantize_rows_at_pages(
            ks[:, None], vs[:, None], scale_k, scale_v,
            start[None].astype(jnp.int32), bt_row[None], pos[None],
            valid[None], page_raw[None], pool.num_pages, ps
        )
        ks, vs = qk[:, 0], qv[:, 0]
    if pool.v is None:
        assert hkv == 1, ks.shape
        return _latent_rows_set(
            pool, ks.reshape(l, t, c), page[None, :], off[None, :]
        )
    # adjacent (page, row) indices: vals arrive [L, T, Hkv*C]
    vals_k = shard_act(
        jnp.transpose(ks, (0, 2, 1, 3)).reshape(l, t, hkv * c),
        None, None, "kv_heads",
    )
    vals_v = shard_act(
        jnp.transpose(vs, (0, 2, 1, 3)).reshape(l, t, hkv * c),
        None, None, "kv_heads",
    )
    li, pg, of = _layer_index(l), page[None, :], off[None, :]
    return PagedKVPool(
        k=shard_act(pool.k.at[li, pg, of].set(
            vals_k.astype(pool.k.dtype), mode="drop"
        ), *POOL_SPEC_AXES),
        v=shard_act(pool.v.at[li, pg, of].set(
            vals_v.astype(pool.v.dtype), mode="drop"
        ), *POOL_SPEC_AXES),
        page_size=ps,
        scale_k=scale_k,
        scale_v=scale_v,
    )


def copy_page(pool: PagedKVPool, src: Array, dst: Array) -> PagedKVPool:
    """Copy one page's K/V to another page — the copy-on-write primitive:
    a request admitted onto a partially-shared cached page gets a private
    copy it may append into, leaving the shared original untouched. One
    dynamic slice + update per pool array; donate the pool when jitting
    (the engine's compiled wrapper does).

    Int8 pools: the per-(page, KV-head) scale rows copy IN THE SAME
    jitted program as the payload — a page and its scale are one atomic
    unit. Copying only the codes would leave the destination decoding
    the cached prefix's values under a stale scale, and because rounding
    is deterministic, the corruption would be silent and bit-stable
    (tests pin the prefix-cache-hit-under-kv-quant identity). The COW
    destination also inherits the source's scale for the rows the
    admitted request APPENDS into the copied page — correct by the
    page-birth contract: a page's scale is fixed at birth, and the copy
    shares the original's birth row."""
    # no shard_act pins here: the engine jits copy_page OUTSIDE any
    # axis_rules scope (one mesh-free wrapper shared by every engine),
    # where shard_act is a no-op by construction. Sharding under TP
    # rides GSPMD propagation instead, which is airtight for this op:
    # both slice and update index the replicated page dim, so the
    # result carries the committed input pool's sharding — and the
    # donated buffer aliases because nothing reshards.
    k_row = jax.lax.dynamic_slice_in_dim(pool.k, src, 1, axis=1)
    if pool.v is None:  # a latent pool: one payload a page
        return PagedKVPool(
            k=jax.lax.dynamic_update_slice_in_dim(pool.k, k_row, dst, axis=1),
            v=None, page_size=pool.page_size,
        )
    v_row = jax.lax.dynamic_slice_in_dim(pool.v, src, 1, axis=1)
    scale_k, scale_v = pool.scale_k, pool.scale_v
    if pool.quantized:
        sk_row = jax.lax.dynamic_slice_in_dim(scale_k, src, 1, axis=1)
        sv_row = jax.lax.dynamic_slice_in_dim(scale_v, src, 1, axis=1)
        scale_k = jax.lax.dynamic_update_slice_in_dim(
            scale_k, sk_row, dst, axis=1
        )
        scale_v = jax.lax.dynamic_update_slice_in_dim(
            scale_v, sv_row, dst, axis=1
        )
    return PagedKVPool(
        k=jax.lax.dynamic_update_slice_in_dim(pool.k, k_row, dst, axis=1),
        v=jax.lax.dynamic_update_slice_in_dim(pool.v, v_row, dst, axis=1),
        page_size=pool.page_size,
        scale_k=scale_k,
        scale_v=scale_v,
    )


def export_pages(
    pool: PagedKVPool, page_ids: tp.Sequence[int]
) -> tp.Tuple:
    """Pull ``n`` pages' K/V payloads (plus int8 scale planes) out of the
    pool as HOST arrays — the disaggregated cluster's page-handoff wire
    format (serving.cluster): a prefill-class engine exports a finished
    prompt's block-table-addressed pages, and a decode-class engine
    :func:`import_pages` them into ITS pool under freshly allocated ids.

    Returns ``(k, v, sk, sv)`` with ``k``/``v`` shaped
    ``[L, n, PS, Hkv*C]`` in the pool dtype (bf16 survives the numpy
    round-trip via ml_dtypes) and ``sk``/``sv`` the ``[L, n, Hkv]`` f32
    scale planes, or None for float pools. Payload and scales travel
    TOGETHER — a page and its scale are one atomic unit (copy_page's
    contract), and splitting them across the handoff would decode the
    moved prefix under a stale scale on the far side.

    Host round-trip on purpose: replica pools live on disjoint
    devices/meshes, so a device-to-device alias cannot cross them, and
    the numpy hop is the honest model of the DCN wire a multi-host
    deployment pays. Pages are COPIED, not moved — the source engine
    releases its ids through the normal cold-retire path afterwards, so
    its prefix cache keeps serving hits on the exported chain."""
    ids = jnp.asarray(list(page_ids), jnp.int32)
    # take() along the replicated page dim is shard-local under TP —
    # each shard gathers its own heads — and np.asarray gathers the
    # full [L, n, PS, Hkv*C] host copy across shards
    k = np.asarray(jnp.take(pool.k, ids, axis=1))
    v = None if pool.v is None else np.asarray(jnp.take(pool.v, ids, axis=1))
    sk = sv = None
    if pool.quantized:
        sk = np.asarray(jnp.take(pool.scale_k, ids, axis=1))
        sv = np.asarray(jnp.take(pool.scale_v, ids, axis=1))
    return k, v, sk, sv


def import_pages(
    pool: PagedKVPool,
    page_ids: tp.Sequence[int],
    k,
    v,
    sk=None,
    sv=None,
) -> PagedKVPool:
    """Write :func:`export_pages` payloads into ``pool`` at
    ``page_ids`` — the receiving half of the page handoff. Payload and
    scale planes land in one logical update (both or neither), the
    byte-exact inverse of the export: no arithmetic touches the values,
    so the imported pages read back bit-identically to the source pool
    (the disaggregated bit-identity gate rests on this).

    Runs eagerly (a handoff is once per request, not per dispatch);
    under TP the page dim is replicated and the head dim sharded, so
    the scatter is shard-local and GSPMD propagation keeps the pool's
    committed sharding, exactly like :func:`copy_page`."""
    n = len(list(page_ids))
    assert k.shape[1] == n and (v is None or v.shape[1] == n), (k.shape, n)
    assert (v is None) == (pool.v is None), "a latent pool has one payload"
    ids = jnp.asarray(list(page_ids), jnp.int32)
    # (layer, page) indexed together, like every pool writer: _layer_index
    li, pg = _layer_index(pool.k.shape[0]), ids[None, :]
    new_k = pool.k.at[li, pg].set(jnp.asarray(k, pool.k.dtype))
    new_v = None if v is None else pool.v.at[li, pg].set(
        jnp.asarray(v, pool.v.dtype)
    )
    scale_k, scale_v = pool.scale_k, pool.scale_v
    if pool.quantized:
        assert sk is not None and sv is not None, (
            "int8 pool import needs the exported scale planes — payload "
            "and scale are one atomic unit"
        )
        scale_k = scale_k.at[:, ids].set(jnp.asarray(sk, jnp.float32))
        scale_v = scale_v.at[:, ids].set(jnp.asarray(sv, jnp.float32))
    return PagedKVPool(
        k=new_k,
        v=new_v,
        page_size=pool.page_size,
        scale_k=scale_k,
        scale_v=scale_v,
    )
