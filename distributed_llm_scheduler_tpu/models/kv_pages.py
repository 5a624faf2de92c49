"""Block-allocated KV-cache pool: fixed-size pages + per-sequence tables.

The dense decode cache (:mod:`.decode`) reserves ``max_len`` rows for
every batch slot up front, so serving mixed-length traffic pays HBM for
the LONGEST request times the whole batch.  This module supplies the
vLLM-style alternative the Ragged Paged Attention line of work makes
TPU-native (PAPERS.md, arxiv 2604.15464): cache rows live in fixed-size
**pages** drawn from one shared pool, each sequence holds a **page
table** (logical page index -> physical page id), and a host-side
free-list allocator recycles pages as requests retire — so the pool is
sized for the *working set*, not ``slots x max_len``.

Three pieces, split by where they run:

* :class:`PagePool` — host-side free-list allocator with an
  HBM-budget-accounted capacity (``PagePool.from_budget`` sizes the pool
  off the device's reported memory via
  :func:`..utils.costmodel.device_hbm_bytes`).  Pure Python; never
  traced.
* :class:`CacheSpec` / :func:`init_paged_kv` — the device-side per-layer
  page pools, ``(n_pages, page_size, row_width)``: a token's cached
  values as one vector on the lanes, pages on the leading axis so one
  gather assembles a sequence and a kernel reads a page where it lies.
* scatter helpers (:func:`write_token_kv`, :func:`write_prompt_kv`,
  :func:`write_chunk_pages`) — static-shape jittable writes: one token's
  K/V row into its page slot (traced page id + slot), a whole prefilled
  prompt page-reshaped into its allocated pages, or a prefill chunk's
  whole pages at a traced position through the table row.

Physical page 0 is RESERVED as the trash page: unallocated page-table
entries point at it, and inactive batch slots redirect their writes to
it, so scatters never need a dynamic shape and gathers of a sequence's
unused tail read finite (masked-out) garbage instead of faulting.
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

#: Default tokens per page.  16 keeps page-granularity waste under one
#: MXU sublane tile at bf16 while still amortizing the table indirection.
DEFAULT_PAGE_SIZE = 16

#: Physical page id reserved for unallocated table entries and inactive
#: slot writes (never handed out by the allocator).
TRASH_PAGE = 0


#: Schema tag for :meth:`PageOwnershipLog.snapshot`.
OWNERSHIP_SCHEMA = "dls.pages/1"


class PageOwnershipLog:
    """Append-only page ownership event stream — the static third leg of
    the page-accounting story next to the runtime ``pages_leaked`` gauge.

    Producers record four core event kinds: ``alloc``/``free`` (the
    :class:`PagePool` itself, with the pool's free/used counts after the
    event — the tiling witness) and ``assign``/``release`` (the decode
    engine, with the owning request id and the lifecycle edge —
    ``admit``/``retire``/``preempt``/``reset``).  Prefix sharing adds
    four more: ``share``/``unshare`` (the pool, refcount up/down without
    touching the free list — physical tiling counts ride along
    unchanged), ``cow`` (the engine: ``pages=[src, dst]`` of a
    copy-on-write split, dst allocated BEFORE src is released), and
    ``write`` (the engine: first generation write into a page — the
    witness PGL007 checks against live refcounts).  Ref-counted events
    carry a ``refcounts`` list (post-event, aligned with ``pages``);
    non-sharing producers omit the key entirely so disabled-sharing
    streams are byte-identical to pre-sharing ones.  The page-lifetime
    prover (:mod:`..analysis.page_pass`) replays the stream against an
    ownership lattice; recording is a dict append per pool operation and
    is completely off (zero overhead, bit-identical engine behavior)
    when no log is attached — the same None-guard contract as the
    memory profiler seam.
    """

    def __init__(self, n_pages: Optional[int] = None):
        self.n_pages = n_pages
        self.events: List[Dict[str, Any]] = []
        #: set by an engine whose cache keeps pages this stream never
        #: sees (ring layers): what they are; the prover refuses (PGL008)
        self.uncovered: Optional[str] = None
        #: set by an engine whose cache keeps a state a slot (state
        #: layers): what it is; the prover refuses (PGL009)
        self.unkeyed: Optional[str] = None

    def record(
        self,
        kind: str,
        pages: Sequence[int],
        *,
        owner: Optional[str] = None,
        site: Optional[str] = None,
        free_pages: Optional[int] = None,
        used_pages: Optional[int] = None,
        refcounts: Optional[Sequence[int]] = None,
    ) -> None:
        e: Dict[str, Any] = {
            "seq": len(self.events),
            "kind": kind,
            "pages": [int(p) for p in pages],
            "owner": owner,
            "site": site,
            "free_pages": free_pages,
            "used_pages": used_pages,
        }
        if refcounts is not None:
            e["refcounts"] = [int(r) for r in refcounts]
        self.events.append(e)

    def __len__(self) -> int:
        return len(self.events)

    def snapshot(self) -> Dict[str, Any]:
        """JSON-ready view (schema ``dls.pages/1``) — what a serve/soak
        artifact embeds so ``doctor --serve`` can replay it offline."""
        out = {
            "schema": OWNERSHIP_SCHEMA,
            "n_pages": self.n_pages,
            "events": [dict(e) for e in self.events],
        }
        if self.uncovered:
            out["uncovered"] = self.uncovered
        if self.unkeyed:
            out["unkeyed"] = self.unkeyed
        return out


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages covering ``n_tokens`` rows (ceil division)."""
    if n_tokens < 0:
        raise ValueError(f"n_tokens must be >= 0, got {n_tokens}")
    return -(-n_tokens // page_size)


def prefix_chunk_keys(tokens: Any, page_size: int) -> List[str]:
    """Chain-hash intern keys for every FULL page of a token prefix.

    Key ``i`` digests the entire prefix ``tokens[0:(i+1)*page_size]``,
    not just page ``i``'s own tokens — a KV row depends on every token
    before it, so two pages are interchangeable only when their whole
    prefixes match.  Chaining gives that for free: each key extends the
    previous digest, so a match on key ``i`` implies matches on all
    earlier keys.  Only full pages get keys (a partial tail page is
    always exclusive — generation writes into it).
    """
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    toks = _flatten_tokens(tokens)
    h = hashlib.sha256()
    keys: List[str] = []
    for i in range(len(toks) // page_size):
        chunk = toks[i * page_size:(i + 1) * page_size]
        h.update((",".join(map(str, chunk)) + ";").encode())
        keys.append(h.hexdigest())
    return keys


def _flatten_tokens(tokens: Any) -> List[int]:
    """Host-side flatten of a token container (list, numpy row, or jax
    row) into plain ints — hashing never traces."""
    if hasattr(tokens, "reshape"):
        flat = tokens.reshape(-1)
        return [int(t) for t in flat.tolist()]
    return [int(t) for t in tokens]


def pool_bytes_per_layer(
    n_pages: int, page_size: int, n_kv_heads: int, head_dim: int, dtype: Any
) -> int:
    """HBM bytes of ONE layer's K+V pools at this geometry."""
    itemsize = jnp.dtype(dtype).itemsize
    return 2 * n_pages * page_size * n_kv_heads * head_dim * itemsize


@dataclass
class PagePool:
    """Host-side free-list page allocator over ``n_pages`` physical pages.

    Page ids are ints in ``[1, n_pages)`` — id 0 is :data:`TRASH_PAGE`
    and is never allocated.  ``alloc``/``free`` are O(k); exhaustion
    raises so callers (the continuous-batching engine) can hold requests
    queued instead of silently corrupting the pool — backpressure, not
    clamping.

    With ``sharing=True`` the pool additionally interns full prefix
    chunks (:func:`prefix_chunk_keys`): a resident page whose chain hash
    matches a new request's prefix is aliased via :meth:`share` instead
    of re-allocated, reference counts track logical owners per physical
    page, and :meth:`release_ref` returns a page to the LIFO free list
    only on last release.  The tiling witness generalizes — ``free +
    unique_used == n_pages - 1`` holds over *physical* pages at every
    event, while :attr:`logical_pages` counts what a non-sharing pool
    would have had to allocate.  With sharing off (the default) every
    page has refcount 1 and alloc/free behave — and record —
    bit-identically to the pre-sharing pool.
    """

    n_pages: int
    page_size: int = DEFAULT_PAGE_SIZE
    _free: List[int] = field(default_factory=list, repr=False)
    _allocated: set = field(default_factory=set, repr=False)
    #: optional :class:`PageOwnershipLog`; every alloc/free appends one
    #: event carrying the post-event free/used counts (the tiling
    #: witness).  None — the default — records nothing and costs nothing.
    ownlog: Optional[Any] = field(default=None, repr=False, compare=False)
    #: enable content-addressed prefix sharing (intern table + refcounts)
    sharing: bool = False
    _refs: Dict[int, int] = field(default_factory=dict, repr=False)
    _intern: Dict[str, int] = field(default_factory=dict, repr=False)
    _page_key: Dict[int, str] = field(default_factory=dict, repr=False)
    #: free pages whose intern entries are RETAINED (LRU cache of
    #: last-released shared prefixes).  Insertion-ordered dict used as an
    #: ordered set: insertion order == release order == eviction order.
    #: Always a subset of ``_free`` — cached pages are physically free
    #: (the books, the leak gauge, and the prover's tiling witness are
    #: untouched); only the intern table keeps pointing at them.
    _cached: Dict[int, None] = field(default_factory=dict, repr=False)

    def __post_init__(self) -> None:
        if self.n_pages < 2:
            raise ValueError(
                f"pool needs >= 2 pages (one is the reserved trash page), "
                f"got {self.n_pages}"
            )
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        # LIFO free list: recently-freed pages are re-issued first, which
        # keeps the hot working set compact
        self._free = list(range(self.n_pages - 1, TRASH_PAGE, -1))

    @classmethod
    def from_budget(
        cls,
        budget_bytes: int,
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        dtype: Any,
        page_size: int = DEFAULT_PAGE_SIZE,
    ) -> "PagePool":
        """Size the pool so ALL layers' K+V pools fit ``budget_bytes``.

        The budget is typically a fraction of
        :func:`..utils.costmodel.device_hbm_bytes` — the costmodel owns
        what the device reports, this allocator owns staying under it.
        """
        per_page = n_layers * pool_bytes_per_layer(
            1, page_size, n_kv_heads, head_dim, dtype
        )
        n_pages = int(budget_bytes // per_page)
        if n_pages < 2:
            raise ValueError(
                f"budget {budget_bytes} bytes fits {n_pages} page(s); "
                f"need >= 2 ({per_page} bytes/page across {n_layers} "
                "layers)"
            )
        return cls(n_pages=n_pages, page_size=page_size)

    # -- accounting --------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        """Physical pages allocated (unique — aliases count once)."""
        return len(self._allocated)

    @property
    def logical_pages(self) -> int:
        """Sum of refcounts: what a sharing-oblivious pool would hold.
        Equals :attr:`used_pages` whenever nothing is shared."""
        return sum(self._refs.values())

    @property
    def shared_pages(self) -> int:
        """Physical pages with more than one live reference."""
        return sum(1 for rc in self._refs.values() if rc > 1)

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    @property
    def cached_pages(self) -> int:
        """Free pages whose prefix intern entries are retained (LRU)."""
        return len(self._cached)

    def is_cached(self, page: int) -> bool:
        """True when ``page`` is physically free but its intern entry is
        retained — a :meth:`match_prefix` hit on it costs one free-list
        page to revive (admission counts it as physical demand)."""
        return int(page) in self._cached

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    # -- alloc / free ------------------------------------------------------
    def alloc(self, n: int) -> List[int]:
        """Take ``n`` pages off the free list; raises on exhaustion
        (callers queue the request — the pool never over-allocates)."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} pages")
        if n > len(self._free):
            raise MemoryError(
                f"page pool exhausted: want {n}, have {len(self._free)} "
                f"free of {self.n_pages - 1} allocatable"
            )
        if not self._cached:
            pages = [self._free.pop() for _ in range(n)]
        else:
            # lazy LRU eviction: serve uncached free pages first (LIFO,
            # as before), and only under pressure evict cached prefixes,
            # oldest release first — a popular prefix stays matchable
            # until the allocator actually needs its page
            pages = []
            held: List[int] = []
            while len(pages) < n and self._free:
                p = self._free.pop()
                if p in self._cached:
                    held.append(p)
                else:
                    pages.append(p)
            self._free.extend(reversed(held))
            for p in list(self._cached):
                if len(pages) >= n:
                    break
                self._evict_cached(p)
                self._free.remove(p)
                pages.append(p)
        self._allocated.update(pages)
        for p in pages:
            self._refs[p] = 1
        if self.ownlog is not None:
            self.ownlog.record(
                "alloc", pages,
                free_pages=len(self._free), used_pages=len(self._allocated),
            )
        return pages

    def alloc_for_tokens(self, n_tokens: int) -> List[int]:
        return self.alloc(pages_needed(n_tokens, self.page_size))

    def free(self, pages: Sequence[int]) -> None:
        """Return pages to the free list; double-free and trash-page
        frees are hard errors (a silent one would hand the same page to
        two sequences), and so is freeing a page other references still
        alias (callers drop refs via :meth:`release_ref`)."""
        pages = list(pages)
        for p in pages:
            if p == TRASH_PAGE:
                raise ValueError("page 0 is reserved and never allocated")
            if p not in self._allocated:
                raise ValueError(f"double free of page {p}")
            if self._refs.get(p, 1) > 1:
                raise ValueError(
                    f"page {p} is shared (refcount "
                    f"{self._refs[p]}); release the reference instead"
                )
            self._allocated.discard(p)
            self._free.append(p)
            self._refs.pop(p, None)
            if self.sharing and p in self._page_key:
                # retain the intern entry: the page is physically free
                # (books unchanged) but stays matchable until alloc
                # pressure evicts it — LRU via _cached insertion order
                self._cached[p] = None
            else:
                key = self._page_key.pop(p, None)
                if key is not None and self._intern.get(key) == p:
                    del self._intern[key]
        if self.ownlog is not None:
            self.ownlog.record(
                "free", pages,
                free_pages=len(self._free), used_pages=len(self._allocated),
            )

    def _evict_cached(self, p: int) -> None:
        """Drop a cached-free page's retained intern entry (the page
        itself stays wherever the free-list caller put it)."""
        del self._cached[p]
        key = self._page_key.pop(p, None)
        if key is not None and self._intern.get(key) == p:
            del self._intern[key]

    def drop_cached(self) -> int:
        """Evict EVERY retained intern entry, returning how many were
        dropped.  Engine reset must call this: reset reinitialises the
        physical KV arrays, so a retained entry would point a future
        :meth:`match_prefix` hit at zeroed storage — and a warm cache
        across runs would also make same-seed repeats diverge."""
        n = len(self._cached)
        for p in list(self._cached):
            self._evict_cached(p)
        return n

    # -- prefix sharing ----------------------------------------------------
    def match_prefix(self, keys: Sequence[str]) -> Tuple[int, List[int]]:
        """Longest resident run of ``keys`` (chain hashes, in prefix
        order): returns ``(h, pages)`` where the first ``h`` keys are
        interned and ``pages`` are their physical ids.  Pure lookup — no
        refcounts move until the caller commits with :meth:`share`."""
        if not self.sharing:
            return 0, []
        pages: List[int] = []
        for k in keys:
            p = self._intern.get(k)
            if p is None:
                break
            pages.append(p)
        return len(pages), pages

    def share(self, pages: Sequence[int]) -> None:
        """Take one additional reference on each page (aliasing commit).

        A RESIDENT page bumps its refcount; free/used counts are
        untouched and the ``share`` event carries them so the prover's
        physical tiling witness extends across sharing traffic.  A
        CACHED-FREE page (retained intern entry, see :meth:`free`) is
        REVIVED instead: it leaves the free list with refcount 1 and is
        recorded as a plain ``alloc`` — to the prover a revival is
        indistinguishable from a fresh allocation, which is exactly the
        physical truth.  Callers must share matched pages BEFORE
        allocating fresh ones, or alloc pressure may evict the match out
        from under them."""
        if not self.sharing:
            raise ValueError("share() on a pool with sharing disabled")
        revived: List[int] = []
        bumped: List[int] = []
        for p in pages:
            p = int(p)
            if p in self._cached:
                del self._cached[p]
                self._free.remove(p)
                self._allocated.add(p)
                self._refs[p] = 1
                revived.append(p)
            elif p in self._allocated:
                self._refs[p] = self._refs.get(p, 0) + 1
                bumped.append(p)
            else:
                raise ValueError(f"share of unallocated page {p}")
        if self.ownlog is not None:
            if revived:
                self.ownlog.record(
                    "alloc", revived,
                    free_pages=len(self._free),
                    used_pages=len(self._allocated),
                )
            if bumped:
                self.ownlog.record(
                    "share", bumped,
                    free_pages=len(self._free),
                    used_pages=len(self._allocated),
                    refcounts=[self._refs[p] for p in bumped],
                )

    def register(self, page: int, key: str) -> None:
        """Intern ``page`` under chain-hash ``key`` (first writer wins —
        a duplicate key keeps the incumbent so its aliases stay valid).
        No-op with sharing disabled."""
        if not self.sharing:
            return
        page = int(page)
        if page not in self._allocated:
            raise ValueError(f"register of unallocated page {page}")
        if key in self._intern or page in self._page_key:
            return
        self._intern[key] = page
        self._page_key[page] = key

    def release_ref(self, pages: Sequence[int]) -> None:
        """Drop one reference per page: last release frees physically
        (normal ``free`` event, page returns to the LIFO free list and
        its intern entry is evicted); earlier releases only decrement
        and record ``unshare``."""
        to_free: List[int] = []
        unshared: List[int] = []
        for p in pages:
            p = int(p)
            if p not in self._allocated:
                raise ValueError(f"release_ref of unallocated page {p}")
            rc = self._refs.get(p, 1)
            if rc <= 1:
                to_free.append(p)
            else:
                self._refs[p] = rc - 1
                unshared.append(p)
        if unshared and self.ownlog is not None:
            self.ownlog.record(
                "unshare", unshared,
                free_pages=len(self._free), used_pages=len(self._allocated),
                refcounts=[self._refs[p] for p in unshared],
            )
        if to_free:
            self.free(to_free)


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """One layer's entry of a :class:`CacheSpec`: its pools ``rows``,
    ``(pool kind, row shape)`` each, the latent ``rank`` where a pool is
    one (the row's leading values that are the latent itself — the part
    the absorbed kernel accumulates; the rest is the rotated key), and —
    for a **ring layer** — the ``window`` of positions a query may see
    (itself included).  ``window`` ``None``: the layer caches the whole
    context in pages of the shared pool, through the page table.
    ``q_heads``: the query heads that read this layer's kv rows, where
    the layers differ in them (else :attr:`CacheSpec.q_heads`).
    ``state``: a **state layer** — each of ``rows`` is one fixed-size
    array a SLOT (a state of any dtype), not a row a token.  ``rows`` may be
    empty: the layer caches nothing."""

    rows: Tuple[Tuple[str, Tuple[int, ...]], ...]
    rank: Optional[int] = None
    window: Optional[int] = None
    q_heads: Optional[int] = None
    state: bool = False


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What each layer caches for a token, and how the paged engine moves
    it: the cache description every family's config maps to (its
    family's ``cache_spec``; :func:`..cache_spec` asks by config).

    ``layers`` holds one :class:`LayerCache` a layer; a family whose
    layers are all alike (GPT-2, Llama, Xing4.0) builds it with
    :meth:`uniform`.  ``kind`` ``"kv"``: pools ``cache_k_{i}`` /
    ``cache_v_{i}`` with row ``(n_kv_heads, head_dim)``; the family's
    dense cache keeps heads ahead of positions, ``(L, b, Hkv, cap, hd)``.
    ``kind`` ``"latent"``: pools of row ``(width,)`` — MLA's normalised
    latent and shared rotated key ``cache_c_{i}``, and whatever else a
    layer keeps a token (an indexer's key); dense ``(L, b, cap, width)``.

    The stored form is one for every kind: a pool is ``(n_pages,
    page_size, row_width)``, the row's values flattened into ONE vector
    that lies on the lanes, pages major.  The device holds
    :func:`...ops.attention.lane_width` lanes for a row (whole 128-lane
    tiles) and a paged kernel reads a page as a ``(page_size,
    row_width)`` block of the argument itself.  Why one vector and not
    ``(heads, head_dim)``: the v5e compiler's default layout puts on the
    lanes whichever dimension pads least to whole tiles, and behind a
    64-wide ``head_dim`` that was the page INDEX, so every kernel call
    paid a transposing copy of the whole pool (PERF.md section 4).  A kv
    row is exactly ``n_kv_heads * head_dim`` wide — the kernel tells the
    heads apart by ``head_dim`` — and GPT-2 XL's 1,600 keeps the lanes
    beside a 16-row page at every pool size that fits; a latent row,
    whose 128-row page pads nothing, is padded by its model to
    ``lane_width`` (576 -> 640) to keep them.

    **Ring layers.**  A layer with a ``window`` is a ring layer: its
    pool is not paged out of the shared :class:`PagePool` but owned by
    the slots — ``ring_pages`` pages a slot in a pool of ``1 + slots *
    ring_pages`` pages (page 0 the trash page, slot ``s`` the pages
    :meth:`ring_table` lists), position ``p`` in ring row ``p mod
    (ring_pages * page_size)`` — so it costs nothing per context token,
    the allocator, admission and ``pages_needed`` never hear of it, and
    the same :func:`write_token_rows` writes it through the static ring
    table.  The dense cache the prefill programs hand the family holds,
    per pool kind, the layers that keep that kind stacked in layer
    order: ``{kind: (layers with it, b, cap, *row)}``, a ring kind
    ``cap`` = the ring's rows (of a ``"kv"`` spec heads ahead of
    positions as everywhere: a ring layer names its two pools apart
    from the paged layers', ``wk`` / ``wv`` beside ``k`` / ``v``).

    ``walk`` names the pool whose live blocks the decode step's paged
    kernel walks, ``(pool kind, rank)`` — what :meth:`resolve_impl` and
    :meth:`block_pages` ask about; ``None``: the first pool of layers
    that are all alike.

    **State layers.**  A layer with ``state`` keeps, for each of its
    pool kinds, ONE array a slot whatever the context's length (a
    recurrent mixer's state, a convolution's last inputs): pool ``(1 +
    slots, *shape)``, row 0 the trash row, slot ``s`` row ``1 + s``
    (:meth:`state_rows`) — slot-owned like a ring, so the allocator,
    admission and ``pages_needed`` never hear of it.  It is not indexed
    by position: a decode step and a prefill chunk each OVERWRITE it, and
    the step's layer task hands back the whole pool (updated in place
    for the slots that decode).  The dense cache of a prefill program
    holds, per state kind, the slots' states stacked in layer order
    ``(layers with it, b, *shape)``.  ``dtypes`` gives a pool kind its
    own dtype where it is not the cache's (a float32 SSM state beside
    bf16 K/V; a short convolution's carried rows keep the cache's).

    **Passes.**  A stack whose ``layers`` run ``passes`` times a token on
    one set of weights keeps a row a (pass, layer): a cache entry is no
    longer a weight layer.  A layer's pool is still ONE array,
    ``passes`` *planes* of ``n_pages`` pages each — pass ``u`` reads and
    writes through ``page_table + u * n_pages`` (:meth:`plane`; page 0 of
    a plane is that plane's trash page) — so one page id names a token's
    rows in every pass of every layer exactly as it names them in every
    layer, and the allocator, admission, preemption, retirement and the
    kernels never hear of passes.  The dense cache holds, per kind,
    entry ``u * layers + l`` for pass ``u`` of layer ``l``.

    **Draft layers.**  The last ``draft_layers`` entries are the layers
    of a draft module the family steps itself with (``models.
    DRAFT_FUNCTIONS``): pools like any other, over the same positions,
    pages and table, written by the step's ``draft`` task and, in a
    prefill program, by the family's ``forward_cached_draft``."""

    kind: str
    layers: Tuple[LayerCache, ...]
    #: kv: the query heads, where they are not the kv heads (GQA)
    q_heads: Optional[int] = None
    #: rows a ring layer keeps for a slot (>= its window)
    ring_rows: int = 0
    walk: Optional[Tuple[str, Optional[int]]] = None
    draft_layers: int = 0
    #: ``(pool kind, dtype)`` of the kinds not kept in the cache's dtype
    dtypes: Tuple[Tuple[str, Any], ...] = ()
    #: times a token runs through ``layers`` (a looped stack): each pass
    #: keeps rows of its own, in a plane of the layer's pool
    passes: int = 1

    def __post_init__(self) -> None:
        if self.passes < 1:
            raise ValueError(f"passes must be >= 1, got {self.passes}")
        if self.passes > 1 and (self.has_rings or self.has_state
                                or self.draft_layers):
            raise ValueError(
                "a cache whose layers run more than once is built for "
                "paged layers only: a ring or a state is a slot's, one a "
                "layer, and a draft module is stepped once")

    @classmethod
    def uniform(cls, kind: str, n_layers: int,
                rows: Tuple[Tuple[str, Tuple[int, ...]], ...],
                q_heads: Optional[int] = None,
                rank: Optional[int] = None) -> "CacheSpec":
        """Every layer alike: ``rows`` / ``rank`` of each."""
        return cls(kind, (LayerCache(rows, rank),) * n_layers, q_heads)

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def _alike(self) -> LayerCache:
        """The one entry of layers that are all alike; a spec whose
        layers differ has no answer for the whole model."""
        first = self.layers[0]
        if any(lc != first for lc in self.layers):
            raise ValueError(
                "this cache's layers differ: ask layer(i) / layer_kinds(i)")
        return first

    @property
    def rows(self) -> Tuple[Tuple[str, Tuple[int, ...]], ...]:
        """``(pool kind, row shape)`` of each pool of layers all alike."""
        return self._alike().rows

    @property
    def rank(self) -> Optional[int]:
        return self._alike().rank

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The pool kinds of layers all alike."""
        return tuple(k for k, _ in self.rows)

    def layer(self, i: int) -> LayerCache:
        return self.layers[i]

    def layer_kinds(self, i: int) -> Tuple[str, ...]:
        return tuple(k for k, _ in self.layers[i].rows)

    @property
    def has_rings(self) -> bool:
        return any(lc.window is not None for lc in self.layers)

    @property
    def has_state(self) -> bool:
        return any(lc.state for lc in self.layers)

    @staticmethod
    def state_rows(slots):
        """The rows of a state layer's pools that ``slots`` own, int32
        (numpy): slot ``s`` owns row ``1 + s``; row 0 is the trash row."""
        import numpy as np

        return 1 + np.asarray(slots, np.int32)

    def owned(self, *args) -> Dict[str, Any]:
        """:meth:`gather` / :meth:`scatter`'s keywords out of the
        positional arguments a prefill program takes for what its slots
        own outright: the ring pages where the spec has ring layers,
        then the state rows where it has state layers."""
        names = [n for n, has in (("ring", self.has_rings),
                                  ("state", self.has_state)) if has]
        return dict(zip(names, args, strict=True))

    def kind_dtype(self, kind: str, dtype: Any) -> Any:
        """The dtype pool kind ``kind`` is kept in: its own, or ``dtype``."""
        return dict(self.dtypes).get(kind, dtype)

    def ring_pages(self, page_size: int) -> int:
        """Pages a slot owns in each ring layer's pool."""
        return -(-self.ring_rows // page_size)

    def ring_table(self, slots: int, page_size: int):
        """The static table of the ring pools, ``(slots, ring_pages)``
        int32 (numpy): slot ``s`` owns pages ``1 + s * ring_pages + j``."""
        import numpy as np

        rp = self.ring_pages(page_size)
        return (1 + np.arange(slots * rp, dtype=np.int32)).reshape(slots, rp)

    def _pools(self):
        """Every pool of rows a token as ``(layer, kind, row, n, window)``,
        ``n`` the layer's index among the layers that keep ``kind`` (the
        layer itself where all are alike); a state layer's are
        :meth:`_states`'."""
        seen: Dict[str, int] = {}
        for i, lc in enumerate(self.layers):
            for kind, row in () if lc.state else lc.rows:
                n = seen.get(kind, 0)
                seen[kind] = n + 1
                yield i, kind, row, n, lc.window

    def _states(self):
        """Every state layer's pool as ``(layer, kind, shape, n)``."""
        seen: Dict[str, int] = {}
        for i, lc in enumerate(self.layers):
            for kind, shape in lc.rows if lc.state else ():
                n = seen.get(kind, 0)
                seen[kind] = n + 1
                yield i, kind, shape, n

    @property
    def head_dim(self) -> Optional[int]:
        """What splits a stored kv row into heads; a latent row has none."""
        return self._walked()[0][-1] if self.kind == "kv" else None

    def _walked(self) -> Tuple[Tuple[int, ...], Optional[int]]:
        """``(row, rank)`` of the pool the decode step's kernel walks."""
        if self.walk is None:
            return self.rows[0][1], self.rank
        kind, rank = self.walk
        return next(row for lc in self.layers for k, row in lc.rows
                    if k == kind), rank

    def _walked_q_heads(self) -> Optional[int]:
        """The query heads that read the walked pool: its first layer's
        where a kv spec says them per layer, else the model's one number."""
        kind = self.walk[0] if self.walk else self.layers[0].rows[0][0]
        first = next(lc for lc in self.layers
                     if kind in (k for k, _ in lc.rows))
        return first.q_heads or self.q_heads

    def resolve_impl(self, impl: Optional[str], slots: int, n_pages: int,
                     page_size: int, dtype: Any) -> str:
        """What this cache's paged decode attention runs at this geometry
        on this backend (``impl`` may be None / ``"auto"``): the op's own
        rule, asked from the host.  An explicit kernel request that the
        geometry cannot honour raises."""
        from ..ops.attention import resolve_mla_paged_impl, resolve_paged_impl

        row, rank = self._walked()
        if self.kind == "latent":
            return resolve_mla_paged_impl(
                impl, page_size, row[0], rank, dtype)
        n_kv, hd = row
        return resolve_paged_impl(
            impl, (slots, self._walked_q_heads() or n_kv, 1, hd),
            (n_pages, page_size, n_kv * hd), dtype)

    def block_pages(self, page_size: int, pages_per_seq: int,
                    dtype: Any) -> int:
        """Pages in one block of the paged decode kernel's walk over this
        cache's pools (``page_size *`` this is the rows of a block)."""
        from ..ops.attention import latent_block_pages, paged_block_pages

        row, _ = self._walked()
        if self.kind == "latent":
            return latent_block_pages(page_size, pages_per_seq, row[0], dtype)
        return paged_block_pages(page_size, pages_per_seq, *row, dtype)

    @property
    def row_elems(self) -> int:
        """Values one token occupies in one layer, all pools (layers all
        alike)."""
        return sum(math.prod(r) for _, r in self.rows)

    @property
    def paged_row_elems(self) -> int:
        """Values one token occupies in the shared pages, all layers and
        all passes: a ring layer holds none there."""
        return self.passes * sum(
            math.prod(row) for _, _, row, _, window in self._pools()
            if window is None)

    def plane(self, pool: jax.Array, table: Any, u: Any) -> Any:
        """``table`` (page ids, any shape) as pass ``u`` (static or
        traced) of a paged layer reads and writes ``pool`` through it:
        shifted into the pass's plane of ``pool.shape[0] // passes``
        pages.  Pass 0's, and every pass of a one-pass cache, is
        ``table`` itself."""
        if self.passes == 1:
            return table
        return table + u * (pool.shape[0] // self.passes)

    def init_pools(self, n_pages: int, page_size: int, dtype: Any,
                   slots: Optional[int] = None) -> Dict[str, jax.Array]:
        """Zeroed pools keyed ``cache_{kind}_{i}``, in the stored form
        (a paged layer's ``passes`` planes of ``n_pages`` pages); a ring
        layer's holds ``slots`` rings and the trash page, a state layer's
        ``slots`` states and the trash row."""
        if (self.has_rings or self.has_state) and slots is None:
            raise ValueError("a spec with ring or state layers sizes their "
                             "pools by the engine's slots")
        ring = 1 + (slots or 0) * self.ring_pages(page_size)
        pools = {
            f"cache_{kind}_{i}": jnp.zeros(
                (self.passes * n_pages if window is None else ring,
                 page_size, math.prod(row)), self.kind_dtype(kind, dtype))
            for i, kind, row, _, window in self._pools()
        }
        pools.update({
            f"cache_{kind}_{i}": jnp.zeros(
                (1 + slots, *shape), self.kind_dtype(kind, dtype))
            for i, kind, shape, _ in self._states()})
        return pools

    def init_slabs(self, batch: int, cap: int,
                   dtype: Any) -> Dict[str, jax.Array]:
        """Zeroed dense per-layer slabs keyed ``cache_{kind}_{i}``: what
        the dense decode-step DAG places (one layer of
        :meth:`init_dense` each; layers all alike)."""
        return {
            f"cache_{kind}_{i}": jnp.zeros(
                self._dense(kind, (batch, cap, *row)), dtype)
            for i in range(self.n_layers) for kind, row in self.rows
        }

    def init_dense(self, batch: int, cap: int, dtype: Any,
                   page_size: Optional[int] = None,
                   in_pages: bool = False) -> Dict[str, Any]:
        """The family's zeroed dense cache ``{kind: (L, b, ...)}``; per
        layer, ``L`` counts the layers that keep the kind and a ring
        kind's ``cap`` is the ring (whole pages of ``page_size``); with
        ``passes`` ``L`` counts each of them once a pass.  ``in_pages``:
        the ring kinds alone — the paged layers stay in their pages
        (:meth:`gather`).  A state kind: ``(L, b, *shape)``."""
        ring = self.ring_pages(page_size or 1) * (page_size or 1)
        shapes: Dict[str, Any] = {}
        for _, kind, row, n, window in self._pools():
            if in_pages and window is None:
                continue
            shapes[kind] = ((n + 1) * self.passes, *self._dense(
                kind, (batch, cap if window is None else ring, *row)))
        for _, kind, shape, n in self._states():
            shapes[kind] = (n + 1, batch, *shape)
        return {kind: jnp.zeros(shape, self.kind_dtype(kind, dtype))
                for kind, shape in shapes.items()}

    def _dense(self, kind: str, shape):
        if self.kind == "kv":      # (b, cap, Hkv, hd) -> (b, Hkv, cap, hd)
            return (shape[0], shape[2], shape[1], shape[3])
        return tuple(shape)

    def to_rows(self, dense_layer: jax.Array) -> jax.Array:
        """One layer of the dense cache as ``(b, cap, *row)``."""
        if self.kind == "kv":
            return dense_layer.transpose(0, 2, 1, 3)
        return dense_layer

    def gather(self, cache: Dict[str, Any], pools: Dict[str, Any],
               pages: jax.Array, batch: int, n_rows: int,
               ring: Optional[jax.Array] = None,
               in_pages: bool = False,
               state: Optional[jax.Array] = None) -> Dict[str, Any]:
        """``cache`` with rows ``[0, n_rows)`` of every layer filled from
        the pools through ``pages`` (flat physical ids, ``batch`` runs);
        a ring layer's whole ring through ``ring`` (the slots' rows of
        :meth:`ring_table`, flat).  ``in_pages``: a paged layer is not
        made dense — its kind's entry is a tuple of the pools themselves,
        in the stored form, one a layer that keeps the kind, for a family
        whose prefill writes and reads them through the page table.  A
        state layer's states through ``state`` (the sequences' rows of
        :meth:`state_rows`, ``(batch,)``)."""
        out = dict(cache)
        for i, kind, _, n in self._states():
            out[kind] = out[kind].at[n].set(
                jnp.take(pools[f"cache_{kind}_{i}"], state, axis=0))
        for i, kind, row, n, window in self._pools():
            if in_pages and window is None:
                out[kind] = out.get(kind, ()) + (pools[f"cache_{kind}_{i}"],)
                continue
            take, rows_n = pages, n_rows
            if window is not None:   # the ring whole: its dense rows
                take = ring
                rows_n = out[kind].shape[3 if self.kind == "kv" else 2]
            pool = pools[f"cache_{kind}_{i}"]
            for u, n_u in self._entries(kind, n):
                rows = jnp.take(pool, self.plane(pool, take, u), axis=0)
                rows = self._from_rows(rows.reshape(batch, rows_n, *row))
                at = ((n_u, slice(None), slice(None), slice(0, rows_n))
                      if self.kind == "kv" else
                      (n_u, slice(None), slice(0, rows_n)))
                out[kind] = out[kind].at[at].set(
                    rows.astype(out[kind].dtype))
        return out

    def _entries(self, kind: str, n: int):
        """``(pass, dense entry)`` of the ``n``-th layer that keeps
        ``kind``: entry ``u * layers with the kind + n`` for pass ``u``."""
        if self.passes == 1:
            return ((0, n),)
        per_pass = sum(1 for _, k, *_ in self._pools() if k == kind)
        return tuple((u, u * per_pass + n) for u in range(self.passes))

    def _from_rows(self, rows: jax.Array) -> jax.Array:
        return rows.transpose(0, 2, 1, 3) if self.kind == "kv" else rows

    def scatter(self, pools: Dict[str, Any], cache: Dict[str, Any],
                pages: jax.Array, page_size: int,
                ring: Optional[jax.Array] = None,
                in_pages: bool = False,
                state: Optional[jax.Array] = None) -> Dict[str, Any]:
        """``pools`` with every page in ``pages`` (flat physical ids,
        covering each sequence's whole capacity) rewritten from the dense
        ``cache``, a ring layer's through ``ring``; out-of-range ids are
        dropped.  ``in_pages``: a paged layer's entry IS its pool
        (:meth:`gather`), the rows already written where they lie.  A
        state layer's rows ``state`` are overwritten whole."""
        new = dict(pools)
        for i, kind, _, n in self._states():
            pool = new[f"cache_{kind}_{i}"]
            new[f"cache_{kind}_{i}"] = pool.at[state].set(
                cache[kind][n].astype(pool.dtype))
        for i, kind, _, n, window in self._pools():
            if in_pages and window is None:
                new[f"cache_{kind}_{i}"] = cache[kind][n]
                continue
            into = pages if window is None else ring
            pool = new[f"cache_{kind}_{i}"]
            for u, n_u in self._entries(kind, n):
                rows = self.to_rows(cache[kind][n_u])
                paged = rows.reshape(into.shape[0], page_size, -1)
                pool = pool.at[self.plane(pool, into, u)].set(
                    paged.astype(pool.dtype), mode="drop")
            new[f"cache_{kind}_{i}"] = pool
        return new


def init_paged_kv(
    n_layers: int,
    n_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    dtype: Any,
) -> Dict[str, jax.Array]:
    """Zeroed per-layer page pools keyed ``cache_k_{i}`` / ``cache_v_{i}``
    — the same naming contract the dense decode DAG uses, so
    ``split_cache_params`` and the analysis passes treat paged and dense
    caches uniformly.  Stored form ``(n_pages, page_size, n_kv_heads *
    head_dim)`` (:class:`CacheSpec`): pages lead, so assembling a
    sequence is one gather on axis 0."""
    row = (n_kv_heads, head_dim)
    return CacheSpec.uniform(
        "kv", n_layers, (("k", row), ("v", row))).init_pools(
            n_pages, page_size, dtype)


def page_table_array(
    tables: Sequence[Sequence[int]], pages_per_seq: int
) -> jax.Array:
    """Stack per-sequence page-id lists into the device table
    ``(slots, pages_per_seq) int32``, padding unallocated entries with
    the trash page."""
    rows = []
    for t in tables:
        if len(t) > pages_per_seq:
            raise ValueError(
                f"sequence holds {len(t)} pages > pages_per_seq "
                f"{pages_per_seq}"
            )
        rows.append(list(t) + [TRASH_PAGE] * (pages_per_seq - len(t)))
    return jnp.asarray(rows, jnp.int32)


def write_token_rows(
    pool: jax.Array,
    rows: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    active: jax.Array,
) -> jax.Array:
    """Scatter one step's rows into their page slots.

    ``pool`` (P, ps, row_width); ``rows`` (S, ...) — this step's row per
    slot, its ``row_width`` values in whatever shape a layer task emits
    them (K or V heads ``(Hkv, 1, hd)``, a latent ``(width,)``);
    ``page_table`` (S, pages_per_seq) int32; ``lengths`` (S,) int32 —
    tokens already cached per slot (the write position); ``active`` (S,)
    bool.  Inactive slots write the trash page, so the scatter stays
    static-shape under an admission/retirement mask.
    """
    n_pages, ps, width = pool.shape
    s_idx = jnp.arange(page_table.shape[0])
    logical = jnp.where(active, lengths // ps, 0)
    pid = jnp.where(active, page_table[s_idx, logical], TRASH_PAGE)
    slot = jnp.where(active, lengths % ps, 0)
    rows = rows.reshape(rows.shape[0], width).astype(pool.dtype)
    # flat row index: one 1-D scatter instead of a 2-D one (inactive
    # slots land in the trash page's row 0)
    flat = pool.reshape(n_pages * ps, width)
    flat = flat.at[pid * ps + slot].set(rows, mode="drop")
    return flat.reshape(pool.shape)


#: the K/V name of :func:`write_token_rows` (one function since the pools
#: hold every kind's row as one vector)
write_token_kv = write_token_rows


def write_step_rows(
    pool: jax.Array,
    rows: jax.Array,
    page_table: jax.Array,
    lengths: jax.Array,
    active: jax.Array,
) -> jax.Array:
    """:func:`write_token_rows` for a step of ``R`` rows a slot (one that
    verifies drafts): ``rows`` (S, R, ...) land at positions ``lengths
    .. lengths + R - 1`` in ONE scatter.  A slot's later rows may lie on
    its next page; every row of an inactive slot goes to the trash page."""
    n_pages, ps, width = pool.shape
    S, R = rows.shape[:2]
    pos = lengths[:, None] + jnp.arange(R, dtype=lengths.dtype)[None, :]
    on = active[:, None]
    logical = jnp.where(on, pos // ps, 0)
    pid = jnp.where(
        on, page_table[jnp.arange(S)[:, None], logical], TRASH_PAGE)
    slot = jnp.where(on, pos % ps, 0)
    flat = pool.reshape(n_pages * ps, width)
    flat = flat.at[(pid * ps + slot).reshape(-1)].set(
        rows.reshape(S * R, width).astype(pool.dtype), mode="drop")
    return flat.reshape(pool.shape)


def write_prompt_kv(
    pool: jax.Array, rows: jax.Array, pages: jax.Array
) -> jax.Array:
    """Scatter a prefilled prompt's rows into a sequence's pages.

    ``rows`` (cap, Hkv, hd) — the sequence's cache rows padded to its
    full page capacity ``cap = len(pages) * page_size``; ``pages``
    (n_pages_seq,) int32 physical ids (tail entries may be the trash
    page — overwriting it is harmless by design).
    """
    n_pg = pages.shape[0]
    ps = pool.shape[1]
    if rows.shape[0] != n_pg * ps:
        raise ValueError(
            f"rows cover {rows.shape[0]} tokens, pages cover {n_pg * ps}"
        )
    paged = rows.reshape(n_pg, ps, -1).astype(pool.dtype)
    return pool.at[pages].set(paged, mode="drop")


def write_chunk_pages(
    pool: jax.Array, rows: jax.Array, pages: jax.Array, pos0: jax.Array
) -> jax.Array:
    """Write a prefill chunk's rows where they lie: ``rows`` (b, T, ...)
    at positions ``pos0 + t`` — ``pos0`` (may be traced) and ``T`` whole
    pages — into pages ``pages[s, pos0 // page_size + j]`` of ``pool``
    (n_pages, page_size, row_width); ``pages`` (b, pages_per_seq) the
    sequences' table rows.  Only these ``T / page_size`` pages a sequence
    are touched; a page past the table's end goes to the trash page, as a
    table entry past the sequence's claimed pages already does."""
    b, T = rows.shape[:2]
    at = pos0 // pool.shape[1] + jnp.arange(
        T // pool.shape[1], dtype=jnp.int32)
    ids = jnp.where(
        at < pages.shape[1],
        jnp.take(pages, jnp.minimum(at, pages.shape[1] - 1), axis=1),
        TRASH_PAGE)
    return write_prompt_kv(pool, rows.reshape(b * T, -1), ids.reshape(-1))


def gather_kv(
    pool: jax.Array, page_table: jax.Array, head_dim: int
) -> jax.Array:
    """Assemble per-sequence contiguous KV views from the pool.

    ``pool`` (P, ps, Hkv * hd), ``page_table`` (S, n_pg) ->
    ``(S, Hkv, n_pg * ps, hd)`` — the dense-cache orientation
    (:func:`..models.decode.cached_attention`), so downstream attention
    math is shared verbatim with the dense path.  Unallocated table
    entries gather the trash page; its rows are masked by the caller's
    per-sequence lengths.

    Pays a materializing transpose to reach the dense orientation —
    right for oracles and tests; the hot attention path uses
    :func:`gather_kv_flat` instead.
    """
    return gather_kv_flat(pool, page_table, head_dim).transpose(0, 2, 1, 3)


def gather_kv_flat(
    pool: jax.Array, page_table: jax.Array, head_dim: int
) -> jax.Array:
    """Token-major per-sequence view: ``(S, n_pg * ps, Hkv, hd)``.

    Same gather as :func:`gather_kv` but WITHOUT the transpose to the
    dense orientation — the reshape is free on the gather's contiguous
    output (pages arrive token-major already, a row's heads side by
    side), so this is the layout the per-step XLA attention path uses;
    the caller permutes its ``dot_general`` batch dims instead of the
    data.  Token order is identical to the dense view's, so
    score/softmax reductions see the same operands in the same logical
    order (the bitwise-parity invariant the op tests pin).
    """
    S, n_pg = page_table.shape
    pages = jnp.take(pool, page_table.reshape(-1), axis=0)
    return pages.reshape(S, n_pg * pool.shape[1], -1, head_dim)


def paged_param_bytes(
    n_layers: int,
    n_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    dtype: Any,
    slots: int,
    pages_per_seq: int,
) -> Dict[str, int]:
    """Byte sizes of every paged-cache param the decode DAG declares —
    the page-residency numbers placement and the DEC analysis pass see."""
    per_pool = pool_bytes_per_layer(
        n_pages, page_size, n_kv_heads, head_dim, dtype
    ) // 2
    out: Dict[str, int] = {}
    for i in range(n_layers):
        out[f"cache_k_{i}"] = per_pool
        out[f"cache_v_{i}"] = per_pool
    out["page_table"] = slots * pages_per_seq * 4
    return out


__all__ = [
    "DEFAULT_PAGE_SIZE",
    "OWNERSHIP_SCHEMA",
    "TRASH_PAGE",
    "PageOwnershipLog",
    "PagePool",
    "CacheSpec",
    "LayerCache",
    "pages_needed",
    "prefix_chunk_keys",
    "pool_bytes_per_layer",
    "init_paged_kv",
    "page_table_array",
    "write_token_kv",
    "write_chunk_pages",
    "write_prompt_kv",
    "gather_kv",
    "gather_kv_flat",
    "paged_param_bytes",
]
