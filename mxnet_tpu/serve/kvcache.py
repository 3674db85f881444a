"""Paged KV-cache storage for mx.serve.decode.

Decoder-LLM serving state is the KV cache, and the KV cache is why
fixed-shape batching fails for autoregressive traffic: every sequence
has a different length at every step, and a contiguous
``[batch, max_len, ...]`` allocation wastes ``O(max_len)`` device
memory per sequence from token one.  The fix (vLLM's PagedAttention)
is blocked storage: the cache is a pool of fixed-size **pages**
(``page_size`` token slots each), and each sequence owns a *page
table* — an ordered list of physical page ids its logical positions
map onto.  Admission reserves a sequence's whole worst case
(``ceil((prompt + max_new_tokens) / page_size)`` pages) up front, so a
running sequence can NEVER hit an allocation failure mid-decode: OOM
is a fast, explicit reject at the admission door, not a crash three
hundred tokens in.

``PagePool`` owns:

- the device-resident cache arrays — one K and one V array shaped
  ``[layers, num_pages, page_size, kv_heads, head_dim]``, threaded
  through the jitted decode-step program with buffer donation (the
  pool is updated in place, never copied per step);
- exact occupancy accounting: ``alloc`` / ``release`` / ``reset`` with
  a free list, per-owner page ledger, in-use / high-water counters,
  and hard invariants (double-free and unknown-owner release raise —
  a leaked page is a serving-capacity leak that compounds forever);
- the **shared segment** backing the mx.serve.cache radix prefix
  cache: ``adopt_shared`` moves immutable prefix pages out of one
  owner's ledger into a refcounted shared pool (``shared_ref`` /
  ``shared_unref``), so identical prompt prefixes are stored once and
  read by many sequences copy-on-write.  A shared page returns to the
  free list only when its LAST reference drops — an evicted prefix
  never yanks storage out from under a live reader — and ``check()``
  audits ``free + owned + shared == capacity`` with the same
  double-free-raises discipline.

The jax-side page-table address arithmetic lives here too so the
decode-step program and the pool agree on the layout by construction:
``gather_pages`` materializes a sequence's pages as a contiguous
``[B, L, S, H, D]`` context (clamp-mode gather: table slots past a
sequence's allocation read garbage that the attention length mask
provably ignores), and ``scatter_pages`` writes the step's fresh K/V
into ``(page, slot)`` addresses (drop-mode scatter: padded batch slots
and padded prompt positions carry an out-of-bounds page id and write
nowhere).
"""
from __future__ import annotations

import threading

from .batching import ServeError

__all__ = ["PageConfig", "PagePool", "PagePoolExhausted",
           "gather_pages", "scatter_pages"]


class PagePoolExhausted(ServeError):
    """Not enough free pages for the requested reservation.  Raised at
    ADMISSION time (fast OOM-reject) — never mid-decode, because
    admission reserves a sequence's whole worst case up front."""


class PageConfig:
    """Geometry of one paged KV pool: pool shape (``page_size`` token
    slots per page x ``num_pages`` pages) plus the per-token cache
    shape of the model it serves (``num_layers`` x ``num_kv_heads`` x
    ``head_dim``, ``dtype``).  ``max_context`` bounds any single
    sequence (prompt + generated); it must fit the pool."""

    def __init__(self, page_size, num_pages, num_layers, num_kv_heads,
                 head_dim, max_context, dtype="float32"):
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.max_context = int(max_context)
        self.dtype = dtype
        if self.page_size < 1 or self.num_pages < 1:
            raise ValueError("page_size and num_pages must be >= 1, got "
                             "%d / %d" % (self.page_size, self.num_pages))
        if self.max_context < 1:
            raise ValueError("max_context must be >= 1")
        if self.pages_per_seq > self.num_pages:
            raise ValueError(
                "max_context=%d needs %d pages/sequence but the pool "
                "only has %d pages total" % (
                    self.max_context, self.pages_per_seq, self.num_pages))

    @property
    def pages_per_seq(self):
        """Page-table width: the worst-case pages one sequence can own."""
        return -(-self.max_context // self.page_size)

    def pages_for(self, total_tokens):
        """Pages a sequence of ``total_tokens`` (prompt + max new) must
        reserve at admission."""
        return max(1, -(-int(total_tokens) // self.page_size))

    @property
    def page_bytes(self):
        import numpy as _np

        return (self.num_layers * self.page_size * self.num_kv_heads *
                self.head_dim * _np.dtype(self.dtype).itemsize * 2)

    def as_dict(self):
        return {"page_size": self.page_size, "num_pages": self.num_pages,
                "num_layers": self.num_layers,
                "num_kv_heads": self.num_kv_heads,
                "head_dim": self.head_dim,
                "max_context": self.max_context,
                "pages_per_seq": self.pages_per_seq,
                "dtype": str(self.dtype),
                "pool_bytes": self.num_pages * self.page_bytes}


class PagePool:
    """Blocked KV-cache storage + exact page accounting (module doc).

    The device arrays ``k`` / ``v`` are plain attributes the decode
    loop re-binds after every donated step dispatch; accounting is
    host-side and lock-protected (admission runs on submitter threads,
    release on the decode loop)."""

    def __init__(self, config, mesh=None, device=None):
        self.config = config
        c = config
        # mx.shard phase 2: on a mesh with an mdl axis the pool shards
        # over the KV-HEAD axis (per-head attention state is
        # independent, so a head split never slices a page row) — each
        # device holds 1/mdl of the cache, which is what makes
        # multi-chip decode residency real.  Indivisible head counts
        # stay replicated (correct, just not smaller).  Without a mesh
        # the pool lives on ``device`` (None: JAX's default device).
        self.sharding = None
        self._device = device
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            raw = getattr(mesh, "mesh", mesh)   # GlobalMesh or raw Mesh
            axes = dict(getattr(raw, "shape", {}) or {})
            mdl = int(axes.get("mdl", 1))
            spec = P(None, None, None, "mdl", None) \
                if mdl > 1 and c.num_kv_heads % mdl == 0 else P()
            self.sharding = NamedSharding(raw, spec)
        self.reset_storage()
        self._lock = threading.Lock()
        self._free = list(range(c.num_pages - 1, -1, -1))  # pop() -> 0,1,2..
        self._owned = {}                 # owner -> [page ids]
        self._shared = {}                # page id -> refcount (>= 1)
        self.high_water = 0
        self.alloc_total = 0
        self.oom_rejects = 0

    def reset_storage(self):
        """(Re)allocate zeroed K/V storage where the pool lives: on its
        mesh sharding, its device, or JAX's default device."""
        import jax.numpy as jnp

        c = self.config
        shape = (c.num_layers, c.num_pages, c.page_size,
                 c.num_kv_heads, c.head_dim)
        where = self.sharding if self.sharding is not None \
            else self._device
        self.k = jnp.zeros(shape, dtype=c.dtype, device=where)
        self.v = jnp.zeros(shape, dtype=c.dtype, device=where)

    # -- accounting ---------------------------------------------------------
    @property
    def capacity(self):
        return self.config.num_pages

    @property
    def available(self):
        with self._lock:
            return len(self._free)

    @property
    def in_use(self):
        with self._lock:
            return self.config.num_pages - len(self._free)

    @property
    def null_page(self):
        """Out-of-bounds page id padded page-table slots carry: the
        drop-mode scatter writes addressed to it write nowhere."""
        return self.config.num_pages

    def can_alloc(self, n):
        with self._lock:
            return n <= len(self._free)

    def alloc(self, owner, n):
        """Reserve ``n`` pages for ``owner`` (all-or-nothing).  Raises
        ``PagePoolExhausted`` without touching anything when fewer than
        ``n`` pages are free — the fast OOM-reject admission control
        leans on."""
        n = int(n)
        if n < 1:
            raise ValueError("alloc needs n >= 1, got %d" % n)
        with self._lock:
            if owner in self._owned:
                raise ServeError("owner %r already holds pages" % (owner,))
            if n > len(self._free):
                self.oom_rejects += 1
                raise PagePoolExhausted(
                    "KV page pool exhausted: %d page(s) requested, %d free "
                    "of %d (page_size=%d); admission must wait for "
                    "evictions" % (n, len(self._free),
                                   self.config.num_pages,
                                   self.config.page_size))
            pages = [self._free.pop() for _ in range(n)]
            self._owned[owner] = pages
            self.alloc_total += n
            used = self.config.num_pages - len(self._free)
            if used > self.high_water:
                self.high_water = used
            return list(pages)

    def release(self, owner):
        """Return every page ``owner`` holds.  Unknown owners raise —
        a silent no-op would hide the double-free/leak bugs the
        accounting exists to catch."""
        with self._lock:
            pages = self._owned.pop(owner, None)
            if pages is None:
                raise ServeError("release of unknown page owner %r"
                                 % (owner,))
            for p in pages:
                self._free.append(p)
            return len(pages)

    def owners(self):
        with self._lock:
            return {o: list(p) for o, p in self._owned.items()}

    def pages_by_group(self, group_of):
        """Live private-page counts rolled up by ``group_of(owner)``
        (e.g. the owning tenant) — how mx.tenant audits per-tenant KV
        residency against its quota ledger.  ``group_of`` returning
        None buckets the owner under ``None`` (base traffic); shared
        prefix pages are global, not attributed."""
        out = {}
        with self._lock:
            items = [(o, len(p)) for o, p in self._owned.items()]
        for owner, n in items:
            key = group_of(owner)
            out[key] = out.get(key, 0) + n
        return out

    # -- shared segment (mx.serve.cache radix prefix cache) -----------------
    def adopt_shared(self, owner, pages, readers=1):
        """Move ``pages`` (a subset of ``owner``'s ledger) into the
        shared segment as immutable prefix storage.  Each page's
        refcount starts at ``1 + readers``: one structural reference
        for the adopting cache plus one per live reader that already
        holds the page in its table.  The owner keeps its remaining
        (private) pages; totals are unchanged — adoption is a ledger
        move, never an allocation."""
        pages = [int(p) for p in pages]
        with self._lock:
            owned = self._owned.get(owner)
            if owned is None:
                raise ServeError(
                    "adopt_shared from unknown page owner %r" % (owner,))
            for p in pages:
                if p not in owned:
                    raise ServeError(
                        "adopt_shared: page %d is not owned by %r"
                        % (p, owner))
                if p in self._shared:
                    raise ServeError(
                        "adopt_shared: page %d is already shared" % p)
            for p in pages:
                owned.remove(p)
                self._shared[p] = 1 + int(readers)

    def shared_ref(self, pages):
        """Take one reference per page (a cache hit attaching a reader
        to an existing prefix).  Unknown pages raise — referencing a
        page that is not in the shared segment is the read half of a
        use-after-free."""
        pages = [int(p) for p in pages]
        with self._lock:
            for p in pages:
                if p not in self._shared:
                    raise ServeError(
                        "shared_ref of non-shared page %d" % p)
            for p in pages:
                self._shared[p] += 1

    def shared_unref(self, pages):
        """Drop one reference per page; pages reaching refcount 0
        return to the free list.  Over-release raises (the shared
        segment's double-free guard).  Returns the number of pages
        actually freed."""
        freed = 0
        with self._lock:
            for p in [int(p) for p in pages]:
                n = self._shared.get(p)
                if not n:
                    raise ServeError(
                        "shared double-free of page %d" % p)
                n -= 1
                if n == 0:
                    del self._shared[p]
                    self._free.append(p)
                    freed += 1
                else:
                    self._shared[p] = n
        return freed

    @property
    def shared_pages(self):
        with self._lock:
            return len(self._shared)

    def shared_refs(self):
        with self._lock:
            return dict(self._shared)

    def reset(self):
        """Free everything (scheduler teardown); storage is reused."""
        with self._lock:
            self._owned.clear()
            self._shared.clear()
            self._free = list(range(self.config.num_pages - 1, -1, -1))

    def check(self):
        """Invariant audit: free + owned + shared == capacity, no
        duplicates, every shared refcount >= 1.  Raises ``ServeError``
        on violation; returns True."""
        with self._lock:
            owned = [p for pages in self._owned.values() for p in pages]
            shared = list(self._shared)
            seen = self._free + owned + shared
            if len(seen) != self.config.num_pages or \
                    len(set(seen)) != len(seen):
                raise ServeError(
                    "page accounting corrupt: %d free + %d owned + %d "
                    "shared != %d capacity (or duplicate ids)" % (
                        len(self._free), len(owned), len(shared),
                        self.config.num_pages))
            if any(n < 1 for n in self._shared.values()):
                raise ServeError("shared page with refcount < 1")
        return True

    def device_bytes(self):
        """Bytes of the K+V arrays resident on ONE device — the number
        the sharded-decode residency bound asserts (1/mdl of the pool
        when head-sharded, the full pool otherwise)."""
        from ..shard import device_bytes as _db

        return _db([self.k, self.v])

    def stats(self):
        with self._lock:
            free = len(self._free)
            owners = len(self._owned)
            shared = len(self._shared)
        cap = self.config.num_pages
        return {
            "kv_sharding": None if self.sharding is None
            else str(self.sharding.spec),
            "kv_device_bytes": self.device_bytes()
            if self.sharding is not None else None,
            "capacity_pages": cap,
            "in_use_pages": cap - free,
            "free_pages": free,
            "shared_pages": shared,
            "high_water_pages": self.high_water,
            "occupancy": round((cap - free) / cap, 4),
            "owners": owners,
            "alloc_total": self.alloc_total,
            "oom_rejects": self.oom_rejects,
            "config": self.config.as_dict(),
        }


# ---------------------------------------------------------------------------
# jax-side page address arithmetic (traced inside the decode-step program)
# ---------------------------------------------------------------------------

def gather_pages(pool, tables):
    """Materialize each sequence's paged cache as a contiguous context.

    ``pool`` is ``[L, N, page, H, D]``; ``tables`` is ``[B, P]`` int32
    physical page ids.  Returns ``[B, L, P * page, H, D]``.  Gather is
    clamp-mode (jax default under jit): table slots past a sequence's
    allocation may read arbitrary pages, but those positions are
    ``>= length`` and the attention mask discards them."""
    import jax.numpy as jnp

    g = pool[:, jnp.clip(tables, 0, pool.shape[1] - 1)]
    lyr, b, p, page, h, d = g.shape
    return jnp.transpose(g, (1, 0, 2, 3, 4, 5)).reshape(
        b, lyr, p * page, h, d)


def scatter_pages(pool, tables, positions, valid, new):
    """Write one chunk's fresh K or V rows into their pages.

    ``new`` is ``[B, T, L, H, D]`` (the model's per-position cache
    rows), ``positions`` ``[B, T]`` absolute token positions, ``valid``
    ``[B, T]`` bool.  Invalid positions (prompt padding, padded batch
    slots) are redirected to the out-of-bounds null page and dropped by
    the scatter mode — the pool is only ever written at addresses the
    owning sequence reserved."""
    import jax.numpy as jnp

    page_size = pool.shape[2]
    npages = pool.shape[1]
    logical = jnp.clip(positions // page_size, 0, tables.shape[1] - 1)
    phys = jnp.take_along_axis(tables, logical, axis=1)       # [B, T]
    phys = jnp.where(valid, phys, npages)                     # OOB -> drop
    slot = positions % page_size                              # [B, T]
    rows = jnp.transpose(new, (2, 0, 1, 3, 4))                # [L,B,T,H,D]
    return pool.at[:, phys, slot].set(rows, mode="drop")
