"""PageLedger: host-side KV page bookkeeping for the paged generation
engine (ISSUE 18; PagedAttention / vLLM, PAPERS.md).

The paged device state holds one global pool of fixed-size KV pages —
``(pages, layers, page_tokens, heads, head_dim)`` — plus a per-slot block
table of page indices. This ledger is the pool's host-side truth: which
pages are free, which slot owns each handed-out page. Same posture as
SlotArena: a page is never double-handed, and a release by anything that
doesn't hold the page raises instead of corrupting — a double-hand would
let one request's decode writes land inside another request's context.

Page 0 is the SENTINEL and is never handed out. The compiled decode step
redirects writes for finished/free lanes to page 0 (their block-table rows
are zeros), so a retired slot can never scribble into pages the ledger has
already re-handed to a new request. The sentinel's contents are garbage by
design; no live lane ever attends through it.

TWO CACHE KINDS (ISSUE 28). A model with window layers keeps, beside the
full pages, the last ``window`` positions of a slot in ONE RING a slot
(``(rings, window, heads, head_dim)`` a layer, written at ``position %
window``). The ledger built with ``rings=N`` owns that pool too: ring 0 is
the rings' sentinel (free and frozen lanes write there), rings 1..N-1 are
handed out one a slot, in the SAME ``acquire`` that reserves the slot's
pages and returned by the SAME ``release``: both or neither, so no path can
leak one kind, and a ring is never double-handed any more than a page is.

WHAT A PAGE IS (ISSUE 55). The ledger counts pages and rings and knows nothing
of what a row holds: ``page_tokens`` is ROWS a page. For most families a row is
a position of context. A family whose row sums several positions up says so in
its plan (``CachePlan.page_positions``: ``eva``'s page of ``window / chunk``
summary rows stands for a whole window, and ``pages_for`` counts windows) and
may keep its rings in the pools (``ring_pages``); nothing here changes for it.

Event-loop-side only (the engine's step loop owns all mutation), so there
is deliberately no lock to witness.
"""

from __future__ import annotations


class PageCorrupted(RuntimeError):
    """The free-list and the ownership ledger disagree — a double acquire
    or a foreign release. The paged KV pool can no longer be trusted."""


class PageLedger:
    """Fixed pool of KV pages [1, pages) with an ownership ledger.

    ``pages`` counts the sentinel: a ledger built with ``pages=N`` hands
    out at most ``N - 1`` (its ``usable``) real pages, indices 1..N-1.
    The engine reserves a request's FULL page need (prompt + decode
    budget) at fold-in, so a admitted sequence can never hit mid-decode
    page exhaustion — admission is where pressure is applied (Clockwork's
    budgeted-admission frame, PAPERS.md P3).
    """

    SENTINEL = 0

    def __init__(self, pages: int, page_tokens: int, rings: int = 0) -> None:
        if int(pages) < 2:
            raise ValueError("PageLedger needs >= 2 pages (sentinel + 1)")
        if int(page_tokens) < 1:
            raise ValueError("page_tokens must be >= 1")
        if int(rings) == 1 or int(rings) < 0:
            raise ValueError("rings must be 0 (no window layers) or >= 2 "
                             "(sentinel + 1)")
        self.pages = int(pages)
        self.page_tokens = int(page_tokens)
        # Window rings [1, rings): one a slot, beside its pages.
        self.rings = int(rings)
        self._free_rings: list[int] = list(range(self.rings - 1, 0, -1))
        self._ring: dict[int, int] = {}          # slot -> its ring
        self._ring_owner: dict[int, int] = {}    # ring -> owning slot
        # LIFO free-list, popping from the low end first (1, 2, ...).
        self._free: list[int] = list(range(self.pages - 1, 0, -1))
        self._owned: dict[int, list[int]] = {}   # slot -> its pages
        self._owner: dict[int, int] = {}         # page -> owning slot
        # Lifetime hand-out count (monotone; feeds /stats).
        self.acquires_total = 0

    @property
    def usable(self) -> int:
        """Allocatable pages (total minus the sentinel)."""
        return self.pages - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_reserved(self) -> int:
        return len(self._owner)

    def utilization(self) -> float:
        """Reserved fraction of the usable pool in [0, 1]."""
        return self.n_reserved / self.usable if self.usable else 0.0

    @property
    def usable_rings(self) -> int:
        return max(0, self.rings - 1)

    @property
    def n_free_rings(self) -> int:
        return len(self._free_rings)

    @property
    def n_reserved_rings(self) -> int:
        return len(self._ring_owner)

    def ring_of(self, slot: int) -> int:
        """The slot's window ring (the sentinel where the pool has none)."""
        return self._ring.get(slot, self.SENTINEL)

    def can_cover(self, count: int) -> bool:
        """Whether ``acquire(slot, count)`` would find its pages AND its
        ring: what admission gates on."""
        return count <= len(self._free) \
            and (not self.rings or bool(self._free_rings))

    def pages_of(self, slot: int) -> list[int]:
        return list(self._owned.get(slot, ()))

    def holds(self, slot: int) -> bool:
        """Whether ``slot`` currently owns pages. The engine's release
        funnel checks this so a slot whose page-acquire itself failed
        mid-admit can still return to the arena without tripping the
        PageCorrupted double-release tripwire."""
        return slot in self._owned

    def acquire(self, slot: int, count: int) -> list[int]:
        """Hand ``count`` free pages to ``slot``; raises PageCorrupted if
        the free-list offers a page the ledger says is already owned, or
        if the slot already holds pages (one reservation per slot
        lifetime), and IndexError when the pool can't cover the count
        (callers gate on n_free)."""
        count = int(count)
        if count < 1:
            raise ValueError("acquire needs count >= 1")
        if slot in self._owned:
            raise PageCorrupted(
                f"slot {slot} already holds pages — double reservation")
        if count > len(self._free):
            raise IndexError(
                f"page pool exhausted: need {count}, free {len(self._free)}")
        if self.rings and not self._free_rings:
            raise IndexError("ring pool exhausted: every window ring is held")
        if self.rings:
            ring = self._free_rings.pop()
            if ring in self._ring_owner or ring == self.SENTINEL:
                self._free_rings.append(ring)
                raise PageCorrupted(
                    f"ring {ring} is on the free-list AND owned — double-hand")
            self._ring_owner[ring] = slot
            self._ring[slot] = ring
        out: list[int] = []
        for _ in range(count):
            page = self._free.pop()
            if page in self._owner or page == self.SENTINEL:
                self._free.append(page)
                raise PageCorrupted(
                    f"page {page} is on the free-list AND owned — double-hand")
            self._owner[page] = slot
            out.append(page)
        self._owned[slot] = out
        self.acquires_total += count
        return out

    def release(self, slot: int) -> list[int]:
        """Return ALL of a slot's pages (and its window ring) to the free
        lists; raises PageCorrupted for a slot holding nothing (foreign or
        double release) or for a page or ring whose owner record disagrees."""
        pages = self._owned.pop(slot, None)
        if pages is None:
            raise PageCorrupted(
                f"release of slot {slot} that holds no pages")
        ring = self._ring.pop(slot, None)
        if ring is not None:
            if self._ring_owner.pop(ring, None) != slot:
                raise PageCorrupted(
                    f"ring {ring} owner ledger disagrees, released by "
                    f"slot {slot}")
            self._free_rings.append(ring)
        elif self.rings:
            raise PageCorrupted(f"slot {slot} holds pages but no ring")
        for page in pages:
            owner = self._owner.pop(page, None)
            if owner != slot:
                raise PageCorrupted(
                    f"page {page} owner ledger says {owner}, released by "
                    f"slot {slot}")
            self._free.append(page)
        return pages

    def release_all(self) -> int:
        """Error-path reset: free every reserved page (the engine
        reinitializes the device state block alongside)."""
        n = 0
        for slot in list(self._owned):
            n += len(self.release(slot))
        return n

    def snapshot(self) -> dict:
        """Compact live-occupancy row for the per-replica /stats block
        (ISSUE 20) — just the pool's current fill, not the full stats()
        geometry dump."""
        return {
            "free": self.n_free,
            "reserved": self.n_reserved,
            "usable": self.usable,
            "utilization": round(self.utilization(), 4),
            **({"rings_reserved": self.n_reserved_rings,
                "rings_usable": self.usable_rings} if self.rings else {}),
        }

    def stats(self) -> dict:
        return {
            **({"rings": self.rings, "rings_reserved": self.n_reserved_rings}
               if self.rings else {}),
            "pages": self.pages,
            "usable": self.usable,
            "free": self.n_free,
            "reserved": self.n_reserved,
            "page_tokens": self.page_tokens,
            "utilization": round(self.utilization(), 4),
            "acquires_total": self.acquires_total,
        }
