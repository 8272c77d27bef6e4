"""Deficit-round-robin tenant queue for the shared verify plane.

The single-tenant `BatchVerifierService` drained one FIFO list, which is
exactly wrong under multi-session load: one hot session (a flooded or very
large committee) enqueues faster than the collector drains and every other
session's candidates age behind its backlog. `TenantQueue` keeps one FIFO
per session and serves them deficit-round-robin [Shreedhar & Varghese '96,
degenerate unit-cost form — every verify candidate costs one launch lane]:
each tenant at the head of the active ring is charged `quantum` lane
credits per visit, spends them on its own candidates, and rotates to the
tail, so a full ring pass hands every backlogged session `quantum` lanes no
matter how deep any one backlog is. An emptied tenant forfeits its residual
deficit (no credit hoarding across idle periods — the standard DRR rule).

Per-tenant admission bound: `push` refuses beyond `max_pending` queued
items for one tenant, so a hot session's backlog is ITS problem — the
refusal surfaces to that session's caller (the processing pipeline's
retry/requeue budget) instead of growing host memory or the ring latency
every other tenant pays.

SLO-driven admission (lifecycle control plane, ISSUE 12c): each tenant
carries an `SloTier` — a priority weight that scales its DRR quantum (a
gold tenant earns `weight ×` lane credits per ring visit) plus a
load-shedding threshold expressed as a fraction of the queue's GLOBAL
`capacity`. When total depth crosses a tier's `shed_at` fraction, NEW work
for that tier is refused at the door — bronze sheds first, gold last — so
an overloaded plane spends its lanes meeting the strictest p99 targets
instead of degrading everyone equally. The flat per-tenant `max_pending`
bound stays as the fallback flood-defense knob; `capacity = 0` disables
shedding entirely (the pre-SLO behavior, byte for byte).

Keys (ISSUE 38): everything is pushed without a key, and `rekey` moves it
to the keys the service computes in bulk; items of one key form a DRR ring
of their own, so the service can take a launch's worth of ONE launch class
(`take(lanes, key)`) with the fairness above holding inside the class;
`take_oldest` hands out the oldest items of some keys whoever owns them
(lanes that would stay empty); `turn_key` and `counts` are what the plan is
made from. The queue never asks what a key means. Admission bounds and
`drop_tenant` span every key; a keyless queue is one ring, as before.

Fairness ACROSS keys is the turn ring's: were the key of each take the
oldest item's, the queue would be one FIFO over keys, and a session whose
backlog sits alone under a key would own every take until the backlog is
gone — the failure of the first paragraph. So the tenants with queued work
form one more deficit ring over all keys. Every item that leaves, under
whatever key and by whatever call, costs its tenant one lane credit; the
tenant at the head is granted `quantum` (x its tier weight) a visit and
names the key of the next take (`turn_key`: its oldest item's) for as long
as it has credit; one that is out of credit passes the turn. A backlogged
tenant so names a take once a ring pass, not once per `lanes` of its
backlog, while tenants that never hold more than a quantum — for whom the
ring's order is their arrival's — name the key of the oldest item queued.

Single-threaded like the service it fronts (core/store.py module
docstring): every caller runs on one asyncio loop, so no lock.
"""

from __future__ import annotations

import heapq
from collections import deque
from dataclasses import dataclass
from typing import Iterator

DEFAULT_QUANTUM = 8
DEFAULT_MAX_PENDING = 4096
#: `take`'s key for "whatever key an item is under"
ANY = object()


@dataclass(frozen=True)
class SloTier:
    """One admission/priority class. `weight` multiplies the tenant's DRR
    quantum; `p99_target_s` is the session-completion SLO the manager
    reports against (service/session.py tier_quantiles); `shed_at` is the
    fraction of queue capacity past which this tier's new work sheds."""

    name: str
    weight: int = 1
    p99_target_s: float = 30.0
    shed_at: float = 1.0


#: the built-in tier ladder; tenants without an explicit tier ride
#: "standard" (weight 1, shed only at full capacity — legacy behavior)
TIERS = {
    "gold": SloTier("gold", weight=4, p99_target_s=5.0, shed_at=0.98),
    "silver": SloTier("silver", weight=2, p99_target_s=15.0, shed_at=0.85),
    "bronze": SloTier("bronze", weight=1, p99_target_s=60.0, shed_at=0.60),
    "standard": SloTier("standard", weight=1, p99_target_s=30.0, shed_at=1.0),
}
DEFAULT_TIER = TIERS["standard"]


class _Ring:
    """One key's deficit-round-robin ring: per-tenant FIFOs of (push
    number, item), the tenants with queued work in serving order, their
    residual lane credits, and the items it holds."""

    __slots__ = ("q", "ring", "deficit", "n")

    def __init__(self):
        self.q: dict[str, deque] = {}
        self.ring: deque[str] = deque()  # tenants with queued work
        self.deficit: dict[str, int] = {}
        self.n = 0

    def retire(self, tenant: str) -> None:
        """`tenant`'s FIFO emptied: off the ring, its residual deficit
        forfeited."""
        del self.q[tenant]
        self.ring.remove(tenant)
        del self.deficit[tenant]


class TenantQueue:
    """Per-tenant FIFOs drained fairly, `quantum` lanes per ring visit.

    Items are pushed without a key and moved to keys by `rekey` (the
    service's launch classes; the queue never asks what a key means): items
    of one key form a DRR ring of their own, so `take(lanes, key)` hands out
    that key's items alone, each tenant's in FIFO order and the tenants'
    shares deficit-round-robin, exactly as the one ring of a keyless queue
    does. Which key is next is the turn ring's to say (`turn_key`, module
    docstring). Admission (`max_pending`, `capacity`) and `drop_tenant` span
    a tenant's items under every key, and every item carries the number of
    its push, so the queue knows which of two is older.
    """

    def __init__(
        self,
        quantum: int = DEFAULT_QUANTUM,
        max_pending: int = DEFAULT_MAX_PENDING,
        capacity: int = 0,
    ):
        if quantum < 1:
            raise ValueError("quantum must be >= 1")
        if max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        if capacity < 0:
            raise ValueError("capacity must be >= 0")
        self.quantum = quantum
        self.max_pending = max_pending
        # global depth bound for SLO shedding; 0 = shedding off
        self.capacity = capacity
        self._rings: dict[object, _Ring] = {}  # key -> ring, live keys only
        self._depth: dict[str, int] = {}  # queued per tenant, every key
        # the turn ring (module docstring): tenants with queued work in the
        # order they name a take's key, their lane credits over every key,
        # and whether the head has been granted this visit's
        self._turn: deque[str] = deque()
        self._credit: dict[str, int] = {}
        self._granted = False
        self._tier: dict[str, SloTier] = {}
        self._total = 0  # queued items across tenants (O(1) shed check)
        self._pushes = 0  # number of the next push: the items' age order
        # reporter counters
        self.pushed = 0
        self.refused = 0
        self.shed = 0
        self.taken = 0

    def set_tier(self, tenant: str, tier: SloTier | str) -> SloTier:
        """Pin one tenant's admission/priority class ("gold"/"silver"/
        "bronze"/"standard", or a custom SloTier)."""
        if isinstance(tier, str):
            tier = TIERS[tier]
        self._tier[tenant] = tier
        return tier

    def tier_of(self, tenant: str) -> SloTier:
        return self._tier.get(tenant, DEFAULT_TIER)

    def drop_tier(self, tenant: str) -> None:
        self._tier.pop(tenant, None)

    def push(self, tenant: str, item) -> bool:
        """Enqueue one item for `tenant`, under no key (`rekey` gives it
        one); False = refused (the item was NOT queued — the caller owns
        the refusal). Two doors: the tier's load-shed threshold against
        GLOBAL depth, then the flat per-tenant bound."""
        if self.capacity > 0:
            tier = self.tier_of(tenant)
            if self._total >= self.capacity * tier.shed_at:
                self.shed += 1
                return False
        depth = self._depth.get(tenant, 0)
        if depth >= self.max_pending:
            self.refused += 1
            return False
        self._append(None, tenant, (self._pushes, item))
        self._pushes += 1
        if not depth:  # new to the queue: the turn ring's tail, no credit
            self._turn.append(tenant)
            self._credit[tenant] = 0
        self._depth[tenant] = depth + 1
        self._total += 1
        self.pushed += 1
        return True

    def _append(self, key, tenant: str, entry: tuple) -> None:
        """`entry`, a (push number, item), to the tail of `tenant`'s FIFO
        in `key`'s ring; a tenant new to the ring joins at its tail."""
        ring = self._rings.get(key)
        if ring is None:
            ring = self._rings[key] = _Ring()
        q = ring.q.get(tenant)
        if q is None:
            q = ring.q[tenant] = deque()
            ring.ring.append(tenant)
            ring.deficit[tenant] = 0
        q.append(entry)
        ring.n += 1

    def rekey(self, key, keys_of) -> int:
        """Move every item queued under `key` to the key `keys_of` gives
        it: ONE call, `keys_of(items) -> keys`, so the caller can compute
        them in bulk. Items keep their push numbers, a tenant's items their
        order; nothing moves if `keys_of` raises. Sound as long as what is
        queued under the target keys was pushed earlier (it was rekeyed
        itself), so that every FIFO stays in push order: the service pushes
        under None and rekeys to launch classes before each plan. Returns
        the number of items moved."""
        ring = self._rings.get(key)
        if ring is None:
            return 0
        moved = [(t, entry) for t in ring.ring for entry in ring.q[t]]
        keys = keys_of([entry[1] for _, entry in moved])
        del self._rings[key]
        for (t, entry), new in zip(moved, keys):
            self._append(new, t, entry)
        return len(moved)

    def shed_rate(self) -> float:
        """Shed pushes over everything offered (the soak SLO metric)."""
        offered = self.pushed + self.refused + self.shed
        return self.shed / offered if offered else 0.0

    def _left(self, key, ring: _Ring, tenant: str, k: int) -> None:
        """`k` of `tenant`'s items left `ring`: the depth books, and the
        turn ring's charge of a lane credit an item. A debt stops at one
        visit's grant, so an overdrawn tenant sits out ONE ring pass."""
        ring.n -= k
        self._total -= k
        self.taken += k
        d = self._depth[tenant] - k
        if d:
            self._depth[tenant] = d
            self._credit[tenant] = max(
                self._credit[tenant] - k, -self._grant(tenant)
            )
        else:
            self._leave_turn(tenant)
        if not ring.n:
            del self._rings[key]

    def _grant(self, tenant: str) -> int:
        """Lane credits a ring visit: the tier weight scales them (a gold
        tenant earns weight x lanes per ring pass: its priority share)."""
        return self.quantum * self.tier_of(tenant).weight

    def _leave_turn(self, tenant: str) -> None:
        """Nothing of `tenant` is queued any more: off the depth books and
        the turn ring, its credit or debt forfeited."""
        del self._depth[tenant]
        if self._turn[0] == tenant:
            self._granted = False  # the next head's visit starts
        self._turn.remove(tenant)
        del self._credit[tenant]

    def turn_key(self):
        """The key of the next take: that of the oldest item of the tenant
        whose turn it is (None for an empty queue, as for a keyless one).
        The head of the turn ring keeps the turn while it has lane credit
        (`_left` charges it for every item of its own that leaves, in the
        takes it named and in those it did not); out of credit, the turn
        passes. Changes nothing but whose turn it is."""
        if not self._turn:
            return None
        while True:
            t = self._turn[0]
            if not self._granted:
                self._credit[t] += self._grant(t)
                self._granted = True
            if self._credit[t] > 0:
                break
            self._turn.rotate(-1)
            self._granted = False
        return min(  # push numbers differ: keys never compare
            (ring.q[t][0][0], key)
            for key, ring in self._rings.items() if t in ring.q
        )[1]

    def take(self, lanes: int, key=ANY) -> list:
        """Dequeue up to `lanes` items across tenants, deficit-round-robin:
        those under `key`, or, with none given, every key's, each time the
        key the turn ring names (`turn_key`).

        The head tenant keeps its position (and residual deficit) when the
        lane budget runs out mid-quantum, so fairness holds ACROSS calls:
        a launch boundary never resets whose turn it is.
        """
        if key is ANY:
            out: list = []
            while len(out) < lanes and self._rings:
                out += self.take(lanes - len(out), self.turn_key())
            return out
        out = []
        ring = self._rings.get(key)
        while lanes > 0 and ring is not None and ring.ring:
            t = ring.ring[0]
            q = ring.q[t]
            d = ring.deficit[t]
            if d <= 0:
                ring.deficit[t] = d = self._grant(t)
            k = min(d, len(q), lanes)
            for _ in range(k):
                out.append(q.popleft()[1])
            ring.deficit[t] = d - k
            lanes -= k
            self._left(key, ring, t, k)
            if not q:
                ring.retire(t)  # emptied: residual deficit forfeited
            elif d == k:
                ring.ring.rotate(-1)  # quantum spent: next tenant's turn
            else:
                break  # lane budget exhausted mid-quantum: resume here
        return out

    def take_oldest(self, lanes: int, keys) -> list:
        """Dequeue the up to `lanes` OLDEST items under any of `keys`,
        oldest first, whoever their tenants are: lanes a launch would leave
        empty, so no ring's deficit is charged (the turn ring's credit is:
        a lane is a lane)."""
        heads = [
            (q[0][0], key, t)
            for key in keys if key in self._rings
            for t, q in self._rings[key].q.items()
        ]
        heapq.heapify(heads)
        out: list = []
        while heads and len(out) < lanes:
            _, key, t = heads[0]
            ring = self._rings[key]
            q = ring.q[t]
            out.append(q.popleft()[1])
            if q:
                heapq.heapreplace(heads, (q[0][0], key, t))
            else:
                heapq.heappop(heads)
                ring.retire(t)
            self._left(key, ring, t, 1)
        return out

    def counts(self) -> dict:
        """Queued items by key (live keys only)."""
        return {key: ring.n for key, ring in self._rings.items()}

    def drop_tenant(self, tenant: str) -> list:
        """Remove one tenant's whole queue, every key's (session evict);
        returns the dropped items, oldest first, so the caller can fail
        their waiters."""
        self.drop_tier(tenant)
        dropped: list = []
        for key, ring in list(self._rings.items()):
            q = ring.q.get(tenant)
            if q is None:
                continue
            dropped += q
            ring.retire(tenant)
            ring.n -= len(q)
            if not ring.n:
                del self._rings[key]
        self._total -= len(dropped)
        if dropped:
            self._leave_turn(tenant)
        return [item for _, item in sorted(dropped, key=lambda e: e[0])]

    def drain(self) -> Iterator:
        """Remove and yield every queued item (service stop())."""
        for t in list(self._depth):
            yield from self.drop_tenant(t)

    def depth(self, tenant: str) -> int:
        return self._depth.get(tenant, 0)

    def depths(self) -> dict[str, int]:
        """Per-tenant queue depths (the `session`-labeled gauge surface)."""
        return dict(self._depth)

    def tenants(self) -> int:
        return len(self._depth)

    def __len__(self) -> int:
        return self._total
