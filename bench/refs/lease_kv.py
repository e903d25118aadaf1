"""Plain reference of the HALCONE timestamp-lease key-value fabric.

Written from the protocol's rules (HALCONE, arXiv 2007.04292, Algorithms
1-5 and the Fig. 5 conventions) and the service's stated semantics, in
plain Python integers and dicts; it imports nothing of the program.

Deployment: ``n_nodes`` nodes, each with one shared tier (the GPU's L2)
and ``replicas_per_node`` private replica tiers (the CU's L1), over a TSU
of ``n_shards`` shards that holds the authoritative value, version and
16-bit clock ``memts`` of each key.

  * MM read of a key with clock m:  lease [m, m + rd_lease], m' = m + rd.
  * MM write:                       lease [m + 1, m + wr_lease], m' = m + wl,
                                    version + 1.
  * A grant that would push m' past 65535 restarts the key's clock:
    lease [0, lease_len], m' = lease_len.
  * Install at a tier with clock cts: wts = max(cts, wts_r),
    rts = max(wts + 1, rts_r).  A line is served while cts <= rts;
    an expired line is dropped (self-invalidation) and fetched again.
  * Writes are posted write-through: the writer's tier holds the value at
    once (version unknown), the node queue holds at most
    ``max_in_flight`` writes and drains the oldest to the TSU; on drain
    the shared tier, then the writer's tier, install the granted lease and
    advance their clocks to the lease's wts.  A fence drains every queue
    and raises every clock to the highest.
  * Tiers are set-associative with LRU victims (empty ways first); a TSU
    shard holds at most ``tsu_capacity`` keys and evicts the one with the
    lowest clock, the earliest allocated among equals.
  * A batched read serves the keys whose replica line is live first, in
    order, then the others, in order.

Results: a read gives ``(value, version)``, ``(value, None)`` while the
reader's own write is in flight, or ``None`` for a key the TSU does not
hold.  ``stats()`` counts the service's events under the names the
service reports them.
"""
from __future__ import annotations

import collections
import hashlib

TS_MAX = (1 << 16) - 1
BLOCK = 64                      # bytes per block on every link

STAT_NAMES = (
    "reads", "writes", "l1_hits", "l2_hits", "l1_to_l2", "l2_to_mm",
    "coh_miss_l1", "coh_miss_l2", "wb_evictions", "inval_msgs",
    "pcie_blocks", "bytes_l1_l2", "bytes_l2_mm", "bytes_inter_gpu",
    "write_throughs", "self_invalidations", "compulsory", "refetches",
    "capacity_evictions", "tsu_evictions", "overflow_reinits", "fences",
    "fast_read_batches", "write_batches")


def key_hash(key) -> int:
    """The key's placement hash: blake2b-64 of its text, big-endian."""
    return int.from_bytes(
        hashlib.blake2b(str(key).encode(), digest_size=8).digest(), "big")


class _Tier:
    """One set-associative tier: ``sets[s]`` maps key -> line, where a
    line is ``[value, version, wts, rts, lru]``."""

    def __init__(self, n_sets: int, n_ways: int):
        self.sets = [dict() for _ in range(n_sets)]
        self.ways = n_ways
        self.tick = 0
        self.cts = 0

    def install(self, row: dict, key, value, version, wts, rts) -> bool:
        """Place the line; True when a live line of another key left."""
        self.tick += 1
        if key in row or len(row) < self.ways:
            row[key] = [value, version, wts, rts, self.tick]
            return False
        victim = min(row, key=lambda k: row[k][4])
        del row[victim]
        row[key] = [value, version, wts, rts, self.tick]
        return True


class LeaseKV:
    """The reference fabric; keys are any objects with a stable text."""

    def __init__(self, *, n_shards, rd_lease, wr_lease, tsu_capacity,
                 shared_sets, shared_ways, replica_sets, replica_ways,
                 max_in_flight, n_nodes=1, replicas_per_node=1):
        self.rd, self.wl = rd_lease, wr_lease
        self.cap = tsu_capacity
        self.maxif = max_in_flight
        self.n_shards = n_shards
        self.rpn = replicas_per_node
        self.shards = [dict() for _ in range(n_shards)]
        self.shared = [_Tier(shared_sets, shared_ways)
                       for _ in range(n_nodes)]
        self.replicas = [_Tier(replica_sets, replica_ways)
                         for _ in range(n_nodes * replicas_per_node)]
        self.queues = [collections.deque() for _ in range(n_nodes)]
        self.st = dict.fromkeys(STAT_NAMES, 0)
        self._place = {}

    def place(self, key):
        """(replica set, shared set, shard) of a key."""
        p = self._place.get(key)
        if p is None:
            h = key_hash(key)
            p = self._place[key] = (h % len(self.replicas[0].sets),
                                    h % len(self.shared[0].sets),
                                    h % self.n_shards)
        return p

    def stats(self) -> dict:
        return dict(self.st)

    # ------------------------------------------------------------ TSU
    def _grant(self, lease_len, wts, rts):
        if rts > TS_MAX:
            self.st["overflow_reinits"] += 1
            return 0, lease_len, lease_len
        return wts, rts, rts

    def _mm(self, key, node, write, value=None):
        """One TSU access: (value, version, wts, rts) or None."""
        st = self.st
        shard = self.place(key)[2]
        st["l2_to_mm"] += 1
        st["bytes_l2_mm"] += BLOCK
        if write:
            st["write_throughs"] += 1
        if shard != node % self.n_shards:
            st["pcie_blocks"] += 1
            st["bytes_inter_gpu"] += BLOCK
        entries = self.shards[shard]
        e = entries.get(key)
        if not write:
            if e is None:
                return None
            wts, rts, e[2] = self._grant(self.rd, e[2], e[2] + self.rd)
            return e[0], e[1], wts, rts
        if e is None:
            if len(entries) >= self.cap:
                victim = min(entries, key=lambda k: entries[k][2])
                del entries[victim]
                st["tsu_evictions"] += 1
            e = entries[key] = [None, 0, 0]
        wts, rts, e[2] = self._grant(self.wl, e[2] + 1, e[2] + self.wl)
        e[0] = value
        e[1] += 1
        return value, e[1], wts, rts

    # ----------------------------------------------------------- reads
    def read(self, key, rep):
        st = self.st
        t = self.replicas[rep]
        node = rep // self.rpn
        s1, s2, _ = self.place(key)
        row = t.sets[s1]
        st["reads"] += 1
        line = row.get(key)
        if line is not None:
            t.tick += 1
            line[4] = t.tick
            if t.cts <= line[3]:
                st["l1_hits"] += 1
                return line[0], line[1]
            st["coh_miss_l1"] += 1
            st["self_invalidations"] += 1
            del row[key]
        else:
            st["compulsory"] += 1
        st["l1_to_l2"] += 1
        st["bytes_l1_l2"] += BLOCK
        sh = self.shared[node]
        row2 = sh.sets[s2]
        got = None
        line = row2.get(key)
        if line is not None:
            sh.tick += 1
            line[4] = sh.tick
            if sh.cts <= line[3]:
                st["l2_hits"] += 1
                got = line[0], line[1], line[2], line[3]
            else:
                st["coh_miss_l2"] += 1
                st["self_invalidations"] += 1
                del row2[key]
        if got is None:
            g = self._mm(key, node, write=False)
            if g is None:
                return None
            wts = max(sh.cts, g[2])
            rts = max(wts + 1, g[3])
            if sh.install(row2, key, g[0], g[1], wts, rts):
                st["capacity_evictions"] += 1
            got = g[0], g[1], wts, rts
        wts = max(t.cts, got[2])
        rts = max(wts + 1, got[3])
        st["refetches"] += 1
        if t.install(row, key, got[0], got[1], wts, rts):
            st["capacity_evictions"] += 1
        return got[0], got[1]

    def read_batch(self, keys, rep):
        t = self.replicas[rep]
        lines = [t.sets[self.place(k)[0]].get(k) for k in keys]
        live = [ln is not None and t.cts <= ln[3] for ln in lines]
        if keys and all(live):
            # every key a live hit: the same results, ticks and counts as
            # reading them one by one, at a fraction of the cost
            st, n = self.st, len(keys)
            st["fast_read_batches"] += 1
            st["reads"] += n
            st["l1_hits"] += n
            for ln, tick in zip(lines, range(t.tick + 1, t.tick + 1 + n)):
                ln[4] = tick
            t.tick += n
            return [(ln[0], ln[1]) for ln in lines]
        out = [None] * len(keys)
        for i, k in enumerate(keys):
            if live[i]:
                out[i] = self.read(k, rep)
        for i, k in enumerate(keys):
            if not live[i]:
                out[i] = self.read(k, rep)
        return out

    # ---------------------------------------------------------- writes
    def _drain_one(self, node):
        key, value, rep = self.queues[node].popleft()
        _, version, wts, rts = self._mm(key, node, write=True, value=value)
        sh = self.shared[node]
        s1, s2, _ = self.place(key)
        wts = max(sh.cts, wts)
        rts = max(wts + 1, rts)
        sh.cts = max(sh.cts, wts)
        if sh.install(sh.sets[s2], key, value, version, wts, rts):
            self.st["capacity_evictions"] += 1
        t = self.replicas[rep]
        wts = max(t.cts, wts)
        rts = max(wts + 1, rts)
        t.cts = max(t.cts, wts)
        if t.install(t.sets[s1], key, value, version, wts, rts):
            self.st["capacity_evictions"] += 1

    def write(self, key, value, rep):
        st = self.st
        st["writes"] += 1
        st["l1_to_l2"] += 1
        st["bytes_l1_l2"] += BLOCK
        t = self.replicas[rep]
        if t.install(t.sets[self.place(key)[0]], key, value, None, t.cts,
                     t.cts):
            st["capacity_evictions"] += 1
        node = rep // self.rpn
        self.queues[node].append((key, value, rep))
        while len(self.queues[node]) > self.maxif:
            self._drain_one(node)

    def write_batch(self, items, rep):
        items = list(items)
        if not items:
            return
        self.st["write_batches"] += 1
        for k, v in items:
            self.write(k, v, rep)

    def fence(self) -> int:
        for node in range(len(self.queues)):
            while self.queues[node]:
                self._drain_one(node)
        self.st["fences"] += 1
        tiers = self.shared + self.replicas
        gmax = max(t.cts for t in tiers)
        for t in tiers:
            t.cts = gmax
        return gmax


class NoWriteThroughKV(LeaseKV):
    """The control: the reference with one stated guarantee broken.  A
    posted write leaves the node queue without reaching the TSU or any
    tier, so the writer's store-buffer copy is all that ever holds it;
    the counts of a drain still advance, as if it had gone through."""

    def _drain_one(self, node):
        self.queues[node].popleft()
        self.st["l2_to_mm"] += 1
        self.st["bytes_l2_mm"] += BLOCK
        self.st["write_throughs"] += 1
