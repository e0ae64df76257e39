"""Plain reference of the Register Dispersion timing model.

One instruction at a time, in plain Python, from the instruction fields of
an RVV-lite trace (opcode, vd, vs1, vs2, byte address, cost override).  It
shares no code with the engine under test: no event expansion, no
folding, no policy kernels, no scan.  Its semantics are the paper's
microarchitecture (Table 1) as the engine documents it:

* a compact VRF of ``capacity`` fully associative slots; ``v0`` is pinned
  outside it; the operands are tag-checked serially (vs1, vs2, vd), and a
  later operand's miss may not evict an earlier operand of the same
  instruction; a miss spills a dirty victim to the victim's reserved line
  and fills the missing register from its own (the paper always fetches);
  FIFO evicts the longest-resident entry, LRU the least recently used;
  ``capacity >= 32`` is the full VRF, where every access hits;
* a write-allocate, write-back L1D of ``l1_sets`` x ``l1_ways`` 32-byte
  lines with LRU within a set; a miss costs the main-memory latency, plus
  as much again when it evicts a dirty line; spill/fill micro-ops pay
  ``uop_hit_cycles`` on a hit, the instruction's own data accesses
  ``l1_hit_cycles``;
* every instruction costs its base cycles plus the cycles of its L1
  accesses.

Timestamps advance six per instruction (vs1, vs2, vd, two memory lanes and
the scalar slot), so every age comparison orders accesses as issued.

``simulate`` returns the twelve counters the engine reports, by name.
"""

from __future__ import annotations

import numpy as np

COUNTERS = ("cycles", "stall_cycles", "vrf_hits", "vrf_misses", "spills",
            "fills", "l1_hits", "l1_misses", "reg_reads", "reg_writes",
            "mem_reads", "mem_writes")

FIFO, LRU = 0, 1
FULL_VRF = 32
LINE_BYTES = 32
MASK_REG = 0

# Per opcode: (reads vs1, reads vs2, reads vd, writes vd, load, store,
# base cycles, bytes touched).  Opcode numbers of the RVV-lite trace format;
# base cycles from the paper's 8-lane low-cost VPU (division, square root
# and reductions are multi-cycle).
_OPS = {
    0: (0, 0, 0, 0, 0, 0, 1, 0),     # scalar bookkeeping
    1: (0, 0, 0, 1, 1, 0, 1, 32),    # vle
    2: (1, 0, 0, 0, 0, 1, 1, 32),    # vse
    3: (1, 1, 0, 1, 0, 0, 1, 0),     # vadd
    4: (1, 1, 0, 1, 0, 0, 1, 0),     # vsub
    5: (1, 1, 0, 1, 0, 0, 1, 0),     # vmul
    6: (1, 1, 0, 1, 0, 0, 8, 0),     # vdiv
    7: (1, 0, 0, 1, 0, 0, 8, 0),     # vsqrt
    8: (1, 1, 1, 1, 0, 0, 1, 0),     # vmacc
    9: (1, 1, 0, 1, 0, 0, 1, 0),     # vmax
    10: (1, 1, 0, 1, 0, 0, 1, 0),    # vmin
    11: (1, 1, 0, 1, 0, 0, 4, 0),    # vredsum
    12: (1, 1, 0, 1, 0, 0, 4, 0),    # vredmax
    13: (0, 0, 0, 1, 1, 0, 2, 4),    # vbcast (scalar load + broadcast)
    14: (1, 0, 0, 1, 0, 0, 1, 0),    # vmv.v.v
    15: (1, 1, 0, 0, 0, 0, 1, 0),    # vmslt (writes the pinned v0)
    16: (1, 1, 0, 1, 0, 0, 1, 0),    # vmerge
    17: (1, 0, 0, 1, 0, 0, 1, 0),    # vslide1dn
    18: (1, 0, 0, 1, 0, 0, 1, 0),    # vslide1up
    19: (1, 1, 0, 1, 0, 0, 1, 0),    # vxor
    20: (1, 0, 0, 1, 0, 0, 1, 0),    # vmul.vx
    21: (1, 0, 0, 1, 0, 0, 1, 0),    # vadd.vx
    22: (1, 0, 0, 0, 0, 1, 2, 4),    # vses (store of element 0)
}


def _table(col: int) -> np.ndarray:
    t = np.zeros(max(_OPS) + 1, np.int64)
    for op, row in _OPS.items():
        t[op] = row[col]
    return t


def decode(op, vd, vs1, vs2, addr, cost_override):
    """Per-instruction operand lists of a trace: which registers are
    tag-checked in which order, which lines the data access touches, and
    the base cycles.  Returns plain Python lists for the loop below, and
    the counts that do not depend on the machine."""
    op = np.asarray(op, np.int64)
    vd, vs1, vs2 = (np.asarray(a, np.int64) for a in (vd, vs1, vs2))
    addr = np.asarray(addr, np.int64)
    r1, r2, rd, wd = (_table(c)[op].astype(bool) for c in range(4))
    load, store = _table(4)[op].astype(bool), _table(5)[op].astype(bool)
    nbytes = _table(7)[op]
    cost = np.where(np.asarray(cost_override) >= 0, cost_override,
                    _table(6)[op])
    a = np.where(r1 & (vs1 >= 0) & (vs1 != MASK_REG), vs1, -1)
    b = np.where(r2 & (vs2 >= 0) & (vs2 != MASK_REG), vs2, -1)
    d = np.where((rd | wd) & (vd >= 0) & (vd != MASK_REG), vd, -1)
    mem = load | store
    line0 = np.where(mem, addr // LINE_BYTES, -1)
    line1 = np.where(mem, (addr + nbytes - 1) // LINE_BYTES, -1)
    line1 = np.where(line1 != line0, line1, -1)
    lines = (line0 >= 0).astype(np.int64) + (line1 >= 0)
    counts = dict(
        accesses=int((a >= 0).sum() + (b >= 0).sum() + (d >= 0).sum()),
        reg_reads=int((a >= 0).sum() + (b >= 0).sum() + (rd & (d >= 0)).sum()),
        reg_writes=int((wd & (d >= 0)).sum()),
        mem_reads=int(lines[~store].sum()), mem_writes=int(lines[store].sum()),
        base_cycles=int(cost.sum()))
    return dict(a=a.tolist(), b=b.tolist(), d=d.tolist(),
                d_writes=(wd & (d >= 0)).tolist(), store=store.tolist(),
                line0=line0.tolist(), line1=line1.tolist(),
                mem_rows=np.flatnonzero(mem).tolist(), counts=counts)


def spill_base_line(memory_nbytes: int) -> int:
    """First line of the reserved spill region: past the data image, with
    four lines of guard (register r lives at base + r)."""
    return -(-int(memory_nbytes) // LINE_BYTES) + 4


def simulate(fields: dict, memory_nbytes: int, *, capacity: int,
             policy: int, l1_sets: int, l1_ways: int, l1_hit_cycles: int,
             uop_hit_cycles: int, mem_latency: int) -> dict:
    """The twelve counters of one trace at one machine point."""
    if policy not in (FIFO, LRU):
        raise ValueError(f"the reference models FIFO and LRU, not {policy}")
    spill0 = spill_base_line(memory_nbytes)
    # Compact VRF: slot -> tag, dirty, FIFO sequence, last use.
    tag = [-1] * capacity
    dirty = [0] * capacity
    seqno = [0] * capacity
    last = [0] * capacity
    where = {}                       # tag -> slot
    # L1: per set a list of [line, age, dirty] per way.
    l1 = [[[-1, 0, 0] for _ in range(l1_ways)] for _ in range(l1_sets)]
    n = dict(seq=0, misses=0, spills=0, fills=0, l1_misses=0)

    def l1_access(line, write, now, hit_cost):
        """Cycles of one line access; LRU within the set, write-back."""
        ways = l1[line % l1_sets]
        for w in ways:
            if w[0] == line:
                w[1] = now
                w[2] |= write
                return hit_cost
        n["l1_misses"] += 1
        # LRU victim: oldest age; at equal age a clean way before a dirty
        # one; then the lowest way.
        v = min(ways, key=lambda w: (w[1] * 2 + w[2]))
        cost = hit_cost + mem_latency
        if v[0] >= 0 and v[2]:
            cost += mem_latency
        v[0], v[1], v[2] = line, now, write
        return cost

    def miss(reg, now, write, lock1, lock2):
        """Install ``reg``: a free slot, else evict (spilling a dirty
        victim) the FIFO- or LRU-first slot not holding a locked operand.
        Returns the stall cycles of the spill and fill micro-ops."""
        n["misses"] += 1
        stall = 0
        slot = tag.index(-1) if -1 in tag else -1
        if slot < 0:
            order = seqno if policy == FIFO else last
            best = None
            for k in range(capacity):
                t = tag[k]
                if t != lock1 and t != lock2 and (best is None
                                                  or order[k] < best):
                    best, slot = order[k], k
            if dirty[slot]:
                stall += l1_access(spill0 + tag[slot], 1, now,
                                   uop_hit_cycles)
                n["spills"] += 1
            del where[tag[slot]]
        stall += l1_access(spill0 + reg, 0, now, uop_hit_cycles)
        n["fills"] += 1
        tag[slot], dirty[slot], seqno[slot], last[slot] = (
            reg, write, n["seq"], now)
        where[reg] = slot
        n["seq"] += 1
        return stall

    A, B, D, DW = fields["a"], fields["b"], fields["d"], fields["d_writes"]
    ST, L0, L1_ = fields["store"], fields["line0"], fields["line1"]
    stall = memc = 0
    if capacity >= FULL_VRF:                 # every register access hits
        rows = fields["mem_rows"]
    else:
        rows = range(len(A))
        for i in rows:
            now = 6 * i
            a, b, d = A[i], B[i], D[i]
            if a >= 0:
                slot = where.get(a)
                if slot is None:
                    stall += miss(a, now, 0, -1, -1)
                else:
                    last[slot] = now
            if b >= 0:
                slot = where.get(b)
                if slot is None:
                    stall += miss(b, now + 1, 0, a, -1)
                else:
                    last[slot] = now + 1
            if d >= 0:
                w = DW[i]
                slot = where.get(d)
                if slot is None:
                    stall += miss(d, now + 2, w, a, b)
                else:
                    last[slot] = now + 2
                    dirty[slot] |= w
            if L0[i] >= 0:
                memc += l1_access(L0[i], ST[i], now + 3, l1_hit_cycles)
                if L1_[i] >= 0:
                    memc += l1_access(L1_[i], ST[i], now + 4, l1_hit_cycles)
        rows = ()
    for i in rows:
        memc += l1_access(L0[i], ST[i], 6 * i + 3, l1_hit_cycles)
        if L1_[i] >= 0:
            memc += l1_access(L1_[i], ST[i], 6 * i + 4, l1_hit_cycles)

    k = fields["counts"]
    l1_accesses = n["spills"] + n["fills"] + k["mem_reads"] + k["mem_writes"]
    return dict(
        cycles=k["base_cycles"] + stall + memc, stall_cycles=stall,
        vrf_hits=k["accesses"] - n["misses"], vrf_misses=n["misses"],
        spills=n["spills"], fills=n["fills"],
        l1_hits=l1_accesses - n["l1_misses"], l1_misses=n["l1_misses"],
        reg_reads=k["reg_reads"], reg_writes=k["reg_writes"],
        mem_reads=k["mem_reads"], mem_writes=k["mem_writes"])

TRACE_FIELDS = ("op", "vd", "vs1", "vs2", "addr", "cost_override")


def simulate_trace(arrays: dict, memory_nbytes: int, machine: dict) -> dict:
    """``simulate`` on a trace given as arrays named by ``TRACE_FIELDS``
    (picklable, for worker processes)."""
    return simulate(decode(*(arrays[f] for f in TRACE_FIELDS)),
                    memory_nbytes, **machine)
