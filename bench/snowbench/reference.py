"""Plain reference of what one sweep query answers.

A sweep query asks: over a fleet of ``n`` members that broadcast with
Snow (or its two-tree Coloring) at fan-out ``k``, what are the mean last
delivery time (LDT), its 95% interval over the delay seeds, the
reliability and the bytes per member, under the §5 delay model?  This
module answers it from the definitions, in float64, and imports nothing
of the program under test:

* the trees follow Algorithm 1 of the paper (root centre split, each
  side cut into k/2 balanced regions whose midpoint forwards, direct
  delivery once a region holds at most k members) and §4.6 Coloring
  (internal nodes of the primary tree share the initiator's ring-distance
  parity, the secondary tree is rooted at the initiator's predecessor);
* the delays are the §5.2 model drawn from the counter-based threefry
  stream that the configuration names: one key per ``(seed, tree slot,
  variate)``, counters over the ``(message, member)`` grid.  Only the
  random bits and their conversion to a float32 uniform come from
  ``jax.random``; everything after is float64 here;
* a member's first-delivery time is ``t[parent] + fwd[parent] +
  link[member]`` (the initiator forwards at once), the minimum over the
  trees, and unreached (a crashed ancestor) members never count;
* the §5.5 breakdown trace crashes one random fixed member every
  ``crash_every`` messages and evicts it ``detect_after_s`` later.

``dtype`` switches the delay arithmetic to a lower precision; the
benchmark's control runs it in bfloat16 to show that its limits catch a
precision drop.
"""
from __future__ import annotations

import math
import os
import random
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from scipy.special import erfinv

#: §5.2 delay model, as the configuration files state it
@dataclass(frozen=True)
class Delays:
    fwd_lo_s: float
    fwd_hi_s: float
    link_median_s: float
    link_sigma: float
    straggler_frac: float
    straggler_delay_s: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Delays":
        return cls(**cfg["delays"])


#: draw tags of the threefry key chain (configuration ``rng`` block)
TAG_FWD, TAG_LINK, TAG_STRAGGLER = 0, 1, 2


# ------------------------------------------------------------------ #
# Trees                                                               #
# ------------------------------------------------------------------ #
def _balanced_cuts(count: np.ndarray, parts: np.ndarray, j: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """First and last offset of part ``j`` when ``count`` items are cut
    into ``parts`` contiguous runs whose sizes differ by at most one:
    cut ``i`` sits at ``round(i * count / parts)``, ties to even."""
    safe = np.maximum(parts, 1)
    lo = np.rint(j * count / safe).astype(np.int64)
    hi = np.rint((j + 1) * count / safe).astype(np.int64) - 1
    return lo, hi


def _oncolor(n: int, start, length, i0: int, want: int):
    """Count of the members of side ``(start, length)`` whose ring
    distance from the initiator has parity ``want``, and a function from
    the q-th of them to its offset in the side.  The distance of offset
    ``t`` is ``(start - i0 + t) mod n``: ``d0 + t`` before the ring wraps
    at ``t = n - d0`` and ``d0 + t - n`` after it."""
    d0 = (start - i0) % n
    wrap = n - d0
    len_a = np.minimum(length, wrap)
    a0 = (want - d0) % 2
    cnt_a = np.maximum(0, (len_a - a0 + 1) // 2)
    b0 = wrap + ((want - d0 + n - wrap) % 2)
    cnt_b = np.maximum(0, (length - b0 + 1) // 2)

    def at(q):
        return np.where(q < cnt_a, a0 + 2 * q, b0 + 2 * (q - cnt_a))

    return cnt_a + cnt_b, at


def tree(n: int, root: int, k: int, color: Optional[int] = None
         ) -> Tuple[np.ndarray, np.ndarray]:
    """``(parent, depth)`` over ring positions ``0..n-1`` of one
    dissemination tree rooted at ring position ``root``.  ``color`` is
    None for Snow, 0 for the Coloring primary tree and 1 for its
    secondary tree.  ``depth`` is -1 where the tree does not reach."""
    if k < 2 or k % 2:
        raise ValueError(f"fan-out k must be a positive even number: {k}")
    parent = np.full(n, -1, dtype=np.int64)
    depth = np.full(n, -1, dtype=np.int64)
    depth[root] = 0
    if n <= 1:
        return parent, depth
    half = k // 2
    one = lambda v: np.asarray([v], dtype=np.int64)  # noqa: E731
    if color == 1:
        # the predecessor receives the whole ring but the initiator and
        # sits at its far edge: everything lies on its left
        sroot = (root - 1) % n
        parent[sroot], depth[sroot] = root, 1
        node, ls, ll, rs, rl = (one(sroot), one((root + 1) % n), one(n - 2),
                                one(root), one(0))
        level = 1
    else:
        right = (n - 1) // 2
        node, ls, ll, rs, rl = (one(root), one((root + 1 + right) % n),
                                one(n - 1 - right), one((root + 1) % n),
                                one(right))
        level = 0
    while node.size:
        total = ll + rl
        kids: List[np.ndarray] = []
        owners: List[np.ndarray] = []
        # direct delivery: the whole region is at most k members
        direct = (total <= k) & (total > 0)
        for s, ln in ((ls[direct], ll[direct]), (rs[direct], rl[direct])):
            own = node[direct]
            for t in range(k):
                has = t < ln
                kids.append((s[has] + t) % n)
                owners.append(own[has])
        split = total > k
        starts = np.concatenate((rs[split], ls[split]))
        lens = np.concatenate((rl[split], ll[split]))
        own = np.concatenate((node[split], node[split]))
        if color is None:
            cnt, at = lens, (lambda q: q)
        else:
            cnt, at = _oncolor(n, starts, lens, root, color)
            # a side without a member of the tree's colour is delivered
            # to directly, every member a leaf
            bare = (cnt == 0) & (lens > 0)
            for t in range(int(lens[bare].max(initial=0))):
                has = bare & (t < lens)
                kids.append((starts[has] + t) % n)
                owners.append(own[has])
        parts = np.minimum(half, cnt)
        nxt = ([], [], [], [], [])
        prev_end = None
        for j in range(half):
            ok = j < parts
            lo, hi = _balanced_cuts(cnt, parts, j)
            last = j == parts - 1
            end = np.where(last, lens - 1, (at(hi) + at(hi + 1)) // 2)
            begin = np.zeros_like(end) if prev_end is None else prev_end + 1
            mid = at((lo + hi + 1) // 2)
            child = (starts + mid) % n
            kids.append(child[ok])
            owners.append(own[ok])
            inner = ok & (end > begin)
            nxt[0].append(child[inner])
            nxt[1].append(((starts + begin) % n)[inner])
            nxt[2].append((mid - begin)[inner])
            nxt[3].append(((starts + mid + 1) % n)[inner])
            nxt[4].append((end - mid)[inner])
            prev_end = end
        kid = np.concatenate(kids) if kids else np.zeros(0, np.int64)
        parent[kid] = np.concatenate(owners)
        depth[kid] = level + 1
        node, ls, ll, rs, rl = (np.concatenate(a) if a else np.zeros(0, np.int64)
                                for a in nxt)
        level += 1
    return parent, depth


def trees(protocol: str, n: int, root: int, k: int):
    """The trees one broadcast travels: one for Snow, two for Coloring
    (one, the primary, where the view holds two members or fewer)."""
    if protocol == "coloring":
        out = [tree(n, root, k, 0)]
        if n > 2:
            out.append(tree(n, root, k, 1))
        return out
    if protocol != "snow":
        raise ValueError(f"no reference for protocol {protocol!r}")
    return [tree(n, root, k)]


def reach(parent: np.ndarray, depth: np.ndarray, crashed: np.ndarray
          ) -> np.ndarray:
    """Members a broadcast reaches when ``crashed`` members neither
    receive nor forward: a crashed member darkens its whole subtree."""
    ok = ~crashed & (depth >= 0)
    order = np.argsort(depth, kind="stable")
    order = order[depth[order] >= 1]
    for h in range(1, int(depth.max()) + 1):
        idx = order[depth[order] == h]
        ok[idx] &= ok[parent[idx]]
    return ok


# ------------------------------------------------------------------ #
# Delays                                                              #
# ------------------------------------------------------------------ #
def _bits_nm(key, m: int, n: int) -> np.ndarray:
    """Threefry bits of a ``(message, member)`` plane, laid out member by
    member: ``(n, m)``."""
    import jax

    return np.asarray(jax.random.bits(key, (m, n), dtype=np.uint32).T)


def _unit_f32(bits: np.ndarray) -> np.ndarray:
    """The float32 uniform in [0, 1) that threefry bits stand for: the
    23 high bits as the mantissa of a number in [1, 2), minus one."""
    f = ((bits >> np.uint32(9)) | np.uint32(0x3F800000)).view(np.float32)
    return f - np.float32(1.0)


def delay_planes(seed: int, n_slots: int, m: int, n: int,
                 fixed: np.ndarray, d: Delays):
    """Forwarding and link delays per tree slot, float64, laid out
    ``(member, message)``, and the straggler mask ``(member,)`` of one
    delay seed."""
    import jax

    base = jax.random.key(int(seed))
    u = _unit_f32(np.asarray(jax.random.bits(
        jax.random.fold_in(base, TAG_STRAGGLER), (n,), dtype=np.uint32)))
    strag = (u < np.float32(d.straggler_frac)) & fixed
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    fwd, link = [], []
    for slot in range(n_slots):
        ks = jax.random.fold_in(base, slot)
        uf = _unit_f32(_bits_nm(jax.random.fold_in(ks, TAG_FWD), m, n))
        f = d.fwd_lo_s + uf.astype(np.float64) * (d.fwd_hi_s - d.fwd_lo_s)
        f[strag] = d.straggler_delay_s
        fwd.append(f)
        ul = _unit_f32(_bits_nm(jax.random.fold_in(ks, TAG_LINK), m, n))
        ul = np.maximum(lo, ul * (np.float32(1.0) - lo) + lo)
        z = erfinv(ul.astype(np.float64))
        z *= math.sqrt(2.0) * d.link_sigma
        link.append(d.link_median_s * np.exp(z))
    return fwd, link, strag


def first_delivery(parent: np.ndarray, depth: np.ndarray, root: int,
                   fwd: np.ndarray, link: np.ndarray, t0: np.ndarray,
                   dtype=np.float64) -> np.ndarray:
    """``(member, message)`` first-delivery times over one tree; NaN
    where the tree does not reach.  ``fwd``/``link`` are ``(member,
    message)`` in the tree's ring positions."""
    n, m = fwd.shape
    fwd = fwd.astype(dtype)
    link = link.astype(dtype)
    t = np.full((n, m), np.nan, dtype=dtype)
    t[root] = np.asarray(t0).astype(dtype)
    order = np.argsort(depth, kind="stable")
    for h in range(1, int(depth.max()) + 1):
        idx = order[depth[order] == h]
        p = parent[idx]
        via = fwd[p]
        via[p == root] = 0
        t[idx] = (t[p] + via) + link[idx]
    return t


def _ldt(times: np.ndarray, t0: np.ndarray, counted: np.ndarray
         ) -> np.ndarray:
    """Per message: latest delivery among the counted, reached members,
    relative to its origination; NaN where none was reached.
    ``times`` is ``(member, message)``."""
    sub = times[counted].astype(np.float64) - np.asarray(t0, np.float64)
    got = ~np.isnan(sub).all(axis=0)
    out = np.full(times.shape[1], np.nan)
    out[got] = np.nanmax(sub[:, got], axis=0)
    return out


def _per_seed(fn, seeds: Sequence[int]) -> list:
    """``fn(seed)`` for every seed, on threads: the float64 work is numpy
    and SciPy ufuncs, which run without the interpreter lock."""
    with ThreadPoolExecutor(max_workers=min(len(seeds),
                                            os.cpu_count() or 1)) as ex:
        return list(ex.map(fn, seeds))


# ------------------------------------------------------------------ #
# Traces                                                              #
# ------------------------------------------------------------------ #
class _Alive:
    """The ascending ids ``0..n-1`` without ``src`` and ``gone``, as a
    sequence, so that ``random.choice`` indexes it as it would a list."""

    def __init__(self, n: int, src: int, gone: Sequence[int]):
        self.skip = sorted(set(gone) | {src})
        self.len = n - len(self.skip)

    def __len__(self) -> int:
        return self.len

    def __getitem__(self, i: int) -> int:
        x = i
        for s in self.skip:
            if s <= x:
                x += 1
        return x


def breakdown_schedule(n: int, n_messages: int, rate_s: float,
                       trace_seed: int, t: dict
                       ) -> Tuple[np.ndarray, List[Tuple[List[int],
                                                         List[int]]]]:
    """§5.5: origination times and, per message, the ``(evicted,
    crashed)`` ids in effect.  ``t`` is the traffic file's trace block;
    a crash is drawn with the stdlib Mersenne Twister seeded by
    ``trace_seed ^ rng_xor`` over the ascending alive fixed ids."""
    rng = random.Random(trace_seed ^ t["rng_xor"])
    src = t["src"]
    events: List[Tuple[float, str, int]] = []
    crashed: List[int] = []
    for i in range(n_messages):
        if i > 0 and i % t["crash_every"] == 0:
            alive = _Alive(n, src, crashed)
            if len(alive):
                v = rng.choice(alive)
                crashed.append(v)
                tc = i * rate_s + t["crash_offset_s"]
                events.append((tc, "crash", v))
                events.append((tc + t["detect_after_s"], "evict", v))
    events.sort(key=lambda e: e[0])
    times = np.asarray([i * rate_s + t["message_offset_s"]
                        for i in range(n_messages)])
    state = []
    down: List[int] = []
    gone: List[int] = []
    ei = 0
    for tm in times:
        while ei < len(events) and events[ei][0] <= tm:
            _, kind, v = events[ei]
            if kind == "crash" and v not in gone and v not in down:
                down.append(v)
            elif kind == "evict" and v not in gone:
                gone.append(v)
                if v in down:
                    down.remove(v)
            ei += 1
        state.append((sorted(gone), sorted(down)))
    return times, state


# ------------------------------------------------------------------ #
# Answers                                                             #
# ------------------------------------------------------------------ #
def _mean(vals: List[float]) -> float:
    vals = [v for v in vals if not math.isnan(v)]
    return float(np.mean(vals)) if vals else float("nan")


def _ci95(vals: List[float]) -> float:
    vals = [v for v in vals if not math.isnan(v)]
    if len(vals) < 2:
        return 0.0
    return float(1.96 * np.std(vals, ddof=1) / np.sqrt(len(vals)))


def _row(per_seed_ldt: List[float], rmr: List[float], red: List[float],
         rel: List[float]) -> Dict[str, float]:
    ldt_ms = [v * 1000.0 for v in per_seed_ldt]
    return {"ldt_ms": _mean(per_seed_ldt) * 1000.0,
            "ldt_ms_ci95": _ci95(ldt_ms),
            "rmr_B": _mean(rmr), "redundant_B": _mean(red),
            "payload_B": _mean(rmr) - _mean(red),
            "reliability": float(min(rel))}


def stable_row(protocol: str, seeds: Sequence[int], cfg: dict,
               dtype=np.float64) -> Dict[str, float]:
    """The row of a stable sweep query: every member of ``0..n-1``
    alive, the initiator at id 0, ``n_messages`` broadcasts
    ``rate_s`` apart."""
    n, k, m = cfg["n"], cfg["k"], cfg["n_messages"]
    d = Delays.from_config(cfg)
    frame = cfg["frame_header_B"] + cfg["payload_B"]
    plans = trees(protocol, n, 0, k)
    t0 = np.arange(m) * cfg["rate_s"]
    counted = np.arange(n) != 0
    covered = sum(int((dp >= 1).sum()) for _, dp in plans)

    def one(seed):
        fwd, link, _ = delay_planes(seed, len(plans), m, n,
                                    np.ones(n, dtype=bool), d)
        total = None
        for slot, (par, dp) in enumerate(plans):
            t = first_delivery(par, dp, 0, fwd[slot], link[slot], t0, dtype)
            total = t if total is None else np.fmin(total, t)
        got = (~np.isnan(total[counted])).sum(axis=0)
        return (float(np.mean(_ldt(total, t0, counted))),
                float(np.mean(got / (n - 1))))

    ldts, rel = zip(*_per_seed(one, seeds))
    rmr = [frame * covered / (n - 1)] * len(seeds)
    red = [float(frame * (len(plans) - 1))] * len(seeds)
    return _row(list(ldts), rmr, red, list(rel))


def breakdown_row(protocol: str, seeds: Sequence[int], trace_seed: int,
                  cfg: dict, traffic: dict, dtype=np.float64
                  ) -> Dict[str, float]:
    """The row of a §5.5 breakdown query over the fixed members
    ``0..n-1`` (the initiator excluded)."""
    n, k, m = cfg["n"], cfg["k"], cfg["n_messages"]
    t = traffic["trace"]
    src = t["src"]
    d = Delays.from_config(cfg)
    frame = cfg["frame_header_B"] + cfg["payload_B"]
    times, state = breakdown_schedule(n, m, cfg["rate_s"], trace_seed, t)
    # epochs: runs of messages under one membership state
    epochs: List[Tuple[int, int]] = []
    for j in range(m):
        if j == 0 or state[j] != state[j - 1]:
            epochs.append((j, j + 1))
        else:
            epochs[-1] = (epochs[-1][0], j + 1)
    shapes = []
    for a, _ in epochs:
        gone, down = state[a]
        members = np.setdiff1d(np.arange(n), np.asarray(gone, dtype=np.int64))
        root = int(np.searchsorted(members, src))
        plans = trees(protocol, members.shape[0], root, k)
        crashed = np.isin(members, down)
        oks = [reach(par, dp, crashed) & (dp >= 1) for par, dp in plans]
        sel = (members < n) & (members != src)
        shapes.append((members, root, plans, oks, sel))
    n_slots = max(len(s[2]) for s in shapes)
    rmr, red, rel = [], [], []
    for (a, b), (members, root, plans, oks, sel) in zip(epochs, shapes):
        n_int = int(sel.sum())
        receipts = sum(ok.astype(np.int64) for ok in oks)
        rec_sub = int(receipts[sel].sum())
        cnt = int(np.logical_or.reduce(oks)[sel].sum())
        rel.extend([cnt / max(1, n_int)] * (b - a))
        rmr.extend([frame * rec_sub / max(1, n_int)] * (b - a))
        red.extend([frame * (rec_sub - cnt) / max(1, n_int)] * (b - a))

    def one(seed):
        fwd, link, _ = delay_planes(seed, n_slots, m, n,
                                    np.ones(n, dtype=bool), d)
        per_msg = []
        for (a, b), (members, root, plans, oks, sel) in zip(epochs, shapes):
            total = None
            for slot, ((par, dp), ok) in enumerate(zip(plans, oks)):
                tt = first_delivery(par, dp, root, fwd[slot][members, a:b],
                                    link[slot][members, a:b], times[a:b],
                                    dtype)
                dark = ~ok & (np.arange(len(dp)) != root)
                tt[dark] = np.nan
                total = tt if total is None else np.fmin(total, tt)
            per_msg.append(_ldt(total, times[a:b], sel))
        return float(np.nanmean(np.concatenate(per_msg)))

    return _row(_per_seed(one, seeds), [float(np.mean(rmr))] * len(seeds),
                [float(np.mean(red))] * len(seeds),
                [float(np.mean(rel))] * len(seeds))


def answer(query: dict, cfg: dict, traffic: dict, dtype=np.float64
           ) -> Dict[str, float]:
    """The reference row of one sweep query (see ``sweep.query``)."""
    if traffic["scene"] == "stable":
        return stable_row(traffic["protocol"], query["seeds"], cfg, dtype)
    if traffic["scene"] == "breakdown":
        return breakdown_row(traffic["protocol"], query["seeds"],
                             query["trace_seed"], cfg, traffic, dtype)
    raise ValueError(f"no reference for scene {traffic['scene']!r}")
