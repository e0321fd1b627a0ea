"""Closed-form vectorized broadcast engine: delivery times over TreePlan.

For a **frozen** uniform view, Snow's first-delivery times are a pure
function of the dissemination tree plus the sampled delays (the paper's
Eq. 8 height bound is exactly this structural predictability):

    t[v] = t0 + Σ over ancestors u of v  (fwd_delay(u) + link_latency(u→v))

with ``fwd_delay(root) = 0`` (the initiator forwards immediately).  This
module evaluates that sum for *every* node of a :class:`TreePlan` with a
level-synchronous gather-and-add over the plan's ``parent``/``depth``
arrays — O(log_k n) host steps, each one batched NumPy/JAX op — batched
across messages (and, at the benchmark layer, seeds) in one shot.
Coloring is the elementwise ``min`` of the primary/secondary tree times;
LDT / RMR / Reliability reduce straight from the arrays.

Bit-exactness against the event-driven simulator
------------------------------------------------
Both engines consume the same :class:`DelayBank` — delays pre-sampled per
``(node, message, tree)`` — and the level sweep reproduces the event
loop's float grouping exactly: the event path schedules the forward at
``t_parent + fwd`` and the delivery at ``(t_parent + fwd) + link``, so
the sweep computes ``(t[parent] + fwd[parent]) + link[v]`` as two
separate adds in that order.  ``tests/test_engine.py`` asserts exact
(not statistical) equality of every first-delivery time.

Epoch segmentation (churn / breakdown)
--------------------------------------
The closed form needs a frozen view, not a *permanently* frozen one.  A
:class:`~repro.core.churn.ChurnTrace` partitions simulated time into
epochs at its membership events; within an epoch the view is constant,
so :func:`run_trace_vectorized` re-plans per epoch and reduces every
broadcast of the epoch in one batched sweep.  Crashed-but-not-yet-
evicted members stay in the membership (and the intended sets) but are
blackholed: :func:`reach_mask` kills them and their whole subtrees, so
Reliability dips exactly as in the paper's §5.5 — until the trace's
``evict`` event re-plans them away.  See DESIGN.md §6.

Control-plane accounting (overhead axis)
----------------------------------------
Every vectorized runner accepts ``control=`` (a
:class:`repro.core.control.ControlParams`): when set, the DESIGN.md §9
closed-form control model — SWIM probe traffic and anti-entropy merges
integrated over the trace's epoch spans, member-update dissemination
per effective membership event (the stale engine prices it from its
adoption sweeps) — is added to the metrics' ``control_summary()``,
statistically pinned against the live loop's per-frame classification
(``tests/test_control_plane.py``).  ``control=None`` (default) accounts
nothing, preserving the engines' byte-identical differential contracts.
The declarative sweep layer on top of these runners is
:mod:`repro.core.experiments`.

The remaining event-loop-only territory: reliable-message retries
(epoch > 0 rebroadcasts), live SWIM/anti-entropy protocol traffic, and
non-Snow baselines.

``REPRO_ENGINE_BACKEND`` (``numpy`` | ``jax``) selects the default array
backend wherever a caller does not pass one — the CI matrix runs the
suite under both.
"""
from __future__ import annotations

import math
import os
import random
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Set, Tuple, Union

import numpy as np

from .churn import ChurnTrace, paper_breakdown_trace, paper_churn_trace
from .control import (ACK_B, UPDATE_FRAME_B, ControlParams, apply_control,
                      repair_digest_epoch_bytes, repair_fetch_bytes,
                      snow_stable_control, snow_trace_control)
from .faults import LossModel, RepairModel
from .ids import NodeId
from .messages import Data
from .planner import (PRIMARY, SECONDARY, TreePlan, depth_levels,
                      plan_broadcast, plan_colored, plan_delta_chain)
from .sim import LatencyModel, Metrics, Sim, straggler_sample
from .spans import span
from .specs import NetworkSpec, RunSpec, resolve_specs
from .topology import TIER_NAMES, HierarchicalLatency

#: expected one-way link latency (lognormal mean) — the closed-form
#: repair pass prices its digest/fetch round trips in these
_MEAN_LINK_S = LatencyModel.median_s * math.exp(LatencyModel.sigma ** 2 / 2)
#: digest request + response + fetch + payload: four link traversals
FETCH_RTT_S = 4.0 * _MEAN_LINK_S


def _repair_control_params(control: Optional[ControlParams],
                           repair: Optional[RepairModel]
                           ) -> Optional[ControlParams]:
    """Repair replaces the plain anti-entropy cadence: when both are
    configured, the §9 anti-entropy stream integrates at the repair
    interval (the live tick does the SyncReq merge and the digest
    exchange in one round)."""
    if control is None or repair is None:
        return control
    return replace(control, anti_entropy_interval_s=repair.interval_s)


def default_backend() -> str:
    """Array backend used when a caller passes ``backend=None`` —
    ``$REPRO_ENGINE_BACKEND`` (the CI matrix axis) or ``"numpy"``."""
    return os.environ.get("REPRO_ENGINE_BACKEND", "numpy")


def _resolve_backend(backend: Optional[str]) -> str:
    return default_backend() if backend is None else backend


def _slot(tree: Optional[int]) -> int:
    """Standard and primary broadcasts share slot 0; secondary is 1."""
    return 1 if tree == SECONDARY else 0


class DelayBank:
    """Pre-sampled per-(node, message, tree-slot) delays.

    The single source of randomness for a stable run: the event engine
    reads scalars out of it (``NodeBase.forward_delay`` /
    ``Network.send``) while the closed-form engine consumes whole
    ``(messages, nodes)`` planes — so the two produce identical times.

    Message ids map to columns on first use, in broadcast order (the
    initiator's immediate root sends touch the bank at origination time,
    which is strictly increasing across messages).
    """

    def __init__(self, members: np.ndarray, fwd: np.ndarray,
                 link: np.ndarray):
        self.members = np.ascontiguousarray(members)
        self.fwd = fwd        #: (n, M, S) forwarding delay, seconds
        self.link = link      #: (n, M, S) inbound link latency, seconds
        self.n_messages = int(fwd.shape[1])
        self.n_slots = int(fwd.shape[2])
        self._cols: Dict[int, int] = {}
        n = int(self.members.shape[0])
        # ids == ring indices (the common scenarios case) → O(1) lookups
        self._identity = bool(n and self.members[0] == 0
                              and self.members[-1] == n - 1)

    @classmethod
    def sample(cls, seed: int, members: np.ndarray,
               stragglers: Set[NodeId], n_messages: int, n_slots: int = 1,
               *, lo: float = 0.010, hi: float = 0.200,
               straggler_delay: float = 1.0,
               latency: Optional[LatencyModel] = None) -> "DelayBank":
        """Vectorized §5.2 sampling: uniform 10–200 ms forwarding delay
        (stragglers pinned at 1 s), lognormal sub-ms link latency."""
        latency = latency or LatencyModel()
        members = np.ascontiguousarray(members)
        n = int(members.shape[0])
        g = np.random.default_rng(
            np.random.SeedSequence([seed & 0xFFFFFFFF, 0xDE1A]))
        fwd = g.uniform(lo, hi, (n, n_messages, n_slots))
        link = latency.median_s * np.exp(
            g.normal(0.0, latency.sigma, (n, n_messages, n_slots)))
        if stragglers:
            smask = np.isin(members,
                            np.fromiter(stragglers, dtype=members.dtype))
            fwd[smask] = straggler_delay
        return cls(members, fwd, link)

    # -- scalar views (event-engine side) ---------------------------------
    def column(self, mid: int) -> Optional[int]:
        """The bank column of ``mid``; assigned on first use, in order."""
        col = self._cols.get(mid)
        if col is None and len(self._cols) < self.n_messages:
            col = len(self._cols)
            self._cols[mid] = col
        return col

    def _index(self, node: NodeId) -> Optional[int]:
        if self._identity:
            i = int(node)
            return i if 0 <= i < self.members.shape[0] else None
        i = int(np.searchsorted(self.members, node))
        if i < self.members.shape[0] and self.members[i] == node:
            return i
        return None

    def fwd_for(self, node: NodeId, mid: int, tree: Optional[int] = None,
                epoch: int = 0) -> Optional[float]:
        if epoch != 0:
            return None       # retries re-time their forwards (live RNG)
        s = _slot(tree)
        if s >= self.n_slots:
            return None
        i = self._index(node)
        if i is None:
            return None
        # column assignment last: an out-of-coverage query must not burn
        # a column and shift every later message off its samples
        col = self.column(mid)
        if col is None:
            return None
        return float(self.fwd[i, col, s])

    def link_for(self, dst: NodeId, msg) -> Optional[float]:
        """Latency of the send carrying ``msg`` into ``dst`` — covered
        only for first-epoch broadcast DATA frames (the frames the
        closed-form engine models); anything else falls back to the live
        RNG in :meth:`Network.send`."""
        mid = getattr(msg, "mid", None)
        tree = getattr(msg, "tree", -2)
        if mid is None or tree == -2 or getattr(msg, "epoch", 0) != 0:
            return None
        s = _slot(tree)
        if s >= self.n_slots:
            return None
        i = self._index(dst)
        if i is None:
            return None
        col = self.column(mid)   # last — see fwd_for
        if col is None:
            return None
        return float(self.link[i, col, s])

    def rows_for(self, members: np.ndarray) -> Optional[np.ndarray]:
        """Bank row of every entry of a (possibly permuted) member
        array, or None when ``members`` already IS the bank order — the
        locality-plan gather.  The None fast path keeps the default
        (sorted-ring) float program untouched: no gather, no copy."""
        if members is self.members:
            return None
        if members.shape == self.members.shape \
                and np.array_equal(members, self.members):
            return None
        return np.searchsorted(self.members, members)

    # -- plane views (closed-form side) -----------------------------------
    def fwd_plane(self, slot: int, n_messages: Optional[int] = None):
        """(M, n) forwarding delays for one tree slot."""
        m = self.n_messages if n_messages is None else n_messages
        return np.ascontiguousarray(self.fwd[:, :m, slot].T)

    def link_plane(self, slot: int, n_messages: Optional[int] = None):
        m = self.n_messages if n_messages is None else n_messages
        return np.ascontiguousarray(self.link[:, :m, slot].T)


def bank_for_stable(seed: int, n: int, protocol: str, n_messages: int,
                    *, straggler_frac: float = 0.05,
                    straggler_delay: float = 1.0,
                    latency: Optional[LatencyModel] = None) -> DelayBank:
    """The bank ``run_stable`` shares between engines: same straggler draw
    as ``build_cluster``/``assign_profiles`` (first use of the profile
    RNG), two tree slots for coloring.  ``latency`` parameterizes the
    link jitter stream (hierarchical models pass their reference model —
    identical parameters to the default, so the stream never shifts)."""
    rng = random.Random(seed ^ 0x5EED)
    stragglers = straggler_sample(rng, range(n), straggler_frac)
    return DelayBank.sample(seed, np.arange(n), stragglers, n_messages,
                            n_slots=2 if protocol == "coloring" else 1,
                            straggler_delay=straggler_delay,
                            latency=latency)


def bank_for_trace(seed: int, trace: ChurnTrace, protocol: str,
                   *, straggler_frac: float = 0.05,
                   straggler_delay: float = 1.0,
                   extra_messages: int = 0,
                   latency: Optional[LatencyModel] = None) -> DelayBank:
    """One bank covering a whole :class:`ChurnTrace`: every id that is
    ever a member (fixed ∪ joins) gets a delay row, every broadcast a
    column.  The straggler draw replicates ``build_cluster`` /
    ``assign_profiles`` over the *fixed* ids (first use of the profile
    RNG), so the event engine on the same trace picks the same
    stragglers; transients are never stragglers (they get fresh default
    profiles in the scenarios, same as here).

    ``extra_messages`` appends columns beyond the trace's broadcasts —
    the stale-view engine samples one per epoch transition for the
    MemberUpdate adoption sweep."""
    rng = random.Random(seed ^ 0x5EED)
    stragglers = straggler_sample(rng, range(trace.n), straggler_frac)
    return DelayBank.sample(seed, trace.all_ids(), stragglers,
                            len(trace.msg_times) + extra_messages,
                            n_slots=2 if protocol == "coloring" else 1,
                            straggler_delay=straggler_delay,
                            latency=latency)


# ------------------------------------------------------------------ #
# Level-synchronous closed-form sweep                                 #
# ------------------------------------------------------------------ #
#: back-compat alias — plan-aware callers should use ``plan.levels``,
#: which caches the argsort per TreePlan (epoch plans are reused across
#: seeds, so per-sweep recomputation was pure waste)
_levels = depth_levels


def delivery_times(plan: TreePlan, fwd, link, t0=0.0,
                   backend: Optional[str] = None):
    """First-delivery time of every node of ``plan``, closed form.

    ``fwd``/``link`` are ``(..., n)`` arrays (leading batch dims are
    broadcast together, typically ``(M, n)`` for M messages); ``t0`` is a
    scalar or ``(...,)`` start-time array.  Returns ``(..., n)`` float64
    absolute times; NaN marks nodes the tree does not reach.  The float
    grouping ``(t[parent] + fwd[parent]) + link[v]`` matches the event
    loop exactly (see module docstring).
    """
    backend = _resolve_backend(backend)
    parent = np.asarray(plan.parent)
    depth = np.asarray(plan.depth)
    fwd = np.asarray(fwd, dtype=np.float64)
    link = np.asarray(link, dtype=np.float64)
    if backend == "jax":
        return _delivery_times_jax(parent, depth, plan.root, fwd, link, t0)
    t = np.full(np.broadcast_shapes(fwd.shape, link.shape), np.nan)
    t[..., plan.root] = t0
    root = plan.root
    for idx in plan.levels:
        p = parent[idx]
        fp = np.where(p == root, 0.0, fwd[..., p])
        t[..., idx] = (t[..., p] + fp) + link[..., idx]
    return t


_JIT_SWEEP = None


def _delivery_times_jax(parent, depth, root, fwd, link, t0):
    """``jax.jit``-compiled variant of the level sweep.

    The per-level gather runs over all n nodes with a ``where`` mask
    inside ``lax.fori_loop`` — O(n·H) device work instead of O(n), but
    every step is one fused XLA op and the whole sweep is a single
    compiled call (cached per shape).
    """
    global _JIT_SWEEP
    import jax
    import jax.numpy as jnp
    from jax import lax

    if _JIT_SWEEP is None:
        def sweep(parent, depth, fwd, link, t0, *, root, height):
            t = jnp.full(jnp.broadcast_shapes(fwd.shape, link.shape),
                         jnp.nan, dtype=fwd.dtype)
            t = t.at[..., root].set(t0)
            fp = jnp.where(parent == root, 0.0,
                           jnp.take(fwd, parent, axis=-1))

            def body(h, t):
                cand = (jnp.take(t, parent, axis=-1) + fp) + link
                return jnp.where(depth == h, cand, t)

            return lax.fori_loop(1, height + 1, body, t)

        _JIT_SWEEP = jax.jit(sweep, static_argnames=("root", "height"))

    height = int(depth.max()) if depth.size else 0
    # device default dtype (f32 unless jax_enable_x64): the jit sweep is
    # the throughput backend; exactness lives on the numpy path
    dt = jnp.result_type(float)
    out = _JIT_SWEEP(jnp.asarray(parent), jnp.asarray(depth),
                     jnp.asarray(fwd.astype(dt)), jnp.asarray(link.astype(dt)),
                     jnp.asarray(np.asarray(t0, dtype=dt)),
                     root=int(root), height=height)
    return np.asarray(out)


def stable_plans(protocol: str, members: np.ndarray, root: NodeId,
                 k: int, ring: Optional[np.ndarray] = None
                 ) -> Tuple[TreePlan, ...]:
    """The plan set one broadcast propagates over: one standard tree for
    snow, the primary/secondary double tree for coloring.  The event
    engine only hands off the secondary root for views larger than two
    (snow_node.broadcast), so degenerate coloring clusters propagate
    over the primary tree alone.  ``ring`` plans over an explicit
    (locality-ordered) permutation of ``members`` instead of the sorted
    ring — the plan's arrays are then indexed by ring position."""
    n = int(members.shape[0]) if ring is None else int(ring.shape[0])
    if protocol == "coloring":
        plans = (plan_colored(members, root, k, PRIMARY, ring=ring),)
        if n > 2:
            plans += (plan_colored(members, root, k, SECONDARY, ring=ring),)
        return plans
    return (plan_broadcast(members, root, k, ring=ring),)


def plan_bytes(plans: Sequence[TreePlan], payload: int) -> int:
    """Total DATA bytes one broadcast moves: one frame per delivery, one
    delivery per node reached per tree — identical to the event engine's
    per-receipt ``Metrics.add_bytes`` accounting on the stable path."""
    size = Data(0, 0, None, None, payload).size
    return size * sum(int((np.asarray(p.depth) >= 1).sum()) for p in plans)


def reach_mask(plan: TreePlan, crashed: np.ndarray) -> np.ndarray:
    """(n,) bool — which nodes a broadcast over ``plan`` actually reaches
    when the ``crashed`` (bool mask over ring indices) members are
    silently blackholed (§5.5): a crashed node's inbound traffic is
    dropped, it never forwards, so its entire subtree goes dark.  One
    level-synchronous AND-sweep down the plan."""
    depth = np.asarray(plan.depth)
    parent = np.asarray(plan.parent)
    ok = ~np.asarray(crashed, dtype=bool)
    ok &= depth >= 0
    for idx in plan.levels:
        ok[idx] &= ok[parent[idx]]
    return ok


def broadcast_times(plans: Sequence[TreePlan], bank: DelayBank,
                    n_messages: int, rate_s: float = 1.0,
                    backend: Optional[str] = None,
                    loss: Optional[LossModel] = None,
                    with_receipts: bool = False,
                    hier: Optional[HierarchicalLatency] = None,
                    tier_acc: Optional[np.ndarray] = None):
    """(M, n) absolute first-delivery times for M broadcasts originating
    at ``i * rate_s`` — the elementwise min over the plan set.

    ``loss`` applies the §11 counter-RNG loss masks per tree: failed
    attempts add their retransmit timeouts to the link plane, edges dead
    after ``max_attempts`` go NaN, and the NaN rides the level sweep's
    adds so the whole subtree goes dark on that tree — before the
    coloring min, exactly like crash blackholing.  ``with_receipts``
    additionally returns the (M, n) per-tree receipt counts (under loss
    a tree only charges the nodes it actually reaches).

    ``hier`` activates the DESIGN.md §12 tier model: each plan's link
    plane is scaled elementwise by its per-tier factor (the exact float
    multiply ``Network.send`` performs per scalar), the per-tier loss
    rates (when set) override the flat loss threshold, and ``tier_acc``
    (a (4,) float64 accumulator) collects per-tier receipt counts.
    Locality-ordered plans gather the bank planes through
    :meth:`DelayBank.rows_for`; on the default sorted ring the gather —
    and every other new branch — is skipped entirely, keeping the flat
    float program byte-identical."""
    t0 = np.arange(n_messages, dtype=np.float64) * rate_s
    cols = np.arange(n_messages)
    total = None
    receipts = None
    loss_on = loss is not None and (
        loss.active or (hier is not None and hier.loss_rates is not None))
    for plan in plans:
        s = _slot(plan.tree)
        fwd = bank.fwd_plane(s, n_messages)
        link = bank.link_plane(s, n_messages)
        rows = bank.rows_for(plan.members)
        if rows is not None:
            fwd = np.ascontiguousarray(fwd[:, rows])
            link = np.ascontiguousarray(link[:, rows])
        if hier is not None:
            link = link * hier.scale_plane(plan)[None, :]
        if loss_on:
            rates = None if hier is None else hier.loss_rate_plane(plan)
            link = loss.apply_to_links(link, cols, s, plan.members,
                                       rates=rates)
        t = delivery_times(plan, fwd, link, t0=t0, backend=backend)
        if with_receipts or tier_acc is not None:
            r = (~np.isnan(t)) & (np.asarray(plan.depth) >= 1)
            if with_receipts:
                receipts = r.astype(np.int64) if receipts is None \
                    else receipts + r
            if tier_acc is not None:
                tier_acc += np.bincount(
                    hier.tier_plane(plan),
                    weights=r.sum(axis=0).astype(np.float64),
                    minlength=4)[:4]
        total = t if total is None else np.fmin(total, t)
    return (total, receipts) if with_receipts else total


def _repair_fill(total: np.ndarray, t0s: np.ndarray, members: np.ndarray,
                 crashed_mask: Optional[np.ndarray], m: int, c: int,
                 repair: RepairModel) -> Tuple[np.ndarray, np.ndarray]:
    """Fill §11 closed-form repair times into a (M, n) delivery plane:
    every alive node a broadcast missed (loss-darkened or crash-darkened
    subtree) pulls the payload at its first digest tick after the miss.
    Returns ``(times, missed)`` — the repaired plane and the (M, n) bool
    mask of repaired slots (crashed nodes stay NaN: nothing repairs a
    blackholed node, so reliability with repair is over the alive set)."""
    alive = np.ones(members.shape[0], dtype=bool) if crashed_mask is None \
        else ~crashed_mask
    missed = np.isnan(total) & alive[None, :]
    if missed.any():
        t0s = np.asarray(t0s, dtype=np.float64)[:, None]
        wait = repair.repair_wait(t0s, members, m, c, FETCH_RTT_S)
        total = np.where(missed, t0s + wait, total)
    return total, missed


# ------------------------------------------------------------------ #
# Metrics over arrays                                                 #
# ------------------------------------------------------------------ #
class ArrayMetrics(Metrics):
    """:class:`Metrics` backed by per-message delivery-time arrays.

    ``per_message`` (and therefore the inherited ``summary``) produces
    rows identical to the event engine's — same keys, same float
    arithmetic (elementwise ``t - t0`` then max) — without ever building
    per-node dicts, so an n = 10⁶ run stays array-shaped end to end.
    """

    def __init__(self, members: np.ndarray):
        super().__init__()
        self.members = np.ascontiguousarray(members)
        self.times: Dict[int, np.ndarray] = {}      # (n,) absolute; NaN=miss
        self.src_index: Dict[int, int] = {}
        #: per-message member arrays for epoch runs, where membership
        #: changes between broadcasts; absent ⇒ ``self.members``
        self.msg_members: Dict[int, np.ndarray] = {}
        #: per-message (n,) DATA-frame receipt counts per member — the
        #: array analogue of the event engine's per-receipt add_bytes;
        #: ``receipts - delivered`` is the duplicate count
        self.receipts: Dict[int, np.ndarray] = {}
        self.frame_bytes: Dict[int, int] = {}       # wire size of one frame
        #: per-message (n,) bool — nodes delivered by the §11 pull-repair
        #: pass (they hold a time but no DATA receipt)
        self.repaired: Dict[int, np.ndarray] = {}
        #: per-message (n,) bool — the metered (topic-multicast) subset
        #: of the member array; absent ⇒ every member is intended.  The
        #: array analogue of the event engine's ``begin(..., intended)``
        #: sets (DESIGN.md §14): dissemination still covers the full
        #: membership, only the metrics denominator narrows.
        self.msg_intended: Dict[int, np.ndarray] = {}

    def record_message(self, mid: int, t0: float, src_index: int,
                       times: np.ndarray, nbytes: int,
                       members: Optional[np.ndarray] = None,
                       receipts: Optional[np.ndarray] = None,
                       frame_bytes: Optional[int] = None,
                       repaired: Optional[np.ndarray] = None,
                       intended: Optional[np.ndarray] = None) -> None:
        self.start[mid] = t0
        self.src_index[mid] = src_index
        self.times[mid] = times
        self.data_bytes[mid] = nbytes
        if members is not None:
            self.msg_members[mid] = members
        if receipts is not None:
            self.receipts[mid] = receipts
        if frame_bytes is not None:
            self.frame_bytes[mid] = frame_bytes
        if repaired is not None:
            self.repaired[mid] = repaired
        if intended is not None:
            self.msg_intended[mid] = intended

    def times_for(self, mid: int) -> np.ndarray:
        return self.times[mid]

    def members_for(self, mid: int) -> np.ndarray:
        """The membership (= ``times_for`` indexing) of one broadcast."""
        return self.msg_members.get(mid, self.members)

    def per_message(self, subset: Optional[Set[NodeId]] = None) -> List[dict]:
        sub = None
        if subset is not None:
            sub = np.fromiter(subset, dtype=self.members.dtype,
                              count=len(subset))
        sel_cache: Dict[int, np.ndarray] = {}   # one isin per member array
        rows = []
        for mid, t0 in sorted(self.start.items()):
            mem = self.msg_members.get(mid, self.members)
            if sub is None:
                mask = np.ones(mem.shape[0], dtype=bool)
            else:
                sel = sel_cache.get(id(mem))
                if sel is None:
                    sel = np.isin(mem, sub)
                    sel_cache[id(mem)] = sel
                mask = sel.copy()
            imask = self.msg_intended.get(mid)
            if imask is not None:
                mask &= imask
            mask[self.src_index[mid]] = False        # intended excludes src
            n_int = int(mask.sum())
            if n_int == 0:
                continue
            tt = self.times[mid][mask]
            vals = tt[~np.isnan(tt)] - t0
            rec = self.receipts.get(mid)
            frame = self.frame_bytes.get(mid, 0)
            if rec is None:
                # legacy record: no per-node receipt info — whole-cluster
                # bytes, no duplicate split
                total = self.data_bytes.get(mid, 0)
                red = dups = 0
            elif sub is None:
                # whole-cluster accounting matches the event engine's
                # global totals; nodes delivered without a receipt (the
                # originator) contribute all their receipts as duplicates
                total = self.data_bytes.get(mid, 0)
                by_receipt = (~np.isnan(self.times[mid])) & (rec >= 1)
                by_receipt[self.src_index[mid]] = False  # src delivered at t0
                dups = int(rec.sum()) - int(by_receipt.sum())
                red = frame * dups
            else:
                rsub = int(rec[mask].sum())
                total = frame * rsub
                # repair-delivered nodes hold a time without a DATA
                # receipt — they are not duplicates of anything
                rep = self.repaired.get(mid)
                n_rep = int(rep[mask].sum()) if rep is not None else 0
                dups = rsub - (vals.size - n_rep)
                red = frame * dups
            rows.append({
                "mid": mid,
                "ldt": float(vals.max()) if vals.size else float("nan"),
                "reliability": vals.size / n_int,
                "rmr": total / max(1, n_int),
                "rmr_redundant": red / max(1, n_int),
                "payload_bytes": total - red,
                "redundant_bytes": red,
                "duplicates": dups,
            })
        return rows

    def _intended_masks(self, subset):
        """Yield ``(mid, t0, mask)`` — the metered population per
        message, shared by the tail/saturation reductions."""
        sub = None
        if subset is not None:
            sub = np.fromiter(subset, dtype=self.members.dtype,
                              count=len(subset))
        sel_cache: Dict[int, np.ndarray] = {}
        for mid, t0 in sorted(self.start.items()):
            mem = self.msg_members.get(mid, self.members)
            if sub is None:
                mask = np.ones(mem.shape[0], dtype=bool)
            else:
                sel = sel_cache.get(id(mem))
                if sel is None:
                    sel = np.isin(mem, sub)
                    sel_cache[id(mem)] = sel
                mask = sel.copy()
            imask = self.msg_intended.get(mid)
            if imask is not None:
                mask &= imask
            mask[self.src_index[mid]] = False
            yield mid, t0, mask

    def delivery_latencies(self, subset=None) -> np.ndarray:
        vals = []
        for mid, t0, mask in self._intended_masks(subset):
            tt = np.asarray(self.times[mid], dtype=np.float64)[mask]
            vals.append(tt[~np.isnan(tt)] - t0)
        return np.concatenate(vals) if vals else np.empty(0)

    def delivered_within(self, deadline_s: float, subset=None) -> float:
        num = den = 0
        for mid, t0, mask in self._intended_masks(subset):
            tt = np.asarray(self.times[mid], dtype=np.float64)[mask]
            den += int(mask.sum())
            num += int(np.count_nonzero(tt - t0 <= deadline_s))
        return num / den if den else 0.0


@dataclass
class VectorCluster:
    """Duck-typed stand-in for :class:`repro.core.scenarios.Cluster` on
    the closed-form path — carries the array metrics and the plan set
    instead of node objects."""

    sim: Sim
    net: None
    metrics: ArrayMetrics
    nodes: Dict
    fixed: Sequence[int]
    protocol: str
    k: int
    plans: Tuple[TreePlan, ...] = ()
    bank: Optional[DelayBank] = None
    trace: Optional[ChurnTrace] = None
    #: membership model the run used: "oracle" (all views flip at the
    #: event instant) or "stale" (views adopt via MemberUpdate sweeps)
    view_model: str = "oracle"


def run_stable_vectorized(protocol: str, n: int = 500, k: int = 4,
                          n_messages: int = 100, rate_s: float = 1.0,
                          seed: int = 0, payload: int = 64,
                          backend: Optional[str] = None,
                          bank: Optional[DelayBank] = None,
                          plans: Optional[Tuple[TreePlan, ...]] = None,
                          control: Optional[ControlParams] = None,
                          loss: Optional[LossModel] = None,
                          repair: Optional[RepairModel] = None,
                          *, net: Optional[NetworkSpec] = None,
                          run: Optional[RunSpec] = None) -> VectorCluster:
    """The stable scenario (§5.3) in closed form: no nodes, no events —
    plan once, sample the bank, one level-synchronous sweep for all
    messages.  Metrics rows are bit-exact against
    ``run_stable(..., engine="events")`` on the shared bank.

    ``net=``/``run=`` are the spec API (DESIGN.md §12.4); the loose
    ``backend``/``control``/``loss``/``repair`` kwargs are the
    deprecated equivalents.  A hierarchical ``net.latency`` scales every
    link plane per tier and fills ``metrics.tier_bytes``;
    ``net.locality="zone"`` plans over the locality ring order.

    ``control`` (a :class:`~repro.core.control.ControlParams`) adds the
    §9 closed-form control-plane bytes — SWIM + anti-entropy at their
    steady rates over the run window ``n_messages * rate_s`` — to the
    metrics' ``control_summary()``.  ``None`` (default) accounts no
    control traffic, matching the live loop's stable configuration
    (SWIM and anti-entropy disabled), which keeps the engines'
    differential tests byte-identical."""
    assert protocol in ("snow", "coloring"), \
        f"closed-form engine models snow/coloring, not {protocol!r}"
    from .messages import fresh_mid

    net, run = resolve_specs(net, run, caller="run_stable_vectorized",
                             backend=backend, control=control,
                             loss=loss, repair=repair)
    backend, control = run.backend, run.control
    loss, repair, hier = net.loss, net.repair, net.hier
    members = np.arange(n)
    ring = net.ring(members)
    if bank is None:
        bank = bank_for_stable(seed, n, protocol, n_messages,
                               latency=net.latency_model())
    if plans is None:
        plans = stable_plans(protocol, members, 0, k, ring=ring)
    plan_members = plans[0].members
    src_index = plans[0].root
    frame = Data(0, 0, None, None, payload).size
    lossy = net.loss_on
    metrics = ArrayMetrics(plan_members)
    tier_acc = None if hier is None else np.zeros(4)
    if not lossy:
        times = broadcast_times(plans, bank, n_messages, rate_s, backend,
                                hier=hier, tier_acc=tier_acc)
        nbytes = plan_bytes(plans, payload)
        # one receipt per node per tree that reaches it (uniform stable
        # view: every tree reaches every non-root node) — coloring's
        # second frame is the duplicate the event engine records
        receipts = sum(np.asarray((np.asarray(p.depth) >= 1),
                                  dtype=np.int64) for p in plans)
        for i in range(n_messages):
            metrics.record_message(fresh_mid(), i * rate_s, src_index,
                                   times[i], nbytes, receipts=receipts,
                                   frame_bytes=frame)
    else:
        # under loss, receipts and bytes depend on which edges survived
        times, rec = broadcast_times(plans, bank, n_messages, rate_s,
                                     backend, loss=loss,
                                     with_receipts=True, hier=hier,
                                     tier_acc=tier_acc)
        repaired = None
        if repair is not None:
            times, repaired = _repair_fill(
                times, np.arange(n_messages, dtype=np.float64) * rate_s,
                plan_members, None, n, 0, repair)
        for i in range(n_messages):
            metrics.record_message(
                fresh_mid(), i * rate_s, src_index, times[i],
                frame * int(rec[i].sum()), receipts=rec[i],
                frame_bytes=frame,
                repaired=None if repaired is None else repaired[i])
    if tier_acc is not None:
        metrics.tier_bytes = [float(frame * v) for v in tier_acc]
    if control is not None:
        params = _repair_control_params(control, repair)
        apply_control(metrics,
                      snow_stable_control(n, n_messages * rate_s, params))
        if repair is not None:
            n_missed = float(sum(r.sum() for r in metrics.repaired.values()))
            apply_control(metrics, {"repair": repair_digest_epoch_bytes(
                n, 0, n_messages * rate_s, repair.interval_s)
                + repair_fetch_bytes(n_missed, payload)})
    return VectorCluster(sim=Sim(seed=seed), net=None, metrics=metrics,
                         nodes={}, fixed=list(range(n)), protocol=protocol,
                         k=k, plans=plans, bank=bank)


def stable_sweep(protocol: str, n: int, k: int, seeds: Sequence[int],
                 n_messages: int = 2, rate_s: float = 1.0,
                 backend: Optional[str] = None,
                 plans: Optional[Tuple[TreePlan, ...]] = None,
                 payload: int = 64,
                 control: Optional[ControlParams] = None,
                 engine: Optional[str] = None,
                 loss: Optional[LossModel] = None,
                 repair: Optional[RepairModel] = None,
                 *, net: Optional[NetworkSpec] = None,
                 run: Optional[RunSpec] = None) -> List[dict]:
    """Multi-seed stable-scenario sweep for the scale benchmarks.

    The plan set depends only on ``(members, root, k)`` and is reused
    across seeds (pass ``plans`` to reuse one built elsewhere).
    ``net=``/``run=`` are the spec API (DESIGN.md §12.4); a
    hierarchical ``net.latency`` scales the link planes per tier and
    adds per-broadcast tier-byte keys (``intra_rack_B`` ...
    ``cross_region_B``) to every row, and ``net.locality="zone"`` plans
    over the locality ring (lossless sweeps only — the loss/repair
    reductions assume the root sits at ring index 0).

    ``engine`` selects the orchestration model:

    * ``"host"`` (default) — each seed re-samples its materialized
      :class:`DelayBank` on the host and re-runs the level sweep
      (``backend`` picks numpy or the per-call jitted jax sweep);
    * ``"device"`` — :mod:`repro.core.device_sweep`: no bank is ever
      materialized (delays regenerate on device from counter-based RNG
      keyed by ``(seed, node, message, slot)``) and the WHOLE sweep —
      all seeds × messages × trees — runs as one fused device dispatch,
      ``vmap``-ed across seeds.  Statistically pinned against the host
      rows (``tests/test_device_sweep.py``), not bit-equal.

    Row schema: ``ldt`` (s), ``rmr`` / ``rmr_redundant`` (bytes/node per
    message — a uniform stable view reaches every non-root node on every
    tree, so redundancy is exactly one frame per extra tree),
    ``reliability``, ``wall_s``/``plan_s`` timings (each seed's share of
    the ``snow.sweep`` span; the one-time plan compile, the
    ``snow.plan.trees`` span, is attributed to the FIRST row only —
    summing ``plan_s`` over rows equals the cost paid once), and — when
    ``control`` is
    given — the §9 per-category control totals under ``control_B`` plus
    the run duration ``duration_s`` the rates were integrated over.
    """
    net, run = resolve_specs(net, run, caller="stable_sweep",
                             engine=engine, backend=backend,
                             control=control, loss=loss, repair=repair)
    engine = "host" if run.engine == "auto" else run.engine
    backend, control = run.backend, run.control
    loss, repair, hier = net.loss, net.repair, net.hier
    ring = net.ring(np.arange(n))
    plan_s = 0.0
    if plans is None:
        with span("snow.plan.trees", epochs=1, full=1) as sp:
            plans = stable_plans(protocol, np.arange(n), 0, k, ring=ring)
        plan_s = sp.seconds
    nbytes = plan_bytes(plans, payload)
    frame = Data(0, 0, None, None, payload).size
    t0 = np.arange(n_messages, dtype=np.float64) * rate_s
    duration = n_messages * rate_s
    ctl = None
    if control:
        with span("snow.control"):
            ctl = snow_stable_control(
                n, duration, _repair_control_params(control, repair))
    seeds = list(seeds)
    lossy = net.loss_on
    tier_B = None
    if hier is not None:
        # per-broadcast tier byte split — seed-independent on the
        # lossless path (every tree reaches every covered node)
        counts = np.zeros(4)
        for p in plans:
            covered = np.asarray(p.depth) >= 1
            counts += np.bincount(hier.tier_plane(p)[covered],
                                  minlength=4)[:4]
        tier_B = {f"{name}_B": float(frame * counts[t])
                  for t, name in enumerate(TIER_NAMES)}
    if lossy or repair is not None:
        if plans[0].root != 0:
            raise NotImplementedError(
                "locality='zone' loss/repair sweeps: the faulty "
                "reductions assume the root at ring index 0")
        return _stable_sweep_faulty(
            protocol, n, k, seeds, n_messages, rate_s, backend, plans,
            payload, engine, loss if lossy else None, repair,
            nbytes, frame, t0, duration, ctl, plan_s, hier=hier)
    with span("snow.sweep", engine=engine) as sw:
        if engine == "device":
            from .device_sweep import stable_stats_device

            stats = list(zip(*stable_stats_device(
                plans, seeds, n_messages, rate_s, hier=hier)))
        else:
            assert engine == "host", \
                f"engine must be host|device, not {engine!r}"
            ridx = plans[0].root
            stats = []
            for seed in seeds:
                bank = bank_for_stable(seed, n, protocol, n_messages,
                                       latency=net.latency_model())
                times = broadcast_times(plans, bank, n_messages, rate_s,
                                        backend, hier=hier)
                # the root originates, never receives (ring index 0
                # unless a locality ring placed node 0 elsewhere)
                rel = times[:, 1:] if ridx == 0 \
                    else times[:, np.arange(times.shape[1]) != ridx]
                ldt = np.nanmax(rel - t0[:, None], axis=1)
                delivered = np.count_nonzero(~np.isnan(rel), axis=1)
                stats.append((ldt.mean(), delivered.mean() / (n - 1)))
    wall = sw.seconds / max(1, len(seeds))
    rows = []
    for i, (seed, (ldt_i, rel_i)) in enumerate(zip(seeds, stats)):
        row = {
            "seed": int(seed), "n": n, "k": k,
            "ldt": float(ldt_i),
            "rmr": nbytes / (n - 1),
            "rmr_redundant": float(frame * (len(plans) - 1)),
            "reliability": float(rel_i),
            "n_messages": n_messages,
            "wall_s": wall,
            "plan_s": plan_s if i == 0 else 0.0,
            "engine": engine,
        }
        if tier_B is not None:
            row.update(tier_B)
        if ctl is not None:
            row["control_B"] = {k_: float(v) for k_, v in ctl.items()}
            row["duration_s"] = duration
        rows.append(row)
    return rows


def _stable_sweep_faulty(protocol, n, k, seeds, n_messages, rate_s,
                         backend, plans, payload, engine, loss, repair,
                         nbytes, frame, t0, duration, ctl, plan_s,
                         hier=None) -> List[dict]:
    """The §11 loss/repair arm of :func:`stable_sweep` — separated so
    the lossless sweep keeps its exact pre-existing float program.

    Rows carry the sweep's standard schema plus ``n_repaired``,
    ``rebroadcast_B`` (one full broadcast's bytes for every message
    that missed ≥1 node — the reliable-epoch comparator) and, with
    repair on, the closed-form ``repair_B``.  ``engine="device"``
    supports loss (threefry masks, statistically pinned) but not
    repair (the repair fill needs the full times plane on the host)."""
    def _finish(seed, i, ldt, rel, rmr, red, wall, extra):
        row = {
            "seed": int(seed), "n": n, "k": k,
            "ldt": ldt,
            "rmr": rmr,
            "rmr_redundant": red,
            "reliability": rel,
            "n_messages": n_messages,
            "wall_s": wall,
            "plan_s": plan_s if i == 0 else 0.0,
            "engine": engine,
        }
        if ctl is not None:
            row["control_B"] = {k_: float(v) for k_, v in ctl.items()}
            row["duration_s"] = duration
        row.update(extra)
        if ctl is not None and "repair_B" in extra:
            row["control_B"]["repair"] = float(extra["repair_B"])
        return row

    if engine == "device":
        if repair is not None:
            raise ValueError(
                "repair sweeps require engine='host': the repair fill "
                "needs the full delivery-time plane on the host")
        if hier is not None:
            raise ValueError(
                "hierarchical loss sweeps require engine='host': the "
                "device loss kernel draws flat-rate masks only")
        from .device_sweep import stable_stats_device_loss

        with span("snow.sweep", engine=engine) as sw:
            ldt_m, rel_m, rec_m = stable_stats_device_loss(
                plans, seeds, n_messages, rate_s, loss=loss)
        wall = sw.seconds / max(1, len(seeds))
        rows = []
        for i, seed in enumerate(seeds):
            delivered = float(rel_m[i]) * (n - 1)
            # per-message miss detail stays on device; these rows exist
            # for the statistical LDT/reliability pin, so no
            # rebroadcast_B comparator here (host rows carry it)
            rows.append(_finish(
                seed, i, float(ldt_m[i]), float(rel_m[i]),
                frame * float(rec_m[i]) / (n - 1),
                frame * (float(rec_m[i]) - delivered) / (n - 1),
                wall, {"n_repaired": 0}))
        return rows

    assert engine == "host", f"engine must be host|device, not {engine!r}"
    members = np.arange(n)
    stats = []
    with span("snow.sweep", engine=engine) as sw:
        for seed in seeds:
            bank = bank_for_stable(
                seed, n, protocol, n_messages,
                latency=None if hier is None else hier.latency_model())
            times, rec = broadcast_times(plans, bank, n_messages, rate_s,
                                         backend, loss=loss,
                                         with_receipts=True, hier=hier)
            repaired = None
            if repair is not None:
                times, repaired = _repair_fill(times, t0, members, None,
                                               n, 0, repair)
                miss = repaired
            else:
                miss = np.isnan(times)
                miss[:, 0] = False       # the root always holds the payload
            sub = times[:, 1:] - t0[:, None]
            cnt = (~np.isnan(sub)).sum(axis=1)
            got = cnt > 0
            ldt = np.full(n_messages, np.nan)
            if got.any():
                ldt[got] = np.nanmax(sub[got], axis=1)
            rec_sub = rec[:, 1:].sum(axis=1)
            push_cnt = cnt if repaired is None \
                else cnt - repaired[:, 1:].sum(axis=1)
            n_missed = int(miss.sum())
            extra = {
                "n_repaired": 0 if repaired is None
                else int(repaired.sum()),
                "rebroadcast_B": float(
                    nbytes * int(miss.any(axis=1).sum())),
            }
            if repair is not None:
                extra["repair_B"] = float(
                    repair_digest_epoch_bytes(n, 0, duration,
                                              repair.interval_s)
                    + repair_fetch_bytes(n_missed, payload))
            stats.append((float(np.nanmean(ldt)),
                          float(cnt.mean()) / (n - 1),
                          frame * float(rec_sub.mean()) / (n - 1),
                          frame * float((rec_sub - push_cnt).mean())
                          / (n - 1), extra))
    wall = sw.seconds / max(1, len(seeds))
    return [_finish(seed, i, ldt, rel, rmr, red, wall, extra)
            for i, (seed, (ldt, rel, rmr, red, extra))
            in enumerate(zip(seeds, stats))]


# ------------------------------------------------------------------ #
# Epoch-segmented engine: churn & breakdown in closed form            #
# ------------------------------------------------------------------ #
@dataclass
class _EpochPlan:
    """One epoch's precompiled state: plans, bank rows, blackholing."""

    members: np.ndarray
    rows: np.ndarray                 #: bank row index of every member
    first: int                       #: first message column of the epoch
    times: np.ndarray                #: (m_e,) origination times
    plans: Tuple[TreePlan, ...]
    reach: Tuple[Optional[np.ndarray], ...]   #: per-plan mask; None=all
    nbytes: int                      #: DATA bytes one broadcast moves
    src_index: int
    receipts: np.ndarray = None      #: (n_e,) frame receipts per member
    frame: int = 0                   #: wire size of one DATA frame
    crashed_mask: Optional[np.ndarray] = None  #: (n_e,) bool; None=none
    full: bool = False               #: planned from scratch, not by delta

    @property
    def count(self) -> int:
        return int(self.times.shape[0])


#: boundaries with more effective membership events than this re-plan
#: from scratch — folding E deltas costs E block-copy passes, a full
#: re-plan one expansion, so the crossover sits at a handful of events
_DELTA_MAX_EVENTS = 16


def _rows_delta(rows: np.ndarray, bank_members: np.ndarray,
                ev) -> np.ndarray:
    """Incrementally maintain an epoch's member→bank-row map through one
    membership event — the O(n) memcpy companion of
    :func:`~repro.core.planner.plan_delta` (``rows`` is ascending
    because members and the bank are both id-sorted, so the edit point
    is a binary search, not a full ``searchsorted`` over the view)."""
    if ev.kind == "crash":
        return rows
    b = int(np.searchsorted(bank_members, ev.node))
    p = int(np.searchsorted(rows, b))
    if ev.kind == "join":
        return np.insert(rows, p, b)
    return np.delete(rows, p)


def compile_trace(protocol: str, trace: ChurnTrace, k: int,
                  bank_members: np.ndarray,
                  payload: int = 64,
                  replan: str = "delta") -> List[_EpochPlan]:
    """Segment ``trace`` into epochs and plan each one — everything that
    depends on the trace but NOT on the delay seed, so multi-seed sweeps
    (``trace_sweep``) pay for planning once.

    ``replan="delta"`` (default) derives epoch ``e+1``'s plan set from
    epoch ``e``'s via :func:`~repro.core.planner.plan_delta` — the dirty
    spine is recomputed, every unchanged subtree is block-transferred,
    and crash-only boundaries reuse the previous plan objects outright
    (so their cached ``levels``/``fingerprint`` survive the boundary).
    Bit-identical to ``replan="full"`` (a from-scratch
    :func:`stable_plans` per epoch) by the planner's delta contract;
    boundaries with more than ``_DELTA_MAX_EVENTS`` membership events,
    shrunken degenerate views, or fold/segmentation disagreements fall
    back to the full path per epoch."""
    size = Data(0, 0, None, None, payload).size
    if replan not in ("delta", "full"):
        raise ValueError(f"replan must be 'delta' or 'full', got {replan!r}")
    trans = dict(trace.transitions()) if replan == "delta" else {}
    prev: Optional[_EpochPlan] = None
    out: List[_EpochPlan] = []
    for ep in trace.epochs():
        members = ep.members
        assert int(np.searchsorted(members, trace.src)) < members.shape[0] \
            and members[np.searchsorted(members, trace.src)] == trace.src, \
            "the broadcast source left or was evicted mid-trace"
        plans = rows = None
        full = False
        evs = trans.get(ep.first)
        n_memb = 0 if evs is None else sum(e.kind != "crash" for e in evs)
        if prev is not None and evs is not None \
                and n_memb <= _DELTA_MAX_EVENTS \
                and members.shape[0] > 2 and prev.members.shape[0] > 2:
            try:
                plans = plan_delta_chain(prev.plans, evs)
            except ValueError:     # e.g. the root leaving mid-fold
                plans = None
            if plans is not None \
                    and np.array_equal(plans[0].members, members):
                rows = prev.rows
                for e in evs:
                    rows = _rows_delta(rows, bank_members, e)
            else:                  # fold/segmentation disagreement
                plans = None
        if plans is None:
            plans = stable_plans(protocol, members, trace.src, k)
            rows = np.searchsorted(bank_members, members)
            full = True
        cmask = np.isin(members, ep.crashed) if ep.crashed.size else None
        reach: List[Optional[np.ndarray]] = []
        receipts = np.zeros(members.shape[0], dtype=np.int64)
        for plan in plans:
            covered = np.asarray(plan.depth) >= 1
            if cmask is None:
                reach.append(None)
                receipts += covered
            else:
                ok = reach_mask(plan, cmask)
                reach.append(ok)
                receipts += ok & covered
        out.append(_EpochPlan(
            members=members, rows=rows,
            first=ep.first, times=ep.times, plans=plans,
            reach=tuple(reach), nbytes=size * int(receipts.sum()),
            src_index=int(np.searchsorted(members, trace.src)),
            receipts=receipts, frame=size, crashed_mask=cmask, full=full))
        prev = out[-1]
    return out


def _epoch_times(ep: _EpochPlan, bank: DelayBank,
                 backend: Optional[str],
                 loss: Optional[LossModel] = None,
                 with_receipts: bool = False,
                 hier: Optional[HierarchicalLatency] = None,
                 tier_acc: Optional[np.ndarray] = None):
    """(m_e, n_e) first-delivery times of one epoch's broadcasts: the
    stable closed form over the epoch's plan set, restricted to the
    epoch's bank rows and message columns, with crashed subtrees NaN'd
    out per tree *before* the coloring min (a node unreachable on one
    tree may still be delivered by the other).

    ``loss`` applies the §11 per-edge loss masks (keyed by the epoch's
    absolute bank columns, so the draws match ``Network.send``'s);
    ``with_receipts`` additionally returns the (m_e, n_e) realized
    per-message receipt counts — under loss the precompiled
    ``ep.receipts`` no longer holds, a tree only charges nodes its
    surviving edges reach."""
    # one-shot gather of exactly the (rows × columns) block needed —
    # row-indexing first would copy the full message axis per epoch
    rows = ep.rows[:, None]
    cols = np.arange(ep.first, ep.first + ep.count)
    total = None
    receipts = None
    loss_on = loss is not None and (
        loss.active or (hier is not None and hier.loss_rates is not None))
    for plan, ok in zip(ep.plans, ep.reach):
        s = _slot(plan.tree)
        fwd = np.ascontiguousarray(bank.fwd[rows, cols[None, :], s].T)
        link = np.ascontiguousarray(bank.link[rows, cols[None, :], s].T)
        if hier is not None:
            link = link * hier.scale_plane(plan)[None, :]
        if loss_on:
            rates = None if hier is None else hier.loss_rate_plane(plan)
            link = loss.apply_to_links(link, cols, s, ep.members,
                                       rates=rates)
        t = delivery_times(plan, fwd, link, t0=ep.times, backend=backend)
        if ok is not None:
            t = np.where(ok, t, np.nan)
        if with_receipts or tier_acc is not None:
            r = (~np.isnan(t)) & (np.asarray(plan.depth) >= 1)
            if with_receipts:
                receipts = r.astype(np.int64) if receipts is None \
                    else receipts + r
            if tier_acc is not None:
                tier_acc += np.bincount(
                    hier.tier_plane(plan),
                    weights=r.sum(axis=0).astype(np.float64),
                    minlength=4)[:4]
        total = t if total is None else np.fmin(total, t)
    return (total, receipts) if with_receipts else total


def run_trace_vectorized(protocol: str, trace: ChurnTrace, k: int = 4,
                         seed: int = 0, payload: int = 64,
                         backend: Optional[str] = None,
                         bank: Optional[DelayBank] = None,
                         control: Optional[ControlParams] = None,
                         loss: Optional[LossModel] = None,
                         repair: Optional[RepairModel] = None,
                         *, net: Optional[NetworkSpec] = None,
                         run: Optional[RunSpec] = None) -> VectorCluster:
    """Replay a :class:`ChurnTrace` in closed form: one re-plan and one
    level-synchronous sweep per epoch, all of an epoch's broadcasts
    batched.  Intended sets follow the paper's methodology — the view at
    send time, crashed-but-not-evicted members included — so Reliability
    dips through crash windows and recovers at eviction.

    On boundary-aligned traces this is bit-exact against
    ``scenarios.run_trace_aligned`` (the oracle-membership event loop)
    on the shared :func:`bank_for_trace`; on mid-flight traces (the
    paper cadences) it is the frozen-view-at-origination model the
    differential tests pin statistically.

    ``control`` adds the §9 closed-form control bytes (SWIM +
    anti-entropy integrated per epoch span, one member-update
    announcement per effective trace event) to ``control_summary()``;
    ``None`` accounts nothing, preserving engine-differential parity.

    ``loss``/``repair`` enable the §11 fault and pull-repair closed
    forms: loss darkens subtrees per tree (NaN through the level
    sweep), repair fills alive-but-missed nodes with their first
    digest-tick-plus-fetch time.  Crashed members stay NaN — nothing
    repairs a blackholed node."""
    from .messages import fresh_mid

    assert protocol in ("snow", "coloring"), \
        f"closed-form engine models snow/coloring, not {protocol!r}"
    net, run = resolve_specs(net, run, caller="run_trace_vectorized",
                             backend=backend, control=control,
                             loss=loss, repair=repair)
    if net.locality != "uniform":
        raise NotImplementedError(
            "locality='zone' is stable-scenario only: epoch re-planning "
            "over locality rings is future work (DESIGN.md §12.3)")
    backend = _resolve_backend(run.backend)
    control = run.control
    loss, repair, hier = net.loss, net.repair, net.hier
    if bank is None:
        bank = bank_for_trace(seed, trace, protocol,
                              latency=net.latency_model())
    epochs = compile_trace(protocol, trace, k, bank.members, payload,
                           replan=run.replan)
    metrics = ArrayMetrics(bank.members)
    lossy = net.loss_on
    tier_acc = None if hier is None else np.zeros(4)
    all_plans: List[TreePlan] = []
    n_missed = 0
    for ep in epochs:
        if not lossy and repair is None:
            total = _epoch_times(ep, bank, backend, hier=hier,
                                 tier_acc=tier_acc)
            for j in range(ep.count):
                metrics.record_message(fresh_mid(), float(ep.times[j]),
                                       ep.src_index, total[j], ep.nbytes,
                                       members=ep.members,
                                       receipts=ep.receipts,
                                       frame_bytes=ep.frame)
        else:
            total, rec = _epoch_times(ep, bank, backend, loss=loss,
                                      with_receipts=True, hier=hier,
                                      tier_acc=tier_acc)
            repaired = None
            if repair is not None:
                m_e = ep.members.shape[0]
                c_e = 0 if ep.crashed_mask is None \
                    else int(ep.crashed_mask.sum())
                total, repaired = _repair_fill(
                    total, ep.times, ep.members, ep.crashed_mask,
                    m_e, c_e, repair)
                n_missed += int(repaired.sum())
            for j in range(ep.count):
                metrics.record_message(
                    fresh_mid(), float(ep.times[j]), ep.src_index,
                    total[j], ep.frame * int(rec[j].sum()),
                    members=ep.members, receipts=rec[j],
                    frame_bytes=ep.frame,
                    repaired=None if repaired is None else repaired[j])
        all_plans.extend(ep.plans)
    if tier_acc is not None and epochs:
        frame = epochs[0].frame
        metrics.tier_bytes = [float(frame * v) for v in tier_acc]
    if control is not None:
        params = _repair_control_params(control, repair)
        apply_control(metrics, snow_trace_control(trace, params=params))
        if repair is not None:
            spans = trace.epoch_spans()
            dur = float(spans[-1][1] - spans[0][0]) if spans else 0.0
            c_mean = float(np.mean(
                [0 if ep.crashed_mask is None else int(ep.crashed_mask.sum())
                 for ep in epochs])) if epochs else 0.0
            m_mean = float(np.mean(
                [ep.members.shape[0] for ep in epochs])) if epochs else 0.0
            apply_control(metrics, {"repair": repair_digest_epoch_bytes(
                m_mean, c_mean, dur, repair.interval_s)
                + repair_fetch_bytes(n_missed, payload)})
    return VectorCluster(sim=Sim(seed=seed), net=None, metrics=metrics,
                         nodes={}, fixed=list(range(trace.n)),
                         protocol=protocol, k=k, plans=tuple(all_plans),
                         bank=bank, trace=trace)


def run_churn_vectorized(protocol: str, n: int = 500, k: int = 4,
                         n_messages: int = 100, rate_s: float = 1.0,
                         seed: int = 0, payload: int = 64,
                         churn_every: int = 10,
                         backend: Optional[str] = None,
                         trace: Optional[ChurnTrace] = None,
                         loss: Optional[LossModel] = None,
                         repair: Optional[RepairModel] = None,
                         *, net: Optional[NetworkSpec] = None,
                         run: Optional[RunSpec] = None) -> VectorCluster:
    """§5.4 churn in closed form (paper cadence unless ``trace`` given)."""
    if trace is None:
        trace = paper_churn_trace(n, n_messages, rate_s, churn_every)
    return run_trace_vectorized(protocol, trace, k, seed, payload, backend,
                                loss=loss, repair=repair, net=net, run=run)


def run_breakdown_vectorized(protocol: str, n: int = 500, k: int = 4,
                             n_messages: int = 100, rate_s: float = 1.0,
                             seed: int = 0, payload: int = 64,
                             crash_every: int = 10,
                             detect_after: Optional[float] = 2.5,
                             backend: Optional[str] = None,
                             trace: Optional[ChurnTrace] = None,
                             loss: Optional[LossModel] = None,
                             repair: Optional[RepairModel] = None,
                             *, net: Optional[NetworkSpec] = None,
                             run: Optional[RunSpec] = None) -> VectorCluster:
    """§5.5 breakdown in closed form: silent crashes blackhole subtrees
    until the ``detect_after`` eviction surrogate re-plans them away."""
    if trace is None:
        trace = paper_breakdown_trace(n, n_messages, rate_s, seed,
                                      crash_every, detect_after=detect_after)
    return run_trace_vectorized(protocol, trace, k, seed, payload, backend,
                                loss=loss, repair=repair, net=net, run=run)


# ------------------------------------------------------------------ #
# Stale-view dissemination: divergent views in closed form            #
# ------------------------------------------------------------------ #
def _update_origin(evs):
    """Root and membership of a boundary's MemberUpdate broadcast, per
    §4.5: a joiner announces itself over its freshly-synced (new) view;
    a leaver announces over its current (old) view — it still holds
    itself; an eviction is announced by the detecting node (surrogate:
    the broadcast source).  Returns ``(t, kind, subject)`` of the first
    membership-changing event, or ``None`` for crash-only boundaries
    (silent crashes change no view — there is nothing to adopt)."""
    for ev in evs:
        if ev.kind != "crash":
            return ev.t, ev.kind, ev.node
    return None


def _parents_in_union(plan: Optional[TreePlan], union: np.ndarray
                      ) -> np.ndarray:
    """The plan's parent pointers re-indexed into union-member space;
    -1 where a union member is outside the plan (or is its root)."""
    pu = np.full(union.shape[0], -1, dtype=np.int64)
    if plan is None:
        return pu
    pos = np.searchsorted(union, plan.members)     # members ⊆ union
    par = np.asarray(plan.parent)
    has = par >= 0
    pu[pos[has]] = pos[par[has]]
    return pu


def _mixed_times(par_old: np.ndarray, par_new: np.ndarray, fwd: np.ndarray,
                 link: np.ndarray, adopt: np.ndarray, t0: float, root: int,
                 recv_ok: np.ndarray, fwd_ok: np.ndarray,
                 max_iter: int) -> Tuple[np.ndarray, np.ndarray]:
    """One broadcast under divergent views, closed form.

    Every node forwards once, at ``t[v] + fwd[v]`` (the event loop's
    ``forwarded`` dedup): if its view has not yet adopted the update
    (``adopt[v] > forward time``) it emits the OLD epoch's children,
    otherwise the new epoch's.  A node can therefore be targeted by two
    distinct forwarders — its old-plan parent (stale) and its new-plan
    parent (adopted) — which is exactly how divergent views manufacture
    duplicate deliveries.

    **Orphan rescue.**  In the live protocol every forwarder covers the
    *region* it received, per its own view — regions nest per hop, so a
    node whose would-be new-plan parent is stale (or itself unreached)
    is still covered by whoever owns the enclosing region.  The plan-
    swap approximation restores that invariant by letting the old-plan
    edge fire from an *adopted* parent whenever the child's new-plan
    parent cannot serve it (stale, absent, or unreached); without this,
    one stale forwarder would artificially darken its entire new-plan
    subtree.  Genuine transient misses survive where the protocol has
    them: a joiner whose new-plan parent is still stale has no old-plan
    edge at all.  Iterated to a fixed point (monotone ``fmin``, so it
    terminates); returns ``(times, receipts)`` over union-member space.
    """
    n = fwd.shape[0]
    t = np.full(n, np.nan)
    t[root] = t0
    fwd_eff = fwd.copy()
    fwd_eff[root] = 0.0            # the initiator forwards immediately
    po = np.maximum(par_old, 0)
    pn = np.maximum(par_new, 0)
    vo = np.zeros(n, dtype=bool)
    vn = np.zeros(n, dtype=bool)
    for _ in range(max_iter):
        ft = t + fwd_eff
        with np.errstate(invalid="ignore"):
            stale = adopt > ft
        can = fwd_ok & ~np.isnan(t)
        vn = (par_new >= 0) & can[pn] & ~stale[pn]
        orphan = (par_new < 0) | stale[pn] | np.isnan(t[pn])
        vo = (par_old >= 0) & can[po] & (stale[po] | orphan)
        base = np.where(vo, ft[po], np.inf)
        base = np.minimum(base, np.where(vn, ft[pn], np.inf))
        cand = np.where(recv_ok & np.isfinite(base), base + link, np.nan)
        t_new = np.fmin(t, cand)
        t_new[root] = t0
        if np.array_equal(t_new, t, equal_nan=True):
            break
        t = t_new
    receipts = np.where(recv_ok, vo.astype(np.int64) + vn.astype(np.int64), 0)
    return t, receipts


def run_trace_stale_vectorized(protocol: str, trace: ChurnTrace, k: int = 4,
                               seed: int = 0, payload: int = 64,
                               backend: Optional[str] = None,
                               bank: Optional[DelayBank] = None,
                               epochs: Optional[List[_EpochPlan]] = None,
                               control: Optional[ControlParams] = None,
                               replan: str = "delta") -> VectorCluster:
    """Replay a :class:`ChurnTrace` with **divergent views** in closed
    form — the model behind the paper's §5.4 redundancy claim.

    Per epoch transition, the MemberUpdate is itself swept through the
    closed form (over the announcer's view, §4.5) to get per-node
    **view-adoption times**; broadcasts originating before every node
    has adopted reduce through a mixed plan (:func:`_mixed_times`) —
    stale forwarders emit the old epoch's children, adopters the new
    ones — producing duplicate deliveries, redundant bytes, and
    transient misses.  Once the update has fully propagated the epoch
    falls back to the frozen-view batch sweep.  The per-message
    intended set follows the *initiator's* view: the old members while
    the initiator is still stale, the new members after it adopts.

    Approximations vs the live event loop (statistically pinned in
    ``tests/test_stale_view.py``): stale nodes keep their whole-plan
    children arrays (region boundaries are not re-derived per hop),
    adoption ignores reliable-message retries, and staleness reaches
    back one epoch (windows are clipped at the next boundary).

    ``epochs`` accepts precompiled :func:`compile_trace` output — the
    plans depend only on the trace, so multi-seed sweeps pay for
    whole-tree planning once (mirrors ``trace_sweep``).

    ``control`` adds §9 control bytes to ``control_summary()``.  Unlike
    the oracle engine's expected-value formula, the member-update
    category here is derived from the adoption sweeps this engine
    already runs: each boundary's announcement costs one update frame
    plus one ACK per node its sweep actually reached (times the number
    of effective events at that boundary) — the seed's sampled delays
    decide the reach, not a closed-form mean.
    """
    from .messages import fresh_mid

    assert protocol in ("snow", "coloring"), \
        f"closed-form engine models snow/coloring, not {protocol!r}"
    backend = _resolve_backend(backend)
    trans = dict(trace.transitions())
    if bank is None:
        bank = bank_for_trace(seed, trace, protocol,
                              extra_messages=len(trans))
    eplans = epochs if epochs is not None else \
        compile_trace(protocol, trace, k, bank.members, payload,
                      replan=replan)
    raw = trace.epochs()
    metrics = ArrayMetrics(bank.members)
    src_row = int(np.searchsorted(bank.members, trace.src))
    n_bank = int(bank.members.shape[0])
    update_col = len(trace.msg_times)     # extra bank columns, in order

    def record_pure(ep: _EpochPlan, first_j: int) -> None:
        """Frozen-view batch sweep over the epoch's messages ≥ first_j."""
        if first_j >= ep.count:
            return
        sub = _EpochPlan(members=ep.members, rows=ep.rows,
                         first=ep.first + first_j,
                         times=ep.times[first_j:], plans=ep.plans,
                         reach=ep.reach, nbytes=ep.nbytes,
                         src_index=ep.src_index, receipts=ep.receipts,
                         frame=ep.frame)
        total = _epoch_times(sub, bank, backend)
        for j in range(sub.count):
            metrics.record_message(fresh_mid(), float(sub.times[j]),
                                   sub.src_index, total[j], sub.nbytes,
                                   members=sub.members,
                                   receipts=sub.receipts,
                                   frame_bytes=sub.frame)

    all_plans: List[TreePlan] = []
    mu_bytes = 0.0        # member-update dissemination, from the sweeps
    for i, ep in enumerate(eplans):
        all_plans.extend(ep.plans)
        origin = _update_origin(trans.get(ep.first, ())) if i > 0 else None
        if origin is None:
            record_pure(ep, 0)
            continue
        t_e, kind, subject = origin
        prev = eplans[i - 1]
        if kind == "join":
            aroot, amembers, arows = subject, ep.members, ep.rows
        elif kind == "leave":
            aroot, amembers, arows = subject, prev.members, prev.rows
        else:                                   # evict: detector surrogate
            aroot, amembers, arows = trace.src, ep.members, ep.rows
        # -- adoption sweep: the MemberUpdate broadcast itself ----------
        # an evict announcement is a standard tree over the epoch's view
        # rooted at the detector — structurally the epoch's own snow
        # plan, so reuse it (delta chains keep its levels cache warm)
        if kind == "evict" and ep.plans[0].tree is None:
            aplan = ep.plans[0]
        else:
            aplan = plan_broadcast(amembers, aroot, k)
        a_t = delivery_times(
            aplan, bank.fwd[arows, update_col, 0],
            bank.link[arows, update_col, 0], t0=t_e, backend=backend)
        adopt_rows = np.full(n_bank, t_e)
        adopt_rows[arows] = a_t
        if control is not None:
            reached = int(np.count_nonzero(~np.isnan(a_t))) - 1
            n_evs = sum(1 for ev in trans[ep.first] if ev.kind != "crash")
            mu_bytes += n_evs * max(0, reached) * (UPDATE_FRAME_B + ACK_B)
        for ev in trans[ep.first]:
            if ev.kind == "leave":
                # a leaver never adopts its own removal: it lingers,
                # forwarding over its old view (§4.5.2)
                adopt_rows[np.searchsorted(bank.members, ev.node)] = np.inf
        settle = float(np.nanmax(a_t))
        # -- mixed sweeps for messages inside the staleness window ------
        union = np.union1d(prev.members, ep.members)
        u_rows = np.searchsorted(bank.members, union)
        adopt_u = adopt_rows[u_rows]
        crashed_u = np.isin(union, raw[i].crashed) \
            if raw[i].crashed.size else np.zeros(union.shape[0], dtype=bool)
        recv_ok = ~crashed_u
        old_by_slot = {_slot(p.tree): p for p in prev.plans}
        new_by_slot = {_slot(p.tree): p for p in ep.plans}
        pars = {s: (_parents_in_union(old_by_slot.get(s), union),
                    _parents_in_union(new_by_slot.get(s), union))
                for s in sorted(set(old_by_slot) | set(new_by_slot))}
        max_h = max(p.height for p in prev.plans + ep.plans)
        root_u = int(np.searchsorted(union, trace.src))
        j = 0
        while j < ep.count and float(ep.times[j]) < settle:
            t0 = float(ep.times[j])
            col = ep.first + j
            total = None
            receipts = np.zeros(union.shape[0], dtype=np.int64)
            for s, (par_old, par_new) in pars.items():
                if s >= bank.n_slots:
                    continue
                t_s, r_s = _mixed_times(
                    par_old, par_new, bank.fwd[u_rows, col, s],
                    bank.link[u_rows, col, s], adopt_u, t0, root_u,
                    recv_ok, recv_ok, max_iter=2 * max_h + 8)
                total = t_s if total is None else np.fmin(total, t_s)
                receipts += r_s
            # the intended set is the INITIATOR's view at send time
            msg_members = prev.members if adopt_rows[src_row] > t0 \
                else ep.members
            pos = np.searchsorted(union, msg_members)
            metrics.record_message(
                fresh_mid(), t0,
                int(np.searchsorted(msg_members, trace.src)),
                total[pos], ep.frame * int(receipts.sum()),
                members=msg_members, receipts=receipts[pos],
                frame_bytes=ep.frame)
            j += 1
        record_pure(ep, j)
        update_col += 1
    if control is not None:
        rates = snow_trace_control(trace, params=control)
        rates["member_update"] = mu_bytes      # swept, not expected-value
        apply_control(metrics, rates)
    return VectorCluster(sim=Sim(seed=seed), net=None, metrics=metrics,
                         nodes={}, fixed=list(range(trace.n)),
                         protocol=protocol, k=k, plans=tuple(all_plans),
                         bank=bank, trace=trace, view_model="stale")


def run_churn_stale_vectorized(protocol: str, n: int = 500, k: int = 4,
                               n_messages: int = 100, rate_s: float = 1.0,
                               seed: int = 0, payload: int = 64,
                               churn_every: int = 10,
                               backend: Optional[str] = None,
                               trace: Optional[ChurnTrace] = None
                               ) -> VectorCluster:
    """§5.4 churn under the stale-view model (paper cadence unless
    ``trace`` is given)."""
    if trace is None:
        trace = paper_churn_trace(n, n_messages, rate_s, churn_every)
    return run_trace_stale_vectorized(protocol, trace, k, seed, payload,
                                      backend)


def trace_sweep(protocol: str, trace: ChurnTrace, k: int,
                seeds: Sequence[int], backend: Optional[str] = None,
                payload: int = 64,
                epochs: Optional[List[_EpochPlan]] = None,
                control: Optional[ControlParams] = None,
                engine: Optional[str] = None,
                loss: Optional[LossModel] = None,
                repair: Optional[RepairModel] = None,
                *, net: Optional[NetworkSpec] = None,
                run: Optional[RunSpec] = None) -> List[dict]:
    """Multi-seed churn/breakdown sweep for the scale benchmarks.

    Epoch plans depend only on the trace and are compiled once; each
    seed re-samples its delays and re-sweeps.  Metrics reduce over the
    paper's fixed subset directly on the arrays, using the generator
    invariant that fixed ids are ``< trace.n`` and transients are not.

    ``engine="host"`` materializes one :class:`DelayBank` per seed and
    sweeps epoch by epoch from Python; ``engine="device"`` runs every
    seed × epoch × message through one fused dispatch
    (:func:`repro.core.device_sweep.trace_ldt_device` — counter-based
    delays, ``lax.map`` over padded epochs inside a seed ``vmap``).
    Reach/byte metrics are delay-independent (delays are always finite;
    only crash blackholing produces NaNs), so both engines share the
    same host-computed reliability/RMR values and differ only in the
    LDT statistics (statistically pinned, not bit-equal).

    ``control`` attaches the §9 closed-form per-category control totals
    (seed-independent expected values over the trace) to every row
    under ``control_B``, with the integration window in ``duration_s``.
    The one-time ``plan_s`` compile cost is attributed to the first row
    only, so summed wall-time reports count it once; ``wall_s`` is each
    seed's share of the ``snow.sweep`` span (:mod:`repro.core.spans`).

    ``loss``/``repair`` run the §11 fault + pull-repair closed forms
    (host engine only — the device path's delay-independent byte/reach
    shortcut does not hold once loss darkens edges).  Rows then carry
    three extra keys: ``n_repaired`` (pull-repaired deliveries over the
    whole trace), ``repair_B`` (closed-form repair bytes: digest cadence
    + realized fetches), and ``rebroadcast_B`` (the comparator — one
    full reliable-epoch rebroadcast for every broadcast that missed at
    least one node).  Reliability under repair is over the alive fixed
    subset (crashed members cannot be repaired).
    """
    net, run = resolve_specs(net, run, caller="trace_sweep",
                             engine=engine, backend=backend,
                             control=control, loss=loss, repair=repair)
    if net.locality != "uniform":
        raise NotImplementedError(
            "locality='zone' is stable-scenario only: epoch re-planning "
            "over locality rings is future work (DESIGN.md §12.3)")
    engine = "host" if run.engine == "auto" else run.engine
    backend = _resolve_backend(run.backend)
    control = run.control
    loss, repair, hier = net.loss, net.repair, net.hier
    lossy = net.loss_on
    if (lossy or repair is not None) and engine == "device":
        raise ValueError(
            "loss/repair sweeps require engine='host': the device path's "
            "delay-independent reach shortcut breaks under edge loss")
    if hier is not None and engine == "device":
        raise ValueError(
            "hierarchical trace sweeps require engine='host': the device "
            "trace kernel generates flat-latency delays only")
    plan_s = 0.0
    if epochs is None:
        with span("snow.trace.scan"):
            bank_members = trace.all_ids()
        with span("snow.plan.trees") as sp:
            epochs = compile_trace(protocol, trace, k, bank_members,
                                   payload, replan=run.replan)
            sp.set(epochs=len(epochs), full=sum(ep.full for ep in epochs))
        plan_s = sp.seconds
    ctl = None
    if control:
        with span("snow.control"):
            ctl = snow_trace_control(
                trace, params=_repair_control_params(control, repair))
    with span("snow.trace.scan"):
        spans = trace.epoch_spans()
        trace_duration = float(spans[-1][1] - spans[0][0]) if spans \
            else 0.0
        fixed_sel = [(ep.members < trace.n) & (ep.members != trace.src)
                     for ep in epochs]
    seeds = list(seeds)

    def _finish(seed, i, ldt, rmr, red, rel, wall, extra=None):
        row = {
            "seed": int(seed), "n": trace.n, "k": k,
            "ldt": ldt, "rmr": rmr, "rmr_redundant": red,
            "reliability": rel,
            "n_messages": len(trace.msg_times),
            "n_epochs": len(epochs),
            "wall_s": wall,
            "plan_s": plan_s if i == 0 else 0.0,
            "engine": engine,
        }
        if ctl is not None:
            row["control_B"] = {k_: float(v) for k_, v in ctl.items()}
            row["duration_s"] = trace_duration
        if extra:
            row.update(extra)
            if ctl is not None and "repair_B" in extra:
                row["control_B"]["repair"] = float(extra["repair_B"])
        return row

    if engine == "device":
        from .device_sweep import trace_ldt_device

        # delay-independent per-epoch stats, computed once on the host:
        # a node counts as delivered iff SOME plan covers it and its
        # crash-reach mask lets the frame through
        rmrs: List[float] = []
        rels: List[float] = []
        reds: List[float] = []
        with span("snow.rows"):
            for ep, sel in zip(epochs, fixed_sel):
                n_int = int(sel.sum())
                rec_sub = int(ep.receipts[sel].sum())
                reached = np.zeros(ep.members.shape[0], dtype=bool)
                for plan, ok in zip(ep.plans, ep.reach):
                    covered = np.asarray(plan.depth) >= 1
                    reached |= covered if ok is None else (ok & covered)
                cnt = int(reached[sel].sum())
                rels.extend([cnt / max(1, n_int)] * ep.count)
                rmrs.extend([ep.frame * rec_sub / max(1, n_int)] * ep.count)
                reds.extend([ep.frame * (rec_sub - cnt) / max(1, n_int)]
                            * ep.count)
        with span("snow.sweep", engine=engine) as sw:
            ldt_dev = trace_ldt_device(epochs, trace, seeds)
        wall = sw.seconds / max(1, len(seeds))
        with span("snow.rows"):
            return [_finish(seed, i, float(ldt_dev[i]),
                            float(np.mean(rmrs)), float(np.mean(reds)),
                            float(np.mean(rels)), wall)
                    for i, seed in enumerate(seeds)]

    assert engine == "host", f"engine must be host|device, not {engine!r}"
    faulty = lossy or repair is not None
    stats = []
    with span("snow.sweep", engine=engine) as sw:
        for seed in seeds:
            bank = bank_for_trace(seed, trace, protocol,
                                  latency=net.latency_model())
            ldts: List[np.ndarray] = []
            rels: List[np.ndarray] = []
            rmrs: List[float] = []
            reds: List[np.ndarray] = []
            n_repaired = 0
            n_missed = 0
            rebroadcast_B = 0.0
            for ep, sel in zip(epochs, fixed_sel):
                rec = repaired = None
                if not faulty:
                    total = _epoch_times(ep, bank, backend, hier=hier)
                else:
                    total, rec = _epoch_times(ep, bank, backend, loss=loss,
                                              with_receipts=True, hier=hier)
                    alive = np.ones(ep.members.shape[0], dtype=bool) \
                        if ep.crashed_mask is None else ~ep.crashed_mask
                    if repair is not None:
                        m_e = ep.members.shape[0]
                        c_e = int(np.count_nonzero(~alive))
                        total, repaired = _repair_fill(
                            total, ep.times, ep.members, ep.crashed_mask,
                            m_e, c_e, repair)
                        miss = repaired
                        n_repaired += int(repaired.sum())
                    else:
                        miss = np.isnan(total) & alive[None, :]
                    n_missed += int(miss.sum())
                    rebroadcast_B += float(
                        ep.nbytes * int(miss.any(axis=1).sum()))
                # §11 semantics: with repair on, reliability is over the
                # alive fixed subset — crashed members cannot be repaired
                basis = sel if (repaired is None or ep.crashed_mask is None) \
                    else (sel & ~ep.crashed_mask)
                sub = total[:, basis] - ep.times[:, None]
                cnt = (~np.isnan(sub)).sum(axis=1)
                ldt = np.full(ep.count, np.nan)
                got = cnt > 0
                if got.any():
                    ldt[got] = np.nanmax(sub[got], axis=1)
                n_int = int(basis.sum())
                ldts.append(ldt)
                rels.append(cnt / max(1, n_int))
                # §5.4 subset semantics: bytes attributed to the metered
                # population only — frames received BY subset members — not
                # whole-cluster bytes over the subset denominator
                if rec is None:
                    rec_sub = int(ep.receipts[sel].sum())
                    rmrs.extend([ep.frame * rec_sub / max(1, n_int)]
                                * ep.count)
                    reds.append(ep.frame * (rec_sub - cnt) / max(1, n_int))
                else:
                    rec_sub = rec[:, basis].sum(axis=1)
                    push_cnt = cnt if repaired is None \
                        else cnt - repaired[:, basis].sum(axis=1)
                    rmrs.extend((ep.frame * rec_sub / max(1, n_int)).tolist())
                    reds.append(ep.frame * (rec_sub - push_cnt)
                                / max(1, n_int))
            ldt_all = np.concatenate(ldts)
            rel_all = np.concatenate(rels)
            red_all = np.concatenate(reds)
            extra = None
            if faulty:
                extra = {"n_repaired": n_repaired,
                         "rebroadcast_B": rebroadcast_B}
                if repair is not None:
                    c_mean = float(np.mean(
                        [0 if ep.crashed_mask is None
                         else int(ep.crashed_mask.sum()) for ep in epochs]))
                    m_mean = float(np.mean(
                        [ep.members.shape[0] for ep in epochs]))
                    extra["repair_B"] = float(
                        repair_digest_epoch_bytes(m_mean, c_mean,
                                                  trace_duration,
                                                  repair.interval_s)
                        + repair_fetch_bytes(n_missed, payload))
            stats.append((float(np.nanmean(ldt_all)), float(np.mean(rmrs)),
                          float(red_all.mean()), float(rel_all.mean()),
                          extra))
    wall = sw.seconds / max(1, len(seeds))
    return [_finish(seed, i, ldt, rmr, red, rel, wall, extra)
            for i, (seed, (ldt, rmr, red, rel, extra))
            in enumerate(zip(seeds, stats))]
