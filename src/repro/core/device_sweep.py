"""Device-resident fused sweep engine: counter-based delays, one dispatch.

The host engines (``engine.stable_sweep`` / ``engine.trace_sweep``) loop
over seeds in Python and re-sample a fully materialized
``(ids × messages × slots)`` float64 :class:`~repro.core.engine.DelayBank`
per seed — at n = 10M the per-seed banks and the Python orchestration
dominate.  This module removes both:

* **No bank.**  Every delay draw is regenerated on device from
  counter-mode threefry: one key per ``(seed, slot, draw-tag)`` (a
  ``fold_in`` chain off ``jax.random.key(seed)``), with the counter
  stream indexed by the ``(mid, node)`` grid position — each scalar is
  a pure function of ``(seed, node, mid, slot)`` and the generation is
  ~1 hash per 2 draws, so delays are cheaper to regenerate than to
  load.  Trace epochs gather their ``(columns × bank rows)`` window out
  of the same conceptual plane the stable path generates directly, so
  the two paths draw from one coordinate system.
* **One dispatch.**  Only the draws are ``vmap``-ed across seeds; each
  plan's level sweep then runs once over all seeds × messages, laid out
  node-major (``repro.kernels.tree_sweep.level_sweep_rows``), and churn
  traces ``lax.map`` over padded epochs with every seed inside each
  epoch's sweep, so a whole multi-seed cell is a single jitted call.

The numpy :class:`DelayBank` stays the bit-exactness oracle: the device
path draws from the *same distributions* (uniform 10–200 ms forwarding,
lognormal sub-ms links, 5% stragglers pinned at 1 s over the fixed ids)
but with a different RNG stream, float32 device math, and per-node
Bernoulli stragglers instead of the host's exact-count sample, so it is
*statistically* pinned against the host rows (mean/p99 LDT tolerances
in ``tests/test_device_sweep.py``), never bit-equal.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels.tree_sweep import (fwd_at_parent, fwd_at_parent_rows,
                                  level_sweep_rows, level_sweep_xla)
from .planner import SECONDARY, TreePlan
from .sim import LatencyModel
from .spans import span

# draw tags — the last fold_in of the key chain picks the variate
_TAG_FWD, _TAG_LINK, _TAG_STRAGGLER, _TAG_LOSS = 0, 1, 2, 3

# §5.2 distribution parameters, identical to DelayBank.sample defaults
_LAT = LatencyModel()
FWD_LO, FWD_HI = 0.010, 0.200
STRAGGLER_FRAC = 0.05
STRAGGLER_DELAY = 1.0


def _plan_slot(plan: TreePlan) -> int:
    return 1 if plan.tree == SECONDARY else 0


def _plan_meta(plans: Sequence[TreePlan]) -> Tuple[Tuple[int, int, int], ...]:
    """Static (root, height, slot) per plan — the jit cache key."""
    return tuple((int(p.root), int(np.asarray(p.depth).max()), _plan_slot(p))
                 for p in plans)


# ------------------------------------------------------------------ #
# Counter-based delay generation                                      #
# ------------------------------------------------------------------ #
# Each stage of a device program carries a named scope in its ops'
# metadata, so a profiler trace can split a program's time by stage:
# ``delay_planes`` (here), ``epoch_gather`` (a trace epoch's window),
# ``level_sweep`` (kernels/tree_sweep.py) and ``ldt_reduce``.
@jax.named_scope("delay_planes")
def _straggler_mask(base, fixed_mask, frac=STRAGGLER_FRAC):
    """(n,) bool — per-node Bernoulli(``frac``) over the fixed ids.  The
    host oracle draws an *exact-count* sample (``straggler_sample``);
    the Bernoulli count concentrates around the same mean, which is
    what the statistical pins absorb."""
    ks = jax.random.fold_in(base, _TAG_STRAGGLER)
    u = jax.random.uniform(ks, fixed_mask.shape)
    return (u < frac) & fixed_mask


@jax.named_scope("delay_planes")
def _fwd_link_planes(base, slot, m, n, strag):
    """``(m, n)`` forwarding/link delay planes for one tree slot,
    regenerated from counters: key = ``(seed → slot → tag)``, counter =
    the ``(mid, node)`` grid position.  ``strag`` pins straggler rows at
    :data:`STRAGGLER_DELAY` on every slot and column, like
    ``DelayBank.sample``."""
    kf = jax.random.fold_in(jax.random.fold_in(base, slot), _TAG_FWD)
    kl = jax.random.fold_in(jax.random.fold_in(base, slot), _TAG_LINK)
    uf = jax.random.uniform(kf, (m, n), minval=FWD_LO, maxval=FWD_HI)
    fwd = jnp.where(strag[None, :], STRAGGLER_DELAY, uf)
    link = _LAT.median_s * jnp.exp(_LAT.sigma
                                   * jax.random.normal(kl, (m, n)))
    return fwd, link


@jax.named_scope("delay_planes")
def _loss_planes(base, slot, m, n, rate, timeout_s, max_attempts):
    """(m, n) retransmit-extra delays and lost masks — the device twin
    of ``LossModel.edge_faults``.  Same protocol (Bernoulli per attempt,
    ``extra = failures × timeout``, dead after ``max_attempts``), but
    threefry draws instead of the host's splitmix64 counter hash, so
    device-under-loss rows pin statistically against host rows, never
    bit-equal — exactly like the delay planes themselves."""
    kl = jax.random.fold_in(jax.random.fold_in(base, slot), _TAG_LOSS)
    u = jax.random.uniform(kl, (max_attempts, m, n))
    ok = u >= rate
    lost = ~ok.any(axis=0)
    failures = jnp.where(lost, max_attempts, jnp.argmax(ok, axis=0))
    extra = timeout_s * failures.astype(jnp.float32)
    return extra, lost


# ------------------------------------------------------------------ #
# Node-major layout: all seeds × messages of a plan as one row a node #
# ------------------------------------------------------------------ #
#: TPU lane count: a node's row of delivery times is padded to a multiple
_LANES = 128


def _row_width(cols: int) -> int:
    """Padded row width ``B`` of ``cols`` seed × message columns."""
    return -(-cols // _LANES) * _LANES


@jax.named_scope("level_sweep")
def _pad_cols(x, width, fill):
    """Pad the trailing (column) axis of ``x`` to ``width`` with
    ``fill``; the padding columns are inert (NaN link, NaN ``t0``)."""
    pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return jnp.pad(x, pad, constant_values=fill)


@jax.named_scope("level_sweep")
def _to_rows(x, width, fill):
    """``(S, M, n)`` seed × message planes → node-major ``(n, width)``:
    column ``s·M + m`` holds message ``m`` of seed ``s``."""
    s, m, n = x.shape
    return _pad_cols(jnp.transpose(x, (2, 0, 1)).reshape(n, s * m), width,
                     fill)


def _per_seed(cols, s, m):
    """``(B,)`` per-column values → ``(S, M)``, padding dropped."""
    return cols[:s * m].reshape(s, m)


def _sum_last(x):
    """Sum over the last axis, left to right.  Elementwise adds fix the
    order, which a reduce fused into the reduction over nodes leaves to
    the compiler: per-seed means are then the same bits however the
    per-message values were laid out."""
    return functools.reduce(jnp.add, [x[..., j] for j in range(x.shape[-1])])


def _sweep_rows(parent, depth, fwd, link, t0, *, root, height):
    """(n, B) times of one plan over node-major ``fwd``/``link`` rows."""
    fp = fwd_at_parent_rows(parent, fwd, root)
    return level_sweep_rows(parent, depth, fp, link, t0, root=root,
                            height=height)


# ------------------------------------------------------------------ #
# Stable scenario: draws vmap over seeds, one node-major sweep a plan #
# ------------------------------------------------------------------ #
def _stable_sweeps(seeds, parents, depths, rate_s, straggler_frac, *,
                   meta, n_messages, n_fixed, adjust=None):
    """Per plan, the ``(n, B)`` delivery times of every seed × message,
    and the ``(B,)`` start time of each column.  Only the draws are
    ``vmap``-ed over seeds, on their ``(messages, n)`` counter grid;
    each plan then runs one node-major sweep over all seeds at once.
    ``adjust(i, base, slot, link)`` rewrites plan ``i``'s link plane of
    one seed (tier scales, loss)."""
    n = parents[0].shape[0]
    s = seeds.shape[0]
    width = _row_width(s * n_messages)
    ids = jnp.arange(n, dtype=jnp.int32)
    t0 = (jnp.arange(n_messages) * rate_s).astype(jnp.float32)

    def draws(seed):
        base = jax.random.key(seed)
        strag = _straggler_mask(base, ids < n_fixed, straggler_frac)
        out = []
        for i, (_, _, slot) in enumerate(meta):
            fwd, link = _fwd_link_planes(base, slot, n_messages, n, strag)
            if adjust is not None:
                link = adjust(i, base, slot, link)
            out.append((fwd, link))
        return out

    t0_rows = _pad_cols(jnp.tile(t0, s), width, jnp.nan)
    times = [_sweep_rows(parent, depth, _to_rows(fwd, width, 0.0),
                         _to_rows(link, width, jnp.nan), t0_rows,
                         root=root, height=height)
             for (fwd, link), parent, depth, (root, height, _)
             in zip(jax.vmap(draws)(seeds), parents, depths, meta)]
    return times, t0_rows


def _stable_reduce(times, t0_rows, root0, s, m):
    """Per-seed (mean LDT, mean reliability) of the coloring min."""
    total = functools.reduce(jnp.fmin, times)
    with jax.named_scope("ldt_reduce"):
        n = total.shape[0]
        valid = ((jnp.arange(n, dtype=jnp.int32) != root0)[:, None]
                 & ~jnp.isnan(total))
        ldt = jnp.max(jnp.where(valid, total - t0_rows, -jnp.inf), axis=0)
        rel = valid.sum(axis=0) / (n - 1)
        return (_sum_last(_per_seed(ldt, s, m)) / m,
                _sum_last(_per_seed(rel, s, m)) / m)


@functools.partial(jax.jit,
                   static_argnames=("meta", "n_messages", "n_fixed"))
def _stable_stats(seeds, parents, depths, rate_s, straggler_frac, *,
                  meta, n_messages, n_fixed):
    times, t0_rows = _stable_sweeps(
        seeds, parents, depths, rate_s, straggler_frac, meta=meta,
        n_messages=n_messages, n_fixed=n_fixed)
    return _stable_reduce(times, t0_rows, meta[0][0], seeds.shape[0],
                          n_messages)


@functools.partial(jax.jit,
                   static_argnames=("meta", "n_messages", "n_fixed"))
def _stable_stats_hier(seeds, parents, depths, scales, rate_s,
                       straggler_frac, *, meta, n_messages, n_fixed):
    """:func:`_stable_stats` with each plan's link plane multiplied by
    its per-node tier scale after the threefry generation."""
    times, t0_rows = _stable_sweeps(
        seeds, parents, depths, rate_s, straggler_frac, meta=meta,
        n_messages=n_messages, n_fixed=n_fixed,
        adjust=lambda i, base, slot, link: link * scales[i][None, :])
    return _stable_reduce(times, t0_rows, meta[0][0], seeds.shape[0],
                          n_messages)


def stable_stats_device(plans: Sequence[TreePlan], seeds: Sequence[int],
                        n_messages: int, rate_s: float = 1.0,
                        straggler_frac: float = STRAGGLER_FRAC,
                        hier=None) -> Tuple[np.ndarray, np.ndarray]:
    """Per-seed ``(mean LDT, mean reliability)`` of a stable multi-seed
    sweep, all seeds × messages × trees fused into one device dispatch.
    The jit cache key is ``(plan shapes, (root, height, slot) tuple,
    n_messages, seed count)`` — re-running with the same shapes reuses
    the compilation.

    ``hier`` (a :class:`~repro.core.topology.HierarchicalLatency`)
    multiplies each plan's link plane by its per-node tier factor
    (``hier.scale_plane``, computed host-side — integer coordinate
    hashing — and fused into the device program as one broadcast
    multiply after the threefry link generation)."""
    with span("snow.device.pack"):
        host = [np.asarray(list(seeds), dtype=np.uint32),
                tuple(np.asarray(p.parent, dtype=np.int32) for p in plans),
                tuple(np.asarray(p.depth, dtype=np.int32) for p in plans)]
        if hier is not None:
            host.append(tuple(hier.scale_plane(p).astype(np.float32)
                              for p in plans))
        host += [float(rate_s), float(straggler_frac)]
        kw = dict(meta=_plan_meta(plans), n_messages=int(n_messages),
                  n_fixed=int(host[1][0].shape[0]))
    program = _stable_stats if hier is None else _stable_stats_hier
    return _run_program(program, host, kw,
                        cols=len(host[0]) * int(n_messages))


def _run_program(program, host, static, *, cols):
    """One device program on host arrays, each step a span of its own:
    upload, dispatch (the jitted call until it returns: a compile on a
    cache miss shows here) and the pull of the result to the host.
    ``cols`` is the seed × message columns of the program's node-major
    sweep: the dispatch records the padded row width and its used
    share."""
    leaves = jax.tree.leaves(host)
    with span("snow.device.upload",
              bytes=int(sum(np.asarray(a).nbytes for a in leaves))):
        args = jax.tree.map(jnp.asarray, host)
    width = _row_width(cols)
    with span("snow.device.dispatch", program=program.__name__,
              row_width=width, row_fill=cols / width):
        out = program(*args, **static)
    with span("snow.device.pull"):
        return jax.tree.map(np.asarray, out)


@functools.partial(jax.jit,
                   static_argnames=("meta", "n_messages", "n_fixed",
                                    "max_attempts"))
def _stable_stats_loss(seeds, parents, depths, rate_s, straggler_frac,
                       loss_rate, loss_timeout, *, meta, n_messages,
                       n_fixed, max_attempts):
    def lossy(i, base, slot, link):
        extra, lost = _loss_planes(base, slot, n_messages, link.shape[1],
                                   loss_rate, loss_timeout, max_attempts)
        return jnp.where(lost, jnp.nan, link + extra)

    times, t0_rows = _stable_sweeps(
        seeds, parents, depths, rate_s, straggler_frac, meta=meta,
        n_messages=n_messages, n_fixed=n_fixed, adjust=lossy)
    s, root0 = seeds.shape[0], meta[0][0]
    receipts = sum(((~jnp.isnan(t)) & (depth >= 1)[:, None])
                   .astype(jnp.int32) for t, depth in zip(times, depths))
    total = functools.reduce(jnp.fmin, times)
    with jax.named_scope("ldt_reduce"):
        n = total.shape[0]
        valid = ((jnp.arange(n, dtype=jnp.int32) != root0)[:, None]
                 & ~jnp.isnan(total))
        got = _per_seed(valid.any(axis=0), s, n_messages)
        ldt = _per_seed(jnp.max(jnp.where(valid, total - t0_rows, -jnp.inf),
                                axis=0), s, n_messages)
        ldt_mean = (_sum_last(jnp.where(got, ldt, 0.0))
                    / jnp.maximum(got.sum(axis=1), 1))
        rel = _per_seed(valid.sum(axis=0) / (n - 1), s, n_messages)
        rec = _per_seed(receipts.sum(axis=0), s, n_messages)
        return (ldt_mean, _sum_last(rel) / n_messages,
                _sum_last(rec.astype(jnp.float32)) / n_messages)


def stable_stats_device_loss(plans: Sequence[TreePlan],
                             seeds: Sequence[int], n_messages: int,
                             rate_s: float = 1.0, *, loss,
                             straggler_frac: float = STRAGGLER_FRAC
                             ) -> Tuple[np.ndarray, np.ndarray,
                                        np.ndarray]:
    """Per-seed ``(mean LDT, mean reliability, mean DATA receipts per
    message)`` of a stable sweep under §11 device-RNG edge loss.  A
    separate entry point so the lossless :func:`stable_stats_device`
    keeps its pinned outputs and jit cache untouched."""
    with span("snow.device.pack"):
        host = (np.asarray(list(seeds), dtype=np.uint32),
                tuple(np.asarray(p.parent, dtype=np.int32) for p in plans),
                tuple(np.asarray(p.depth, dtype=np.int32) for p in plans),
                float(rate_s), float(straggler_frac), float(loss.rate),
                float(loss.timeout_s))
        kw = dict(meta=_plan_meta(plans), n_messages=int(n_messages),
                  n_fixed=int(host[1][0].shape[0]),
                  max_attempts=int(loss.max_attempts))
    return _run_program(_stable_stats_loss, host, kw,
                        cols=len(host[0]) * int(n_messages))


@functools.partial(jax.jit,
                   static_argnames=("meta", "n_messages", "n_fixed", "impl"))
def _stable_times(seed, parents, depths, rate_s, straggler_frac, *,
                  meta, n_messages, n_fixed, impl):
    from ..kernels.ops import tree_sweep

    n = parents[0].shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)
    t0 = jnp.arange(n_messages) * rate_s
    base = jax.random.key(seed)
    strag = _straggler_mask(base, ids < n_fixed, straggler_frac)
    total = None
    for parent, depth, (root, height, slot) in zip(parents, depths, meta):
        fwd, link = _fwd_link_planes(base, slot, n_messages, n, strag)
        fp = fwd_at_parent(parent, fwd, root)
        t = tree_sweep(parent, depth, fp, link, t0.astype(fwd.dtype),
                       root=root, height=height, impl=impl)
        total = t if total is None else jnp.fmin(total, t)
    return total


def stable_times_device(plans: Sequence[TreePlan], seed: int,
                        n_messages: int, rate_s: float = 1.0,
                        impl: str = "xla",
                        straggler_frac: float = STRAGGLER_FRAC
                        ) -> np.ndarray:
    """(M, n) absolute first-delivery times of one device-RNG stable
    sweep — the single-seed debug/pinning view of
    :func:`stable_stats_device` (identical draws: both run the same
    counter chain).  ``impl`` routes the sweep through
    :func:`repro.kernels.ops.tree_sweep`, so ``"pallas_interpret"``
    exercises the Pallas kernel on the same generated delays as
    ``"xla"`` — the pair is bit-equal."""
    out = _stable_times(
        jnp.uint32(int(seed) & 0xFFFFFFFF),
        tuple(jnp.asarray(np.asarray(p.parent, dtype=np.int32))
              for p in plans),
        tuple(jnp.asarray(np.asarray(p.depth, dtype=np.int32))
              for p in plans),
        jnp.asarray(float(rate_s)), jnp.asarray(float(straggler_frac)),
        meta=_plan_meta(plans), n_messages=int(n_messages),
        n_fixed=int(np.asarray(plans[0].parent).shape[0]), impl=impl)
    return np.asarray(out)


# ------------------------------------------------------------------ #
# Churn traces: lax.map over padded epochs, all seeds in each sweep   #
# ------------------------------------------------------------------ #
@jax.named_scope("epoch_gather")
def _epoch_window(bank, slot, rows, col0, q, s):
    """``(P, S·q)`` window of a node-major ``(slots, n_bank, S·M)`` bank:
    the epoch's member rows of one slot, then each seed's ``q`` messages
    from ``col0``, those past the last message clipped to it (the edge
    padding)."""
    w = bank[slot, rows]                                   # row gather
    p = w.shape[0]
    w = jnp.pad(w.reshape(p, s, -1), ((0, 0), (0, 0), (0, q - 1)),
                mode="edge")
    return lax.dynamic_slice_in_dim(w, col0, q, axis=2).reshape(p, s * q)


@functools.partial(jax.jit,
                   static_argnames=("q", "height", "maxp", "n_slots",
                                    "m_total"))
def _trace_ldt(seeds, st, fixed_mask, *, q, height, maxp, n_slots,
               m_total):
    n_bank = fixed_mask.shape[0]
    s = seeds.shape[0]
    width = _row_width(s * q)

    def draws(seed):
        base = jax.random.key(seed)
        strag = _straggler_mask(base, fixed_mask)
        planes = [_fwd_link_planes(base, sl, m_total, n_bank, strag)
                  for sl in range(n_slots)]
        return ([p[0] for p in planes], [p[1] for p in planes])

    fwd, link = jax.vmap(draws)(seeds)
    # XLA fuses the draws into the node-major stacking: scoped with them
    with jax.named_scope("delay_planes"):
        def bank(planes):                     # (slots, n_bank, S·M)
            return jnp.stack([jnp.transpose(x, (2, 0, 1))
                              .reshape(n_bank, s * m_total)
                              for x in planes])
        fwd_all, link_all = bank(fwd), bank(link)

    def ep_fn(e):
        times = e["times"].astype(jnp.float32)
        t0_rows = _pad_cols(jnp.tile(times, s), width, jnp.nan)
        total = None
        for p in range(maxp):
            sl = e["slot"][p]
            fwd = _epoch_window(fwd_all, sl, e["rows"], e["col0"], q, s)
            link = _epoch_window(link_all, sl, e["rows"], e["col0"], q, s)
            t = _sweep_rows(e["parent"][p], e["depth"][p],
                            _pad_cols(fwd, width, 0.0),
                            _pad_cols(link, width, jnp.nan), t0_rows,
                            root=e["root"], height=height)
            t = jnp.where(e["mask"][p][:, None], t, jnp.nan)
            total = t if total is None else jnp.fmin(total, t)
        with jax.named_scope("ldt_reduce"):
            valid = e["sel"][:, None] & ~jnp.isnan(total)
            ldt = _per_seed(jnp.max(jnp.where(valid, total - t0_rows,
                                              -jnp.inf), axis=0), s, q)
            ok = e["msgmask"][None, :] & _per_seed(valid.any(axis=0), s, q)
            return ldt, ok

    ldt, ok = lax.map(ep_fn, st)                          # (E, S, q)
    with jax.named_scope("ldt_reduce"):
        # each epoch's sum over its messages, then the sum over epochs
        sums = _sum_last(jnp.moveaxis(_sum_last(jnp.where(ok, ldt, 0.0)),
                                      0, -1))
        c = ok.sum(axis=(0, 2))
        return jnp.where(c > 0, sums / jnp.maximum(c, 1), jnp.nan)


def _stack_epochs(epochs) -> Tuple[dict, int, int, int]:
    """Pad a ``compile_trace`` epoch list into rectangular device
    arrays.  Padding is inert by construction: padded members carry
    ``depth = -1`` (no level ever matches → times stay NaN) and
    ``sel/mask/msgmask = False``; dummy plan slots (epochs with fewer
    trees than ``maxp``) keep an all-False mask, so their sweep output
    is discarded before the coloring min."""
    pmax = max(int(ep.members.shape[0]) for ep in epochs)
    q = max(ep.count for ep in epochs)
    maxp = max(len(ep.plans) for ep in epochs)
    e = len(epochs)
    st = {
        "rows": np.zeros((e, pmax), dtype=np.int32),
        "col0": np.zeros(e, dtype=np.int32),
        "times": np.zeros((e, q), dtype=np.float64),
        "msgmask": np.zeros((e, q), dtype=bool),
        "root": np.zeros(e, dtype=np.int32),
        "sel": np.zeros((e, pmax), dtype=bool),
        "parent": np.zeros((e, maxp, pmax), dtype=np.int32),
        "depth": np.full((e, maxp, pmax), -1, dtype=np.int32),
        "mask": np.zeros((e, maxp, pmax), dtype=bool),
        "slot": np.zeros((e, maxp), dtype=np.int32),
    }
    height = 0
    for i, ep in enumerate(epochs):
        ne = int(ep.members.shape[0])
        st["rows"][i, :ne] = ep.rows
        st["col0"][i] = ep.first
        st["times"][i, :ep.count] = ep.times
        st["msgmask"][i, :ep.count] = True
        st["root"][i] = ep.src_index
        for p, (plan, ok) in enumerate(zip(ep.plans, ep.reach)):
            st["parent"][i, p, :ne] = np.asarray(plan.parent)
            st["depth"][i, p, :ne] = np.asarray(plan.depth)
            st["mask"][i, p, :ne] = True if ok is None else ok
            st["slot"][i, p] = _plan_slot(plan)
            height = max(height, int(np.asarray(plan.depth).max()))
    return st, q, maxp, height


def trace_ldt_args(epochs, trace, seeds: Sequence[int]) -> Tuple[tuple, dict]:
    """The host arrays and static arguments of :func:`_trace_ldt` for
    one trace: ``(seeds, stacked epochs, fixed mask)`` and
    ``(q, height, maxp, n_slots, m_total)``."""
    st, q, maxp, height = _stack_epochs(epochs)
    for i, ep in enumerate(epochs):
        sel = (ep.members < trace.n) & (ep.members != trace.src)
        st["sel"][i, :ep.members.shape[0]] = sel
    args = (np.asarray(list(seeds), dtype=np.uint32), st,
            trace.all_ids() < trace.n)
    static = dict(q=q, height=height, maxp=maxp,
                  n_slots=int(st["slot"].max()) + 1,
                  m_total=len(trace.msg_times))
    return args, static


def trace_ldt_device(epochs, trace, seeds: Sequence[int]) -> np.ndarray:
    """Per-seed mean LDT over the paper's fixed subset for a whole churn
    trace — every seed × epoch × message in one fused dispatch.  The
    delay-independent metrics (reliability, RMR) are the caller's job
    (``trace_sweep`` computes them once on the host); only the LDT
    reduction needs the delays."""
    with span("snow.device.pack"):
        args, static = trace_ldt_args(epochs, trace, seeds)
    return _run_program(_trace_ldt, args, static,
                        cols=len(args[0]) * static["q"])


# ------------------------------------------------------------------ #
# Workload engine: per-publisher group sweep with a queue plane        #
# ------------------------------------------------------------------ #
@functools.partial(jax.jit, static_argnames=("meta",))
def _workload_times(seed, gidx, parent, depth, qadd, t0, straggler_frac,
                    *, meta):
    """One publisher-group: regenerate the group's delay planes from
    counters keyed by ``(seed → group)``, fuse the host-computed §14.2
    queue plane into the link plane (the device twin of the host path's
    ``link + q``), and run one level sweep with the group's publish
    times as ``t0`` — a separate jitted entry so the stable/trace
    programs keep their compiled caches untouched."""
    root, height, slot = meta
    n = parent.shape[0]
    m = t0.shape[0]
    base = jax.random.fold_in(jax.random.key(seed), gidx)
    strag = _straggler_mask(base, jnp.ones((n,), dtype=bool),
                            straggler_frac)
    fwd, link = _fwd_link_planes(base, slot, m, n, strag)
    link = link + qadd
    fp = fwd_at_parent(parent, fwd, root)
    return level_sweep_xla(parent, depth, fp, link, t0.astype(fwd.dtype),
                           root=root, height=height)


def workload_times_device(plan, seed: int, group_index: int, t0,
                          qadd=None,
                          straggler_frac: float = STRAGGLER_FRAC
                          ) -> np.ndarray:
    """(m, n) absolute delivery times for one workload publisher-group
    over ``plan`` — the bank-free device arm of
    :func:`repro.core.workload.run_workload_vectorized`.  Threefry
    draws replace the host bank (no (n, M) arrays in memory at n = 1M),
    so rows pin statistically against the host oracle, never bit-equal
    — exactly like the stable device sweep.  ``qadd`` is the
    host-computed (m, n) queue plane (``None`` = uncapped)."""
    parr = np.asarray(plan.parent, dtype=np.int32)
    darr = np.asarray(plan.depth, dtype=np.int32)
    n = int(parr.shape[0])
    m = int(np.asarray(t0).shape[0])
    q = np.zeros((m, n), dtype=np.float32) if qadd is None \
        else np.asarray(qadd, dtype=np.float32)
    meta = (int(plan.root), int(darr.max()), _plan_slot(plan))
    out = _workload_times(
        jnp.asarray(np.uint32(seed)), jnp.asarray(np.int32(group_index)),
        jnp.asarray(parr), jnp.asarray(darr), jnp.asarray(q),
        jnp.asarray(np.asarray(t0, dtype=np.float32)),
        jnp.asarray(float(straggler_frac)), meta=meta)
    return np.asarray(out)
