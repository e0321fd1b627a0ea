"""Level-synchronous tree sweep — Pallas kernel + XLA reference.

Snow's closed-form delivery model (``repro.core.engine``) reduces every
first-delivery time to ``t[v] = (t[parent] + fwd[parent]) + link[v]``
applied level by level down a :class:`~repro.core.planner.TreePlan`.
This module is the device expression of that sweep, shared by the
device-resident sweep engine (``repro.core.device_sweep``):

* :func:`level_sweep_rows` (with :func:`fwd_at_parent_rows`) — the
  node-major fast path that the multi-seed programs run: planes are
  ``(n, B)``, all seeds × messages of one plan in a node's row (``B``
  padded to a multiple of 128 lanes), so a level's ``t[parent]``
  gathers whole contiguous rows.  Bit-equal to the reference on the
  transposed planes.
* :func:`level_sweep_xla` — the reference: a ``lax.fori_loop`` over
  levels, each step one fused gather-add-where over all n nodes of
  ``(..., n)`` planes, gathering single lanes of the minor axis.  The
  single-seed entries and the Pallas kernel's bit-equality tests use it.
* :func:`tree_sweep_pallas` — the Pallas kernel, following the
  ``flash_attention.py`` tiling idiom: grid = (message blocks, level);
  the level axis is the trailing (sequential) grid dimension, so the
  output tile for one message block stays resident in VMEM across all
  levels, with the ``TreePlan.parent``/``depth`` arrays held alongside
  it and re-gathered per level.  Block budget: one (block_m, n) fp32
  time tile plus the (block_m, n) fp/link tiles and two (n,) int32 plan
  arrays — ~``12·block_m·n`` bytes, so n up to ~10⁵ per tile fits the
  16 MB/core VMEM envelope at the default ``block_m``; larger n belongs
  to the XLA path (``impl="xla"``), which :mod:`repro.kernels.ops`
  selects automatically off-TPU.

Both paths compute the *identical* float program — same op sequence,
same ``(t[parent] + fp) + link`` grouping, same NaN-init/where masking
— so interpret-mode Pallas output is bit-equal to the XLA sweep on the
same inputs (asserted in ``tests/test_device_sweep.py``).  ``fp`` is
the forwarding delay *pre-gathered at the parent* with the root's
contribution zeroed (``fwd_at_parent``): the gather that varies per
level is the one over ``t``, which is what the kernel keeps in VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_M = 8


@jax.named_scope("level_sweep")
def fwd_at_parent(parent: jax.Array, fwd: jax.Array, root: int) -> jax.Array:
    """``fwd`` gathered at each node's parent, zero where the parent is
    the root (the initiator forwards immediately) — the per-message
    ``fp`` operand both sweep implementations consume."""
    return jnp.where(parent == root, 0.0,
                     jnp.take(fwd, parent, axis=-1))


@jax.named_scope("level_sweep")
def level_sweep_xla(parent: jax.Array, depth: jax.Array, fp: jax.Array,
                    link: jax.Array, t0: jax.Array, *, root: int,
                    height: int) -> jax.Array:
    """(..., n) absolute first-delivery times, XLA reference sweep.

    ``fp``/``link`` are ``(..., n)`` (leading message batch dims), ``t0``
    broadcasts into the leading dims.  Every level is one fused
    gather-add-where over all n nodes; NaN marks unreached nodes
    (``depth`` outside ``1..height``, e.g. -1 for non-members).
    """
    t = jnp.full(jnp.broadcast_shapes(fp.shape, link.shape), jnp.nan,
                 dtype=fp.dtype)
    t = t.at[..., root].set(t0)

    def body(h, t):
        cand = (jnp.take(t, parent, axis=-1) + fp) + link
        return jnp.where(depth == h, cand, t)

    return lax.fori_loop(1, height + 1, body, t)


@jax.named_scope("level_sweep")
def fwd_at_parent_rows(parent: jax.Array, fwd: jax.Array,
                       root: int) -> jax.Array:
    """:func:`fwd_at_parent` on a node-major ``(n, B)`` plane: each node
    gathers its parent's whole row."""
    return jnp.where((parent == root)[:, None], 0.0,
                     jnp.take(fwd, parent, axis=0))


@jax.named_scope("level_sweep")
def level_sweep_rows(parent: jax.Array, depth: jax.Array, fp: jax.Array,
                     link: jax.Array, t0: jax.Array, *, root: int,
                     height: int) -> jax.Array:
    """(n, B) absolute first-delivery times, node-major fast path.

    :func:`level_sweep_xla` with the node axis leading: ``fp``/``link``
    are ``(n, B)``, every column an independent sweep (all seeds ×
    messages of one plan), ``t0`` is ``(B,)``.  A level's ``t[parent]``
    gathers whole contiguous rows instead of single lanes; the float
    program is the same, so the result is bit-equal to
    :func:`level_sweep_xla` on the transposed planes.
    """
    t = jnp.full(jnp.broadcast_shapes(fp.shape, link.shape), jnp.nan,
                 dtype=fp.dtype)
    t = t.at[root].set(t0)
    at_level = depth[:, None]

    def body(h, t):
        cand = (jnp.take(t, parent, axis=0) + fp) + link
        return jnp.where(at_level == h, cand, t)

    return lax.fori_loop(1, height + 1, body, t)


def _sweep_kernel(parent_ref, depth_ref, fp_ref, link_ref, t0_ref, out_ref,
                  *, root: int):
    h = pl.program_id(1)            # level axis — sequential on TPU

    @pl.when(h == 0)
    def _init():
        t = jnp.full(out_ref.shape, jnp.nan, dtype=out_ref.dtype)
        out_ref[...] = t.at[:, root].set(t0_ref[:, 0])

    @pl.when(h > 0)
    def _step():
        t = out_ref[...]                         # (block_m, n), resident
        cand = (jnp.take(t, parent_ref[...], axis=-1) + fp_ref[...]) \
            + link_ref[...]
        out_ref[...] = jnp.where(depth_ref[...][None, :] == h, cand, t)


def tree_sweep_pallas(parent: jax.Array, depth: jax.Array, fp: jax.Array,
                      link: jax.Array, t0: jax.Array, *, root: int,
                      height: int, block_m: int = DEFAULT_BLOCK_M,
                      interpret: bool = False) -> jax.Array:
    """Pallas level sweep over one plan: ``fp``/``link`` are ``(M, n)``
    message planes, ``t0`` is ``(M,)``.  Grid = (M/block_m, height+1);
    level 0 initializes the resident output tile, levels ``1..height``
    gather-and-add in place."""
    m, n = fp.shape
    block_m = math.gcd(min(block_m, m), m)       # tiles must divide M
    nm = m // block_m
    kernel = functools.partial(_sweep_kernel, root=root)
    return pl.pallas_call(
        kernel,
        grid=(nm, height + 1),
        in_specs=[
            pl.BlockSpec((n,), lambda im, h: (0,)),           # parent
            pl.BlockSpec((n,), lambda im, h: (0,)),           # depth
            pl.BlockSpec((block_m, n), lambda im, h: (im, 0)),  # fp
            pl.BlockSpec((block_m, n), lambda im, h: (im, 0)),  # link
            pl.BlockSpec((block_m, 1), lambda im, h: (im, 0)),  # t0
        ],
        # the output tile is revisited across the sequential level axis:
        # the index map ignores h, so one message block's times stay in
        # VMEM from init (h=0) to the last level
        out_specs=pl.BlockSpec((block_m, n), lambda im, h: (im, 0)),
        out_shape=jax.ShapeDtypeStruct((m, n), fp.dtype),
        interpret=interpret,
    )(parent, depth, fp, link, t0[:, None])
