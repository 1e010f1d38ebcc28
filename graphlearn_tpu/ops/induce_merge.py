"""Merge-sort exact inducer: cross-hop dedup/relabel built on sorts only.

The third (and fastest) exact-dedup engine, alongside the direct-address
table (ops/induce_map.py) and the legacy searchsorted engine
(ops/induce.py). Same semantic contract as the reference's GPU hash-table
inducer (/root/reference/graphlearn_torch/include/hash_table.cuh:43-84,
csrc/cuda/inducer.cu:95-165): every node sampled within a batch gets one
globally-unique local index; which duplicate "wins" is unspecified (the
reference takes atomicCAS first-writer; this engine takes the
first-in-flat-order occurrence).

Why sorts: on TPU (v5e device trace, rounds 2-20; PERF.md section 6) random
element scatters/gathers run at ~140-200 M transactions/s regardless of
table size — HBM-transaction-bound, so the [N]-table engine's 6 random
ops/hop cost ~30 ms/batch at products scale. A key+payload `lax.sort` of
the same volume runs 3-5x faster than ONE such gather (768k pairs =
1.2 ms: lane-parallel bitonic networks are dense VPU work). This engine
therefore does per-hop dedup + cross-hop membership with one merged sort
and two compaction sorts, zero random access:

  sorted-view invariant: state carries (sorted_ids, sorted_loc) — the
  current node set ascending, with each id's local index. Only the first
  ``prefix_cap`` slots (the static max node count before this hop, i.e.
  the same per-hop offset the tree layout uses) can be occupied, so each
  hop touches a prefix that grows with the hop, not the full capacity.

  per hop (C = prefix_cap, S = frontier*k candidates):
    1. ONE sort of [C+S]: keys = (state sorted ids ++ candidate ids),
       second key orders state entries before candidates of the same id
       and candidate duplicates by flat position. First-occurrence
       candidates are the new nodes; their rank (cumsum) assigns local
       indices num_nodes+0.., and a segmented fill-forward (associative
       scan — dense, log-depth) broadcasts each group's local index to
       every duplicate.
    2. compaction sort #2 restores candidate results to flat order (the
       edge-output contract matches nbrs.reshape(-1), like the other
       engines) — a sort is ~3x cheaper than the equivalent unsort
       scatter on TPU.
    3. compaction sort #3 packs the winners into the append block: one
       contiguous dynamic-update-slice extends ``nodes``, and the same
       block IS the (compact) next-hop frontier.
    4. compaction sort #4 rebuilds the sorted view for the next hop
       (skipped on the final hop via ``update_view=False``).

Memory scales with the batch only (no [N] table), so this engine also
replaces the legacy engine for billion-node graphs.
"""
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .unique import FILL, masked_unique

# payload encoding: state entries carry their local index (< _MARK);
# candidates carry _MARK + flat position. Static capacities above 4M
# nodes/edges per batch would alias — asserted at trace time.
_MARK = 1 << 22


class MergeInducerState(NamedTuple):
  nodes: jax.Array       # [cap] global ids, FILL-padded; pos == local idx
  num_nodes: jax.Array   # scalar int32
  sorted_ids: jax.Array  # [cap] ascending ids, INT-MAX-padded
  sorted_loc: jax.Array  # [cap] local index of sorted_ids (-1 padded)


def _seg_fill(vals: jax.Array, flags: jax.Array) -> jax.Array:
  """Broadcast ``vals`` at flagged positions forward until the next flag
  (segmented fill).

  Implemented as THREE packed cummaxes instead of an associative scan:
  the scan's log-depth slice/concat cascade lowers to ~40 small XLA ops
  per call (~1 ms/batch of pure op overhead at products scale, rounds
  2-20), while a cummax is one fused op. Packing rides the
  group rank in the high bits — cummax then always selects the CURRENT
  group's value — with the payload split into 3 bytes so everything
  fits int32: group rank < 2^23, values in [0, 2^24). Positions before
  the first flag return garbage (callers mask them; in sorted-key order
  the first valid element is always a flag).
  """
  n = vals.shape[0]
  assert n < (1 << 23), 'seg_fill capacity exceeds packed-cummax bound'
  grp = jnp.cumsum(flags.astype(jnp.int32))          # <= n < 2^23
  v = jnp.where(flags, vals, 0)
  b0 = jax.lax.cummax((grp << 8) | (v & 0xFF))
  b1 = jax.lax.cummax((grp << 8) | ((v >> 8) & 0xFF))
  b2 = jax.lax.cummax((grp << 8) | ((v >> 16) & 0xFF))
  return ((b0 & 0xFF) | ((b1 & 0xFF) << 8) | ((b2 & 0xFF) << 16))


@functools.partial(jax.jit, static_argnames=('capacity',))
def init_node_merge(seeds: jax.Array, seed_mask: jax.Array, capacity: int):
  """Start a batch: dedup seeds into local indices (ascending order, like
  the legacy sort engine). Returns (state, uniq [B], uniq_mask [B],
  inverse [B])."""
  b = seeds.shape[0]
  uniq, count, inverse = masked_unique(seeds, seed_mask, size=b)
  big = jnp.iinfo(seeds.dtype).max
  nodes = jnp.full((capacity,), FILL, seeds.dtype).at[:b].set(uniq)
  sorted_ids = jnp.full((capacity,), big, seeds.dtype)
  sorted_ids = sorted_ids.at[:b].set(jnp.where(uniq == FILL, big, uniq))
  sorted_loc = jnp.full((capacity,), -1, jnp.int32)
  sorted_loc = sorted_loc.at[:b].set(
      jnp.where(uniq == FILL, -1, jnp.arange(b, dtype=jnp.int32)))
  state = MergeInducerState(nodes, count.astype(jnp.int32), sorted_ids,
                            sorted_loc)
  return state, uniq, jnp.arange(b) < count, inverse


@functools.partial(jax.jit, static_argnames=('capacity', 'dtype'))
def init_empty_merge(capacity: int, dtype=jnp.int32):
  """A merge-inducer state with no nodes yet (hetero lazy per-type
  states)."""
  big = jnp.iinfo(dtype).max
  return MergeInducerState(
      jnp.full((capacity,), FILL, dtype),
      jnp.asarray(0, jnp.int32),
      jnp.full((capacity,), big, dtype),
      jnp.full((capacity,), -1, jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=('prefix_cap', 'max_new',
                                    'update_view'))
def induce_next_merge(state: MergeInducerState, src_idx: jax.Array,
                      nbrs: jax.Array, nbr_mask: jax.Array,
                      prefix_cap: int, max_new=None,
                      update_view: bool = True):
  """Absorb one hop (same output contract as ops.induce.induce_next:
  edge arrays in ``nbrs.reshape(-1)`` order, compact frontier).

  Args:
    prefix_cap: static max node count BEFORE this hop — under clamped
      plans, the sum of clamped per-hop frontier caps; bounds the
      sorted-view prefix this hop must merge against, and (with
      ``max_new``) keeps the contiguous node append statically in
      bounds.
    max_new: static clamp on nodes KEPT this hop (the plan's
      ``caps[i+1]``). None = the hop's full candidate width (valid for
      unclamped plans, where capacity = sum of full widths).
    update_view: skip the sorted-view rebuild (one compaction sort) when
      no further hop will be induced on this state (the final hop).
  """
  f, k = nbrs.shape
  size = f * k
  cap = state.nodes.shape[0]
  c = min(prefix_cap, cap)
  # encoding bounds: state payloads (local idx < cap) must stay below
  # _MARK, and candidate payloads (_MARK + pos, pos < size) must fit int32
  assert cap <= _MARK and _MARK + size < 2 ** 31, \
      'batch capacity exceeds payload encoding'
  # _seg_fill packs its payload into 3 bytes: every value it carries here
  # (tentative local idx new_idx < num_nodes + num_new <= cap + size) must
  # fit 2^24. Asserted directly so a future bump of _MARK or the seg-fill
  # capacity bound fails at trace time instead of corrupting local indices.
  assert cap + size < (1 << 24), \
      'cap + hop size exceeds the seg_fill 3-byte payload bound'
  big = jnp.iinfo(state.nodes.dtype).max

  flat = nbrs.reshape(-1).astype(state.nodes.dtype)
  flat_mask = nbr_mask.reshape(-1)

  # -- sort #1: merged (state-prefix ++ candidates) ------------------------
  keys = jnp.concatenate([
      jax.lax.slice(state.sorted_ids, (0,), (c,)),
      jnp.where(flat_mask, flat, big)])
  payload = jnp.concatenate([
      jax.lax.slice(state.sorted_loc, (0,), (c,)),
      _MARK + jnp.arange(size, dtype=jnp.int32)])
  keys_s, pay_s = jax.lax.sort((keys, payload), num_keys=2)

  valid = keys_s != big
  is_state = pay_s < _MARK
  first = valid & jnp.concatenate([
      jnp.ones((1,), bool), keys_s[1:] != keys_s[:-1]])
  winner = first & ~is_state                     # first occurrence, no
  rank = (jnp.cumsum(winner) - 1).astype(jnp.int32)   # state entry before
  num_new = jnp.sum(winner).astype(jnp.int32)
  limit = min(size, cap - c, size if max_new is None else max_new)
  num_kept = jnp.minimum(num_new, limit)
  new_idx = state.num_nodes + rank
  base = jnp.where(is_state, pay_s, new_idx)     # local idx at each first
  local_all = _seg_fill(jnp.where(first, base, -1), first)

  # -- sort #2: candidate locals back to flat order ------------------------
  pos_key = jnp.where(is_state, size, pay_s - _MARK)
  cols_sorted = jnp.where(valid & ~is_state, local_all, -1)
  _, cols_full = jax.lax.sort((pos_key, cols_sorted), num_keys=1)
  cols = jax.lax.slice(cols_full, (0,), (size,))
  # edges whose target winner was overflow-truncated (local idx past the
  # stored region) must NOT stay valid — models would silently aggregate
  # clamped-garbage rows. No-op on unclamped plans (cols < new_total
  # always holds there).
  emask = flat_mask & (cols >= 0) & (cols < state.num_nodes + num_kept)
  cols = jnp.where(emask, cols, -1)
  rows = jnp.where(emask, jnp.repeat(src_idx.astype(jnp.int32), k), -1)

  # -- sort #3: winners -> contiguous append block (also the frontier) -----
  # Clamped-growth invariant: callers pass prefix_cap = the CLAMPED
  # occupancy bound before this hop (sum of clamped frontier caps), so
  # num_nodes <= c by induction and a block of limit = min(size, cap-c)
  # always fits — the append is one contiguous dynamic-update-slice on
  # every plan, including node_budget / frontier_caps-clamped ones.
  # Under overflow (num_new > limit, detectable as
  # num_sampled_nodes[i+1] > caps[i+1]) the extra winners are TRUNCATED:
  # not stored, not in the frontier — num_nodes stays <= capacity.
  wkey = jnp.where(winner, rank, size + c)
  _, block_full = jax.lax.sort((wkey, keys_s), num_keys=1)
  in_new = jnp.arange(limit) < num_kept
  block = jnp.where(in_new, jax.lax.slice(block_full, (0,), (limit,)),
                    FILL)
  nodes = jax.lax.dynamic_update_slice(state.nodes, block,
                                       (state.num_nodes,))
  frontier = jnp.concatenate(
      [block, jnp.full((size - limit,), FILL, block.dtype)]) \
      if limit < size else block
  fin = jnp.arange(size) < num_kept
  frontier_idx = jnp.where(
      fin, state.num_nodes + jnp.arange(size, dtype=jnp.int32), -1)

  # -- sort #4: new sorted view prefix [c+size] ----------------------------
  if update_view:
    # overflow-truncated winners (rank >= limit) must not enter the view
    # either — their ids were never stored
    keep = valid & (is_state | (winner & (rank < limit)))
    sid, sloc = jax.lax.sort((jnp.where(keep, keys_s, big),
                              jnp.where(keep, local_all, -1)), num_keys=1)
    if c + size < cap:
      sorted_ids = jnp.concatenate(
          [sid, jax.lax.slice(state.sorted_ids, (c + size,), (cap,))])
      sorted_loc = jnp.concatenate(
          [sloc, jax.lax.slice(state.sorted_loc, (c + size,), (cap,))])
    else:
      sorted_ids, sorted_loc = sid[:cap], sloc[:cap]
  else:
    sorted_ids, sorted_loc = state.sorted_ids, state.sorted_loc

  # num_new reports the RAW new-unique count (overflow detection:
  # num_sampled_nodes[i+1] > caps[i+1]); state growth is clamped so the
  # occupancy invariant holds on every plan
  out = dict(rows=rows, cols=cols, edge_mask=emask, frontier=frontier,
             frontier_idx=frontier_idx, frontier_mask=fin,
             num_new=num_new)
  return MergeInducerState(nodes, state.num_nodes + num_kept, sorted_ids,
                           sorted_loc), out
