"""The owners' side of the miss-only row exchange is bounded by what the
received buckets hold (ISSUE 40): ``dist_feature.bounded_lookup`` runs
``lookup_local`` tile by tile below the block's last valid column and
returns, bit for bit, the rows the one-piece lookup returns — for any
mask, over the plain table and the slab-backed one, under both capacities
of the flat exchange and both paths of the hierarchical one — and a block
under the draw's threshold lowers to the program it had.
"""
import re

import numpy as np
import pytest

from graphlearn_tpu import metrics
from graphlearn_tpu.distributed import dist_feature
from graphlearn_tpu.distributed.dist_feature import (DistFeature,
                                                     bounded_lookup,
                                                     miss_capacity)
from graphlearn_tpu.metrics import registry_names as names
from graphlearn_tpu.ops.neighbor import draw_tile_rows

N, P, F = 9001, 4, 4
SHARD = 1                    # the owner whose tables the helper tests read
CAP, T = 72, 16              # a block no multiple of the tile: 5 tiles,
                             # the last one clamped to end at the cap
HOT = 40                     # the slab variant's HBM prefix, in positions
INT32_MAX = np.iinfo(np.int32).max


def _mesh(kind='flat'):
  import jax
  from jax.sharding import Mesh
  devs = np.array(jax.devices()[:P])
  return (Mesh(devs, ('g',)) if kind == 'flat'
          else Mesh(devs.reshape(2, 2), ('slice', 'chip')))


def _store(kind='flat', **kw):
  rng = np.random.default_rng(40)
  pb = (np.arange(N) % P).astype(np.int32)
  feat = rng.normal(size=(N, F)).astype(np.float32)
  feat[SHARD + P * 3, 2] = -0.0                  # a row with a negative zero
  parts = [(np.nonzero(pb == p)[0].astype(np.int64), feat[pb == p])
           for p in range(P)]
  return DistFeature(P, parts, pb, _mesh(kind), **kw), feat


# the columns of each of the block's P rows that hold a request
BLOCKS = {
    'empty': [[], [], [], []],
    'one_request': [[], [0], [], []],
    'ends_on_a_tile_boundary': [range(2 * T), range(5), [], range(T)],
    'ends_one_past_it': [range(3), range(2 * T + 1), [], []],
    'full': [range(CAP)] * P,
    # no prefix: a hole inside it, and a request behind the pads
    'a_hole_and_a_straggler': [[c for c in range(21) if c != 5] + [40],
                               range(7), [], [2]],
}


def _block(case):
  """``r [P, CAP]``: ids SHARD owns at the case's columns (every fourth
  one an id of ANOTHER shard: a request this owner does not find), -1
  elsewhere."""
  own = np.arange(SHARD, N, P)
  rng = np.random.default_rng(len(case))
  r = np.full((P, CAP), -1, np.int32)
  for row, cols in enumerate(BLOCKS[case]):
    cols = np.asarray(list(cols), np.int64)
    r[row, cols] = rng.choice(own, cols.shape[0], replace=False)
    r[row, cols[::4]] += 1                       # not this shard's
  last = max([max(c) + 1 for c in BLOCKS[case] if len(c)], default=0)
  return r, last


def _tables(df, table, r):
  """One shard's ``(feat_ids, feats)`` arguments of ``lookup_local``: the
  plain ``[n, F]`` table, or the slab pytree with every requested
  position beyond the hot prefix staged."""
  fid, rows = df.feat_ids[SHARD], df.feats[SHARD]
  starts = np.asarray(df._row_index.starts[SHARD])
  if table == 'plain':
    return fid, (starts, rows)
  pos = np.searchsorted(fid, r[r >= 0])
  pos = np.unique(pos[(pos >= HOT) & (fid[np.minimum(pos, fid.shape[0] - 1)]
                                      == r[r >= 0])])
  slab_pos = np.full((P * CAP,), INT32_MAX, np.int32)
  slab_pos[:pos.shape[0]] = pos
  slab_rows = np.zeros((P * CAP, F), np.float32)
  slab_rows[:pos.shape[0]] = rows[pos]
  return fid, (starts, (rows[:HOT], slab_pos, slab_rows))


def _helper(df, table):
  look = df._lookup_fn(slab=table == 'slab')
  return lambda fid, feats, r: bounded_lookup(
      lambda flat, tiled: look(fid, feats, flat, tiled), r, F, np.float32)


@pytest.fixture(scope='module')
def store():
  return _store()


@pytest.fixture
def tile_rule(monkeypatch):
  """The rule hands out tiles from 2,048 columns up; ``tile_rule(T)`` gives
  the store's module a rule of ``T`` columns for any block (0: one piece),
  so the loop engages at ``CAP`` columns."""
  def set_(t):
    monkeypatch.setattr(dist_feature, 'draw_tile_rows', lambda cap: t)
  return set_


@pytest.mark.parametrize('case', sorted(BLOCKS))
@pytest.mark.parametrize('table', ['plain', 'slab'])
def test_the_tiled_rows_are_the_one_piece_lookups_bit_for_bit(store, table,
                                                              case,
                                                              tile_rule):
  import jax
  import jax.numpy as jnp
  df, feat = store
  r, last = _block(case)
  fid, feats = _tables(df, table, r)
  tile_rule(0)
  whole = jax.jit(_helper(df, table))(fid, feats, r)
  tile_rule(T)
  tiled = jax.jit(_helper(df, table))(fid, feats, r)
  assert np.asarray(tiled).tobytes() == np.asarray(whole).tobytes()
  # ceil(last valid column / T) tiles ran, no more: a lookup that answers
  # every slot, pads too, marks the columns the loop visited
  marked = jax.jit(lambda r: bounded_lookup(
      lambda flat, tiled: jnp.ones((flat.shape[0], F), np.float32), r, F,
      np.float32))(r)
  tiles = -(-last // T)
  assert (np.asarray(marked) == (np.arange(CAP) < min(tiles * T, CAP))[
      None, :, None]).all()
  assert tiled.shape == (P, CAP, F) and tiled.dtype == np.float32
  # and both are the rows themselves: this owner's ids, zeros elsewhere
  mine = (r >= 0) & (r % P == SHARD)
  want = np.where(mine[..., None], feat[np.maximum(r, 0)], 0)
  assert np.asarray(tiled).tobytes() == want.astype(np.float32).tobytes()


@pytest.mark.parametrize('table', ['plain', 'slab'])
def test_a_block_under_the_threshold_lowers_to_the_parents_program(store,
                                                                   table,
                                                                   tile_rule):
  """``CAP`` is under the draw's threshold: by the rule itself the helper
  is the one piece ``exchange_flat.do`` ran before it — the same text,
  names and all; told to tile, the same block lowers to a loop whose
  body carries the ``tile`` component behind both parts' names."""
  import jax
  df, _ = store
  r, _ = _block('full')
  fid, feats = _tables(df, table, r)
  look = df._lookup_fn(slab=table == 'slab')

  def parent():
    def f(fid, feats, r):
      rows = look(fid, feats, r.reshape(-1))
      with jax.named_scope(names.SCOPE_ROWS):
        return rows.astype(np.float32).reshape(P, CAP, F)
    return f

  def by_rule():
    def f(fid, feats, r):
      return _helper(df, table)(fid, feats, r)
    return f

  lowered = lambda f: jax.jit(f).lower(fid, feats, r)
  text = lambda f: lowered(f).as_text(debug_info=True)
  op_names = lambda f: {q for q in re.findall(r'"([^"]*)"', text(f))
                        if q.startswith('jit(f)/')}
  assert draw_tile_rows(CAP) == 0
  assert lowered(by_rule()).as_text() == lowered(parent()).as_text()
  assert op_names(by_rule()) == op_names(parent()) != set()
  assert not any(names.SCOPE_TILE in q.split('/') for q in op_names(by_rule()))
  tile_rule(T)
  tiled = text(by_rule())
  assert 'stablehlo.while' in tiled
  for part in (names.SCOPE_LOOKUP, names.SCOPE_ROWS):
    assert f'{part}/{names.SCOPE_TILE}/' in tiled, part


@pytest.mark.parametrize('cap, tile', [
    (72, 0), (2047, 0), (2048, 256), (124096, 2048), (261248, 4096)])
def test_the_tile_is_the_draws_rule_of_the_blocks_width(cap, tile):
  """ONE rule, ``ops.neighbor.draw_tile_rows``: a label store's or a
  serving call's narrow block takes one piece, the mesh cell's 124,096
  columns take tiles of 2,048 (8,192 slots over four senders)."""
  import jax
  import jax.numpy as jnp
  seen = []

  def lookup(flat, tiled):
    seen.append((flat.shape[0], tiled))
    return jnp.zeros((flat.shape[0], F), np.float32)

  rows = jax.eval_shape(
      lambda r: bounded_lookup(lookup, r, F, jnp.bfloat16),
      jax.ShapeDtypeStruct((P, cap), np.int32))
  assert draw_tile_rows(cap) == tile
  assert seen == [(P * (tile or cap), bool(tile))]
  assert rows.shape == (P, cap, F) and rows.dtype == jnp.bfloat16


def _requests(kind, b, seed=7):
  """Per-shard request blocks ``[P, b]``: ids spread over every owner, or
  every id on partition 0 (more than a fractional bucket holds)."""
  rng = np.random.default_rng(seed)
  if kind == 'spread':
    ids = rng.integers(0, N, (P, b)).astype(np.int32)
    ids[:, -5:] = -1
    return ids
  own = np.arange(0, N, P)
  return np.stack([rng.choice(own, b, replace=False)
                   for _ in range(P)]).astype(np.int32)


@pytest.mark.parametrize('requests', ['spread', 'one_partition'])
@pytest.mark.parametrize('mesh_kind', ['flat', 'slice_chip'])
def test_both_capacities_of_both_exchanges_return_cpu_gets_rows(mesh_kind,
                                                                requests):
  """At 2,048 requests a shard the full-width blocks (the flat
  exchange's fallback ``[P, b]``, the hierarchical one's ``flat_path``)
  and the 'slice' stage's ``[S, cap2]`` are wide enough to tile by the
  rule itself; ``one_partition`` overflows the fractional buckets, so
  the fallback is what runs."""
  b = 2048
  df, feat = _store(mesh_kind)
  ids = _requests(requests, b)
  got = np.asarray(df.get(ids))
  want = np.where((ids >= 0)[..., None], feat[np.maximum(ids, 0)], 0)
  assert got.tobytes() == want.astype(np.float32).tobytes()
  assert got.tobytes() == np.where(
      (ids >= 0)[..., None],
      df.cpu_get(np.maximum(ids, 0).reshape(-1)).reshape(P, b, F),
      0).tobytes()
  s = df.stats()
  assert (s['overflow'] > 0) == (requests == 'one_partition')
  assert s['lookups'] == int((ids >= 0).sum())


def test_the_flat_program_tiles_both_capacities_and_no_collective():
  """At 4,096 requests a shard both branches of the overflow ``cond``
  hold a tile loop; the two ``all_to_all``s stay outside it."""
  import jax
  b = 4096
  df, _ = _store()
  assert draw_tile_rows(miss_capacity(b, P, df.bucket_frac)) == 256
  ids = _requests('spread', b)
  text = jax.jit(df._build_fn(b)).lower(ids, ids >= 0).as_text(
      debug_info=True)
  df.reset_stats()                   # the lowering left a tracer there
  quoted = set(re.findall(r'"([^"]*)"', text))
  for branch in ('branch_0_fun', 'branch_1_fun'):
    for part in (names.SCOPE_LOOKUP, names.SCOPE_ROWS):
      assert any(f'glt.collate/exchange/cond/{branch}/while/body/{part}/'
                 f'{names.SCOPE_TILE}/' in q for q in quoted), (branch, part)
  assert any('all_to_all' in q for q in quoted)
  assert not any('all_to_all' in q and names.SCOPE_TILE in q.split('/')
                 for q in quoted)
  assert not any('all_to_all' in q and 'while' in q.split('/')
                 for q in quoted)


def _gauge(name):
  return metrics.snapshot()['gauges'].get(name)


@pytest.mark.parametrize('kind', ['flat', 'slice_chip'])
def test_the_store_publishes_the_slots_a_tile_holds(kind):
  """``<stats_prefix>.lookup_tile_slots`` beside ``exchange_slots``:
  buckets x tile columns of the no-overflow block, 0 where it is not
  tiled; set when the body is built."""
  for name in ('dist_feature.lookup_tile_slots',
               'dist_label.lookup_tile_slots'):
    metrics.reset(name)
  df, _ = _store(kind)
  assert _gauge('dist_feature.lookup_tile_slots') is None
  b = 8192
  df._shard_body(b)
  buckets, cap = ((P, miss_capacity(b, P, df.bucket_frac))
                  if kind == 'flat' else
                  (2, min(2 * b, miss_capacity(b, 2, df.bucket_frac))))
  assert _gauge('dist_feature.exchange_slots') == buckets * cap
  assert draw_tile_rows(cap) == 256
  assert _gauge('dist_feature.lookup_tile_slots') == buckets * 256
  lab, _ = _store(kind)
  lab.stats_prefix = 'dist_label'
  lab._shard_body(8)                 # a label store's narrow block
  assert _gauge('dist_label.lookup_tile_slots') == 0
  assert _gauge('dist_feature.lookup_tile_slots') == buckets * 256
