"""The rank directory over the hot cache's ids (ops/rank_directory.py).

One contract: ``directory_membership`` returns ``searchsorted_membership``'s
``(found, pos)`` over the ascending cached ids, for every int32 query —
random ids, the edges of a word, pads — whatever the cache holds; the
directory a device builds is the host's bit for bit; and the mesh store
splits hits from misses through it, homogeneous and typed.
"""
import numpy as np
import pytest

from graphlearn_tpu.distributed.dist_feature import DistFeature
from graphlearn_tpu.ops import rank_directory as rd
from graphlearn_tpu.ops import searchsorted_membership

MAX, MIN = np.iinfo(np.int32).max, np.iinfo(np.int32).min
P, F = 4, 4


def _edges(n):
  """Word and superblock edges, the space's ends and the pads a node
  buffer carries."""
  return np.array([0, 15, 16, 17, 31, 32, 65_535, 65_536, 65_537,
                   131_071, 131_072, n - 1, n, n + 15, n + 16, -1, MAX,
                   MIN], np.int64)


CASES = {
    # name: (cached ids, id space)
    'random': (np.sort(np.random.default_rng(0).choice(
        100_003, 5_000, replace=False)), 100_003),
    'every_row': (np.arange(1_000), 1_000),
    'every_row_whole_words': (np.arange(256), 256),
    'one_entry': (np.array([77]), 1_000),
    'word_edges': (np.array([0, 15, 16, 17, 31, 32, 998, 999]), 1_000),
    'last_bit_of_words': (np.arange(15, 4_096, 16), 4_096),
    # superblocks of 65,536 ids: a full one, edges, an empty one between
    'superblock_edges': (np.concatenate([
        np.arange(65_536), [65_536 + 1, 3 * 65_536 - 1, 3 * 65_536,
                            3 * 65_536 + 2]]), 3 * 65_536 + 5),
}


def _queries(n, seed=1):
  rng = np.random.default_rng(seed)
  q = np.concatenate([rng.integers(-40, n + 40, 4_000), _edges(n)])
  return np.clip(q, MIN, MAX).astype(np.int32)


def _check(words, rank, ids, n):
  import jax
  import jax.numpy as jnp
  q = _queries(n)
  found, pos = jax.jit(
      lambda w, r, x: rd.directory_membership(w, r, x, ids.shape[0]))(
          words, rank, q)
  want_found, want_pos = searchsorted_membership(
      jnp.asarray(ids.astype(np.int32)), jnp.asarray(q))
  assert np.array_equal(np.asarray(found), np.asarray(want_found))
  assert np.array_equal(np.asarray(pos), np.asarray(want_pos))


@pytest.mark.parametrize('case', sorted(CASES))
def test_directory_is_the_search_and_the_device_builds_the_host(case):
  import jax
  ids, n = CASES[case]
  words, rank = rd.build_directory_host(ids, n)
  assert words.dtype == np.uint32 and rank.dtype == np.int32
  assert words.shape == (n // 16 + 1,) and rank.shape == (n // 65_536 + 1,)
  dw, dr = jax.jit(lambda t: rd.build_directory(t, n))(ids.astype(np.int32))
  assert np.asarray(dw).tobytes() == words.tobytes()
  assert np.asarray(dr).tobytes() == rank.tobytes()
  last = int(words[-1])
  assert (int(rank[-1]) + (last >> 16) + bin(last & 0xFFFF).count('1')
          == ids.shape[0])
  _check(words, rank, ids, n)


def test_the_pad_directory_holds_no_id():
  """A store with no cache hands its programs a one-entry pad directory
  (the branch that would read it is not traced). It still answers as
  the one-entry pad table ``[INT32_MAX]`` does below ``INT32_MAX``, and
  no query is a member of it."""
  import jax
  import jax.numpy as jnp
  store = DistFeature(P, *_world(203)[1:3], _mesh())
  (words, rank), rows = store._cache_tables()
  assert words.shape == rank.shape == (1,) and rows.shape == (1, F)
  q = _queries(203)
  found, pos = jax.jit(
      lambda w, r, x: rd.directory_membership(w, r, x, 1))(words, rank, q)
  want_found, want_pos = searchsorted_membership(
      jnp.full((1,), MAX, jnp.int32), jnp.asarray(q))
  assert not np.asarray(found).any()
  below = q < MAX
  assert np.array_equal(np.asarray(want_found)[below],
                        np.asarray(found)[below])
  assert np.array_equal(np.asarray(want_pos), np.asarray(pos))


def _mesh():
  import jax
  from jax.sharding import Mesh
  return Mesh(np.array(jax.devices()[:P]), ('g',))


def _world(n, seed=5):
  rng = np.random.default_rng(seed)
  pb = rng.integers(0, P, n).astype(np.int32)
  feat = rng.normal(size=(n, F)).astype(np.float32)
  parts = [(np.nonzero(pb == p)[0], feat[pb == p]) for p in range(P)]
  hot = np.bincount(rng.zipf(1.4, 4 * n) % n, minlength=n)
  return feat, parts, pb, hot


def _requests(n, b=40, seed=3):
  rng = np.random.default_rng(seed)
  ids = rng.integers(0, n, (P, b)).astype(np.int32)
  ids[:, :6] = [0, 31, 32, 33, n - 1, n - 2]
  ids[:, -3:] = -1
  return ids


# typed: one store a node type, each over its own id space (one whole
# number of words, one ragged), each with its own cache
TYPED = {'paper': (203, 23), 'author': (64, 64)}


@pytest.mark.parametrize('ntype', sorted(TYPED))
def test_both_constructors_build_one_directory_and_split_by_it(ntype):
  import jax
  n, h = TYPED[ntype]
  feat, parts, pb, hot = _world(n, seed=len(ntype))
  mesh = _mesh()
  host = DistFeature(P, parts, pb, mesh, cache_rows=h, hotness=hot)
  fa = host.device_arrays()
  dev = DistFeature.from_device_shards(
      mesh, pb, fa['feat_ids'], fa['feats'], cache_rows=h,
      hotness=jax.numpy.asarray(hot.astype(np.int32)))
  fb = dev.device_arrays()
  assert np.array_equal(host.cache_ids, dev.cache_ids)
  words, rank = rd.build_directory_host(host.cache_ids, n)
  for k, want in (('cache_words', words), ('cache_rank', rank)):
    assert np.asarray(fa[k]).tobytes() == want.tobytes(), k
    assert np.asarray(fb[k]).tobytes() == want.tobytes(), k
  assert 'cache_ids' not in fb and 'cache_starts' not in fb
  _check(words, rank, host.cache_ids, n)
  ids = _requests(n)
  want_rows = np.where((ids >= 0)[..., None], feat[np.maximum(ids, 0)], 0)
  for store in (host, dev):
    assert np.asarray(store.get(ids)).tobytes() == want_rows.tobytes()
    s = store.stats()
    assert s['hits'] == int(np.isin(ids[ids >= 0], host.cache_ids).sum())
    assert (s['hits'] == s['lookups']) if h == n else (0 < s['hits'])
    store.reset_stats()
