"""One-chip probe of the owners' bounded lookup (dist_feature.bounded_lookup)
at the mesh cell's shard size, tiled against one piece, by the received
block's width and fill: ``chiprun --chips 1 -- python3
scripts/probe_bounded_lookup.py``.

The mesh cell reads the loop at ONE fill (8.5 % of 124,096 columns). This
times the plain ``lookup_local`` over a 9.25 M-row shard for blocks of
``[4, cap]`` whose buckets hold a valid prefix of ``fill`` of their
columns, under the draw's rule and with the rule answering 0 (one piece),
checks the two return the same bytes, and prints one ``micro:`` JSON line
a case (program times off the device trace, ``utils.device_program_ms``,
mean of ``REPS`` calls). PERF.md section 6, PR 40 has the readings.
"""
import json, os, re, sys, time, types
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from graphlearn_tpu.distributed import dist_feature
from graphlearn_tpu.ops import sorted_index as si
from graphlearn_tpu.ops.neighbor import draw_tile_rows

N, ROWS, P, F = 37_019_985, 9_254_997, 4, 128
REPS = 5
rng = np.random.default_rng(40)

own = (1 + 4 * np.arange(ROWS, dtype=np.int64))
own = own[own < N].astype(np.int32)
ids_np = np.concatenate([own, np.full(ROWS - own.shape[0],
                                      np.iinfo(np.int32).max, np.int32)])
index = si.build_sorted_index_host(ids_np, N)
look = dist_feature.DistFeature._lookup_fn(
    types.SimpleNamespace(_row_index=index), False)
feat_ids, starts = jnp.asarray(ids_np), jnp.asarray(index.starts)
feats = jax.jit(lambda: (jnp.arange(ROWS, dtype=jnp.float32)[:, None]
                         + jnp.arange(F, dtype=jnp.float32)[None]))()


def owner_rows(feat_ids, starts, feats, r):
  return dist_feature.bounded_lookup(
      lambda flat, tiled: look(feat_ids, (starts, feats), flat, tiled),
      r, F, jnp.float32)


def build(cap, fill):
  """Both programs of a case, warmed, and the bytes they agree on."""
  valid = int(round(fill * cap))
  r_np = np.full((P, cap), -1, np.int32)
  r_np[:, :valid] = rng.choice(own, (P, valid))
  r = jnp.asarray(r_np)
  res = dict(cap=cap, fill=fill, valid_columns=valid,
             tile=draw_tile_rows(cap),
             tiles=-(-valid // draw_tile_rows(cap)))
  fns, outs = {}, {}
  rule = dist_feature.draw_tile_rows
  for kind, answer in (('tiled', rule), ('one_piece', lambda cap: 0)):
    def fn(feat_ids, starts, feats, r):
      return owner_rows(feat_ids, starts, feats, r)
    fn.__name__ = f'{kind}_c{cap}'
    dist_feature.draw_tile_rows = answer
    try:
      fns[kind] = jax.jit(fn)
      outs[kind] = np.asarray(fns[kind](feat_ids, starts, feats, r))
    finally:
      dist_feature.draw_tile_rows = rule
  res['equal'] = outs['tiled'].tobytes() == outs['one_piece'].tobytes()
  res['found'] = int((outs['tiled'][..., 1] != 0).sum())
  return res, fns, r


def main():
  import tempfile
  import graphlearn_tpu as glt
  caps, fills = (2_048, 8_192, 32_768, 124_096), (0.085, 0.25, 0.5, 1.0)
  # a width's program is one executable whatever the block holds: one
  # trace a fill, so that a program's events are one case's calls
  for fill in fills:
    cases = [build(cap, fill) for cap in caps]
    with tempfile.TemporaryDirectory() as d:
      with glt.utils.profile_trace(d):
        for res, fns, r in cases:
          for kind, fn in fns.items():
            t0 = time.perf_counter()
            for _ in range(REPS):
              jax.block_until_ready(fn(feat_ids, starts, feats, r))
            # the host's clock around a blocking call: the device time
            # plus a dispatch and a wake-up
            res[kind + '_host_ms'] = round(
                (time.perf_counter() - t0) / REPS * 1e3, 4)
      ms = glt.utils.device_program_ms(d)
    for res, fns, _ in cases:
      for kind, fn in fns.items():
        # the timeline puts an id behind a program's name
        hit = [v for n, v in ms.items()
               if re.match(f'jit_{fn.__name__}(\\D|$)', n)]
        assert len(hit) <= 1 and all(c == REPS for _, c in hit), ms
        res[kind + '_ms'] = round(hit[0][0], 4) if hit else None
      print('micro: ' + json.dumps(res), flush=True)


if __name__ == '__main__':
  main()
