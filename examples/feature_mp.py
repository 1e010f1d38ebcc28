"""Feature store shared across worker processes.

Counterpart of /root/reference/examples/feature_mp.py: build one Feature
(hot/cold split by in-degree, id2index reorder), hand it to multiple
worker processes, and verify every worker gathers identical, correct
rows. The reference ships CUDA-IPC handles to each GPU rank; on TPU the
handoff is host arrays (Feature.share_ipc) and each worker re-inits its
own device placement lazily — same contract, no device pointers.

Workers run on the CPU backend (this example validates the sharing
contract, not device bandwidth; a chip belongs to one process at a
time, so several workers cannot share it).

Run: python examples/feature_mp.py
"""
import multiprocessing as mp
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))


def worker(rank, handle, q):
  try:
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import graphlearn_tpu as glt
    feature = glt.data.Feature.from_ipc_handle(handle)
    assert list(feature.shape) == [128 * 3, 128]
    # ids span all three value blocks (reference feature_mp.py:23-27)
    ids = np.array([10, 20, 200, 210, 300, 310], np.int64)
    got = np.asarray(feature[ids], np.float32)
    want = np.concatenate([np.ones((2, 128), np.float32) * v
                           for v in (1.0, 2.0, 3.0)])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    q.put((rank, 'ok'))
  except Exception as e:  # surface child failures to the parent
    q.put((rank, f'{type(e).__name__}: {e}'))


def main():
  import jax
  jax.config.update('jax_platforms', 'cpu')
  import graphlearn_tpu as glt

  world_size = 2
  attr = np.ones((128, 128), np.float32)
  tensor = np.concatenate([attr, attr * 2, attr * 3])

  rng = np.random.default_rng(0)
  n = 128 * 3
  rows = np.concatenate([np.arange(n), rng.integers(0, 128, n),
                         rng.integers(0, 256, n)])
  cols = rng.integers(0, n, rows.shape[0])
  topo = glt.data.Topology(np.stack([rows, cols]), num_nodes=n)

  split_ratio = 0.8
  reordered, id2index = glt.data.sort_by_in_degree(tensor, split_ratio,
                                                   topo)
  feature = glt.data.Feature(reordered, split_ratio=split_ratio,
                             id2index=id2index)
  handle = feature.share_ipc()

  ctx = mp.get_context('spawn')
  q = ctx.Queue()
  procs = [ctx.Process(target=worker, args=(r, handle, q))
           for r in range(world_size)]
  for p in procs:
    p.start()
  results = [q.get(timeout=120) for _ in procs]
  for p in procs:
    p.join()
  for rank, status in sorted(results):
    print(f'worker {rank}: {status}')
  assert all(s == 'ok' for _, s in results), results
  print('feature_mp OK')


if __name__ == '__main__':
  main()
