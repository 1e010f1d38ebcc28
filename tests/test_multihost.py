"""Multi-host substrate test: 2 processes x 4 CPU devices each, one global
2-axis (slice=2, chip=4) mesh, a full distributed sample + feature step.

The documented CPU harness for dist_context.init_multihost (SURVEY §2.3
comm-backend mapping; the reference's equivalent is its multi-node RPC
launch path, distributed/launch.py): collectives run over gloo between the
two processes, exercising exactly the shard_map programs a TPU pod runs
over ICI/DCN.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

_WORKER = r'''
import os
import sys
pid = int(sys.argv[1])
port = sys.argv[2]
# The parent test process's XLA_FLAGS carry ITS 8-device count
# (conftest), so replace any existing count with this worker's 4.
import re
flags = os.environ.get('XLA_FLAGS', '')
flags = re.sub(r'--xla_force_host_platform_device_count=\d+', '', flags)
os.environ['XLA_FLAGS'] = (
    flags + ' --xla_force_host_platform_device_count=4').strip()
import jax
jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_num_cpu_devices', 4)
import numpy as np
import graphlearn_tpu as glt
from graphlearn_tpu.typing import GraphPartitionData

# 2-axis multi-slice layout: one slice per process (2 x 4) — the 'chip'
# axis is the per-process ICI analog, 'slice' crosses processes (DCN)
ctx = glt.distributed.init_multihost(f'localhost:{port}', num_processes=2,
                                     process_id=pid,
                                     mesh_shape='per_process')
assert ctx.world_size == 2 and ctx.rank == pid
assert ctx.num_partitions == 8
assert dict(ctx.mesh.shape) == {'slice': 2, 'chip': 4}, ctx.mesh.shape

N = 40
P = 8
rows = np.concatenate([np.arange(N), np.arange(N)])
cols = np.concatenate([(np.arange(N) + 1) % N, (np.arange(N) + 2) % N])
eids = np.arange(2 * N)
node_pb = (np.arange(N) % P).astype(np.int32)
epb = node_pb[rows]
parts, feats = [], []
for p in range(P):
  m = epb == p
  parts.append(GraphPartitionData(
      edge_index=np.stack([rows[m], cols[m]]), eids=eids[m]))
  ids = np.nonzero(node_pb == p)[0]
  feats.append((ids.astype(np.int64),
                ids[:, None].astype(np.float32) * np.ones((1, 4),
                                                          np.float32)))

dg = glt.distributed.DistGraph(P, 0, parts, node_pb)
df = glt.distributed.DistFeature(P, feats, node_pb, ctx.mesh)
sampler = glt.distributed.DistNeighborSampler(dg, [2], ctx.mesh, seed=0,
                                              dist_feature=df,
                                              collect_features=True)
seeds = np.arange(2 * P, dtype=np.int32).reshape(P, 2)
out = sampler.sample_from_nodes(seeds)
x, _ = sampler.collate(out)

# every process checks ITS addressable shards against the ring invariant
for shard_n, shard_r, shard_c, shard_m, shard_x in zip(
    out.node.addressable_shards, out.row.addressable_shards,
    out.col.addressable_shards, out.edge_mask.addressable_shards,
    x.addressable_shards):
  n = np.asarray(shard_n.data)[0]
  r = np.asarray(shard_r.data)[0]
  c = np.asarray(shard_c.data)[0]
  m = np.asarray(shard_m.data)[0]
  fx = np.asarray(shard_x.data)[0]
  assert m.sum() > 0
  for ri, ci, mi in zip(r, c, m):
    if not mi:
      continue
    u, v = int(n[ci]), int(n[ri])
    assert v in ((u + 1) % N, (u + 2) % N), (u, v)
  valid = n >= 0
  np.testing.assert_allclose(fx[valid][:, 0], n[valid])
print(f'MULTIHOST-OK pid={pid}', flush=True)
'''


def test_two_process_mesh(tmp_path):
  from graphlearn_tpu.utils import get_free_port
  port = str(get_free_port())
  script = tmp_path / 'worker.py'
  script.write_text(_WORKER)
  env = dict(os.environ)
  env.pop('JAX_PLATFORMS', None)
  env['PYTHONPATH'] = os.path.dirname(os.path.dirname(
      os.path.abspath(__file__)))
  procs = [subprocess.Popen(
      [sys.executable, str(script), str(i), port],
      stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
      text=True) for i in range(2)]
  outs = [p.communicate(timeout=240)[0] for p in procs]
  for i, (p, out) in enumerate(zip(procs, outs)):
    assert p.returncode == 0, f'process {i} failed:\n{out[-3000:]}'
    assert f'MULTIHOST-OK pid={i}' in out
