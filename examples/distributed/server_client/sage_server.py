"""Sampling SERVER for server-client mode.

Counterpart of /root/reference/examples/distributed/server_client_mode/
sage_supervised_server.py: the server owns the graph + features, runs
sampling producers on request, and streams batches to training clients
over RPC. Start this first; it prints its endpoint for the client.

A chip belongs to one process: beside a trainer on a one-chip machine the
server is started on the CPU backend (graph_mode='CPU' keeps the arrays on
the host but does not pick a backend — the sampler uploads to whatever
backend this process has).

Run: JAX_PLATFORMS=cpu python examples/distributed/server_client/sage_server.py --port 18777
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..',
                                '..'))

import graphlearn_tpu as glt


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--port', type=int, default=18777)
  ap.add_argument('--num-nodes', type=int, default=20_000)
  ap.add_argument('--avg-deg', type=int, default=12)
  ap.add_argument('--num-clients', type=int, default=1)
  args = ap.parse_args()

  rng = np.random.default_rng(0)
  n, e = args.num_nodes, args.num_nodes * args.avg_deg
  rows = rng.integers(0, n, e)
  cols = rng.integers(0, n, e)
  feat = rng.standard_normal((n, 64)).astype(np.float32)

  ds = glt.data.Dataset()
  ds.init_graph(np.stack([rows, cols]), num_nodes=n, graph_mode='CPU')
  ds.init_node_features(feat, with_device=False)
  ds.init_node_labels(rng.integers(0, 16, n))

  host, port = glt.distributed.init_server(
      num_servers=1, num_clients=args.num_clients, server_rank=0,
      dataset=ds, server_client_master_port=args.port)
  print(f'server listening on {host}:{port}', flush=True)
  glt.distributed.wait_and_shutdown_server()
  print('server exited', flush=True)


if __name__ == '__main__':
  main()
