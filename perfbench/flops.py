"""Operations and bytes a training step REQUIRES, counted on valid rows.

Never from padded buffer sizes: a buffer sized for the worst case is the
program's choice, not the algorithm's (PERF.md §6: padded rows in the
numerator overstated utilisation about six times). ``nodes[h]`` is the
number of valid node rows hop h added (``nodes[0]`` = seeds) and
``edges[h]`` the number of valid sampled edges of hop h, both read from a
batch's masks.

The count is of the layered computation the algorithm needs: with L
layers, layer i (0 = input side) produces rows for the nodes within
``L-1-i`` hops of the seeds, from the rows of the nodes within ``L-i`` hops
and the edges of hops ``< L-i``. A multiply-add is two operations.
Backward costs the forward's matmuls twice (one for the weights' gradient,
one for the input's), except at layer 0, whose input is data and needs no
gradient. Elementwise work per edge (mean, softmax) is counted once
forward and once backward.
"""


def _prefix(v, k):
  return int(sum(v[:k]))


def sage_layer_flops(n_out, e_used, d_in, d_out, first):
  """Forward+backward operations of one mean-SAGE layer: two [n_out, d_in]
  x [d_in, d_out] matmuls (self, neighbour), the mean's e_used x d_in
  adds."""
  matmul = 2 * 2 * n_out * d_in * d_out
  agg = e_used * d_in
  return matmul * (2 if first else 3) + agg * 2


def gat_layer_flops(n_in, n_out, e_used, d_in, heads, d_head, first):
  """Forward+backward operations of one GAT layer: the [n_in, d_in] x
  [d_in, H*D] projection, two attention dot products per projected row,
  and per edge and head the logit, the softmax (about 6) and the
  weighted sum of D-vectors (2*D)."""
  hd = heads * d_head
  proj = 2 * n_in * d_in * hd
  att = 2 * 2 * n_in * hd
  edge = e_used * heads * (6 + 2 * d_head)
  del n_out
  return proj * (2 if first else 3) + (att + edge) * 2


def step_flops(model, nodes, edges):
  """Required operations of one training step of ``model`` (the
  description :func:`perfbench.reference.layer_dims` reads) on a batch
  with these valid counts, the classifier's softmax left out (47 wide,
  nothing beside the rest)."""
  from perfbench.reference import layer_dims
  dims = layer_dims(model)
  depth = len(dims)
  total = 0
  for i, (d_in, d, heads) in enumerate(dims):
    n_in = _prefix(nodes, depth - i + 1)
    n_out = _prefix(nodes, depth - i)
    e_used = _prefix(edges, depth - i)
    if model['kind'] == 'sage':
      total += sage_layer_flops(n_out, e_used, d_in, d, i == 0)
    else:
      total += gat_layer_flops(n_in, n_out, e_used, d_in, heads, d, i == 0)
  return total


def collate_bytes(valid_rows, feat_dim, itemsize):
  """Bytes the feature gather must move: every valid row read once from
  the table and written once into the batch."""
  return 2 * valid_rows * feat_dim * itemsize
