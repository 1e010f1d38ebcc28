"""Operations and bytes a typed training step over learned node tables
REQUIRES (family ``hetero_table_node``), counted on valid rows and edges
per node type and per relation — never from padded buffer sizes.

``nodes[t][h]`` is the number of valid rows of node type ``t`` that hop
``h`` added (``h = 0``: the seeds), ``edges[r][h]`` the valid sampled edges
of stored relation ``r`` in hop ``h``. The count is of the layered R-GCN
(``reference_hetero_table_node.layer_relations``): layer ``i`` of ``L``
maps, per relation, its targets within ``L - i - 1`` hops by two
``[n, d_in] x [d_in, hidden]`` matmuls (the self row and the neighbours'
mean, ``flops.sage_layer_flops``' terms) and sums the edges of hops
``< L - i``; backward costs a matmul twice more where its input needs a
gradient (a later layer's rows, a learned table's rows) and once more where
it is data (the labelled type's feature rows, layer 0); the classifier is a
``hidden x classes`` matmul on the seed rows. The gather of table rows, the
scatter-add of their gradients and Adam's sweep move bytes, not operations:
:func:`table_update_bytes` counts the sweep.
"""


def _prefix(v, k):
  return sum(v[:k])


def step_flops(model, nodes, edges):
  """Required operations of one training step of the R-GCN ``model``
  (``reference_hetero_table_node``'s description) on a batch with these
  valid counts per type and per relation."""
  from perfbench.reference_hetero_table_node import layer_relations
  depth, hid = model['layers'], model['hidden']
  learned = set(model['tables'])
  total = 0.0
  for i, names in enumerate(layer_relations(model)):
    hops = depth - i
    for name in names:
      s_t, d_t = model['relations'][name]
      n_out = _prefix(nodes[d_t], hops)
      e_used = _prefix(edges[name], hops)
      for t in (d_t, s_t):                   # lin_self, lin_nbr
        d_in = model['in_dims'][t] if i == 0 else hid
        data = i == 0 and t not in learned
        total += 2 * n_out * d_in * hid * (2 if data else 3)
      d_src = model['in_dims'][s_t] if i == 0 else hid
      total += 2 * e_used * d_src            # the mean, forward + backward
  total += 3 * 2 * nodes[model['out_ntype']][0] * hid * model['out_dim']
  return total


def table_update_bytes(tables, itemsize=4):
  """Bytes dense Adam's sweep over the learned tables must move a step:
  per table entry the gradient, the parameter and the two moments read
  and the parameter and the two moments written — 7 passes over ``rows x
  width`` of each table (``{type: (rows, width)}``)."""
  return sum(7 * int(r) * int(w) * itemsize for r, w in tables.values())
