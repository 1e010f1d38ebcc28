"""Operations and bytes a typed training step REQUIRES, counted on valid
rows and edges per node type and per relation (family ``hetero_node``) —
never from padded buffer sizes (``flops.py`` says why).

``nodes[t][h]`` is the number of valid rows of node type ``t`` that hop
``h`` added (``h = 0``: the seeds), ``edges[r][h]`` the valid sampled edges
of stored relation ``r`` in hop ``h``. The count is of the layered
computation the R-GAT needs (``reference_hetero_node.layer_relations``):
a ``Linear`` per node type on every valid row; then layer ``i`` of ``L``
reads the rows within ``L - i`` hops and the edges of hops ``< L - i``, and
per relation projects its two end types' rows, takes two attention dot
products per projected row, and per edge and head the logit, the softmax
(about 6) and the weighted sum of D-vectors (``flops.gat_layer_flops``, per
relation). Backward costs the matmuls twice more, except the input
``Linear``, whose input is data; the classifier is a ``hidden x classes``
matmul on the seed rows (2,983 wide here: counted).
"""
from perfbench import flops


def _prefix(v, k):
  return sum(v[:k])


def step_flops(model, nodes, edges):
  """Required operations of one training step of the R-GAT ``model``
  (``reference_hetero_node``'s description) on a batch with these valid
  counts per type and per relation."""
  from perfbench.reference_hetero_node import layer_relations
  depth, hid, heads = model['layers'], model['hidden'], model['heads']
  d_head = hid // heads
  total = 0.0
  for t in model['ntypes']:
    # the input Linear: its input is data, so forward + weight gradient
    total += 2 * 2 * _prefix(nodes[t], depth + 1) * model['in_dim'] * hid
  for i, names in enumerate(layer_relations(model)):
    hops = depth - i
    for name in names:
      s_t, d_t = model['relations'][name]
      # projected rows: the sources within `hops` hops and, where the
      # target type is another, the targets (within `hops - 1`)
      n_in = _prefix(nodes[s_t], hops + 1)
      if d_t != s_t:
        n_in += _prefix(nodes[d_t], hops)
      total += flops.gat_layer_flops(
          n_in, _prefix(nodes[d_t], hops), _prefix(edges[name], hops), hid,
          heads, d_head, first=False)
  total += 3 * 2 * nodes[model['out_ntype']][0] * hid * model['out_dim']
  return total


def collate_bytes(valid_rows_by_type, feat_dim, itemsize):
  """Bytes the typed feature gather must move: per node type every valid
  row read once from that type's table and written once into the batch."""
  return sum(flops.collate_bytes(n, feat_dim, itemsize)
             for n in valid_rows_by_type.values())
