"""Operations a link-prediction training step REQUIRES, on valid rows: the
model's (``flops.step_flops``: the layered SAGE on the batch's valid node
rows and edges, the last layer ``out_dim`` wide over the seed union) plus
the scores' — one ``out_dim``-long dot product a pair, 2 operations an
element, forward once and backward once to each endpoint."""
from perfbench import flops


def score_flops(pairs, out_dim):
  return 3 * 2 * int(pairs) * int(out_dim)


def step_flops(model, nodes, edges, pairs):
  """Required operations of one link training step of ``model`` (the
  description ``perfbench.reference.layer_dims`` reads) on a batch with
  these valid counts and ``pairs`` scored pairs; the BCE itself (a few
  operations a pair) is left out, as the classifier's softmax is."""
  return flops.step_flops(model, nodes, edges) + score_flops(
      pairs, model['out_dim'])
