"""The plain reference of family ``homo_link``: unsupervised GraphSAGE by
link prediction on one sampled batch, in straight ``jax.numpy``.

The embeddings are ``perfbench/reference.py``'s SAGE forward (full-width
segment sums over the batch's valid edges; that file is the benchmark's, and
its ``init_params`` makes the cell's weights). What this file adds is the
link objective, its gradients (``jax.grad`` of this file's loss) and its
own Adam: the two endpoints' embeddings of every pair of the batch's
``edge_label_index``, their dot product, sigmoid binary cross-entropy
against ``edge_label`` (ones for the seed edges, zeros for the sampled
negatives), mean over the valid pairs.

It imports nothing of the program and takes nothing the program made but
the sampled subgraph and the pairs' local indices, whose truth
``perfbench/families/homo_link.py`` checks against the generator's arrays
(``bad_pos_pairs``, ``false_negatives``, ``bad_pair_index``, ``bad_labels``).

``compute_dtype=float32`` runs every matmul at ``precision='highest'``; the
lower-precision control runs the same code with weights and features cast
to ``bfloat16`` (scores, loss, the gradients' accumulation and Adam stay
float32).
"""
import numpy as np

from perfbench import reference

B1, B2, EPS = reference.B1, reference.B2, reference.EPS
init_params = reference.init_params
layer_dims = reference.layer_dims


def bce_with_logits(score, label):
  """Sigmoid binary cross-entropy per pair, the stable form:
  ``max(s, 0) - s * y + log(1 + exp(-|s|))``."""
  import jax.numpy as jnp
  return (jnp.maximum(score, 0) - score * label +
          jnp.log1p(jnp.exp(-jnp.abs(score))))


def make_step(model, lr, compute_dtype='float32', half_batch=False,
              precision='highest'):
  """The jitted reference step ``(params, mu, nu, t, batch) -> (params, mu,
  nu, loss, grads)`` with ``batch = dict(x, src, tgt, emask, pair_src,
  pair_dst, pair_label)`` (a pair with a negative index is not valid).
  ``half_batch`` plants the fault "half of the PAIRS left out (every
  second one, so positives and negatives alike), the mean taken over the
  rest" (for reading the limits; never used by a run)."""
  import jax
  import jax.numpy as jnp
  dtype = jnp.dtype(compute_dtype)
  stride = 2 if half_batch else 1

  def loss_fn(params, b):
    h = reference._forward(model, params, b['x'], b['src'], b['tgt'],
                           b['emask'], dtype)
    ps, pd = b['pair_src'][::stride], b['pair_dst'][::stride]
    label = b['pair_label'][::stride].astype(jnp.float32)
    valid = (ps >= 0) & (pd >= 0)
    score = (h[jnp.maximum(ps, 0)] * h[jnp.maximum(pd, 0)]).sum(-1)
    per_pair = jnp.where(valid, bce_with_logits(score, label), 0.0)
    return per_pair.sum() / jnp.maximum(valid.sum(), 1)

  def step(params, mu, nu, t, b):
    loss, g = jax.value_and_grad(loss_fn)(params, b)
    t = t + 1
    mu = jax.tree.map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    c1, c2 = 1 - B1 ** t, 1 - B2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + EPS),
        params, mu, nu)
    return params, mu, nu, loss, g

  jitted = jax.jit(step)

  def run(params, mu, nu, t, b):
    # float32 matmuls on a TPU run in bf16 passes unless told otherwise
    with jax.default_matmul_precision(precision):
      return jitted(params, mu, nu, jnp.float32(t), b)

  return run


def follow(model, lr, params0, batches, compute_dtype='float32',
           half_batch=False, precision='highest'):
  """Drive the reference through ``batches`` from ``params0``. Returns
  host values: losses [n], the first step's gradient tree, and the
  parameter / first-moment trees after the last step."""
  import jax
  import jax.numpy as jnp
  step = make_step(model, lr, compute_dtype, half_batch, precision)
  params = jax.tree.map(jnp.asarray, params0)
  mu = jax.tree.map(jnp.zeros_like, params)
  nu = jax.tree.map(jnp.zeros_like, params)
  losses, g0 = [], None
  for t, b in enumerate(batches):
    params, mu, nu, loss, g = step(params, mu, nu, t, b)
    losses.append(loss)
    if t == 0:
      g0 = g
  return (np.asarray(jnp.stack(losses)), jax.device_get(g0),
          jax.device_get(params), jax.device_get(mu))
