"""The plain reference of family ``tiered_node``: supervised GraphSAGE on one
sampled batch whose feature rows the program gathered from a table that
spans device and host memory — computed here with nothing of how the
program finds a row.

For each step it takes the batch the program trained on (node ids, local
edge list, mask — the only things read from the program, and
``families/tiered_node.py`` checks their membership in the graph
separately) and REGENERATES the batch's rows from the node ids on its one
device: a row is a function of the dataset's seed and the id (the caller
hands the function in — the generator's ``rows_of``), so the store under
test, whichever tier served a row, cannot vouch for itself. The embeddings
are ``perfbench/reference.py``'s SAGE forward (full-width segment sums over
the batch's valid edges; that file is the benchmark's own, and its
``init_params`` makes the cell's weights); this file adds the masked
cross-entropy over the seed rows, its gradients (``jax.grad`` of this
file's loss) and its own Adam. It imports nothing of the program.

``compute_dtype=float32`` runs every matmul at ``precision='highest'``; the
lower-precision control runs the same code with weights and rows cast to
``bfloat16`` (loss, gradients and Adam stay float32).
"""
import numpy as np

from perfbench import reference

B1, B2, EPS = reference.B1, reference.B2, reference.EPS
init_params = reference.init_params
layer_dims = reference.layer_dims


def make_step(model, lr, batch_size, rows_of, compute_dtype='float32',
              half_batch=False, precision='highest'):
  """The jitted reference step ``(params, mu, nu, t, batch) -> (params, mu,
  nu, loss, grads)`` with ``batch = dict(ids, live, y, src, tgt, emask)``:
  rows regenerated from ``ids`` (a slot that is not ``live`` reads zeros),
  the plain forward, the mean cross-entropy over the first ``batch_size``
  rows. ``half_batch`` plants the fault "half of the seeds left out, the
  mean taken over the rest" (for reading the limits; never used by a
  run)."""
  import jax
  import jax.numpy as jnp
  dtype = jnp.dtype(compute_dtype)
  seeds = batch_size // 2 if half_batch else batch_size

  def loss_fn(params, b):
    x = jnp.where(b['live'][:, None], rows_of(b['ids']), 0)
    logits = reference._forward(model, params, x, b['src'], b['tgt'],
                                b['emask'], dtype)[:seeds]
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, b['y'][:seeds, None], 1).mean()

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


def follow(model, lr, batch_size, params0, batches, rows_of,
           compute_dtype='float32', half_batch=False, precision='highest'):
  """Drive the reference through ``batches`` from ``params0``. Returns
  host values: losses [n], the first step's gradient tree, and the
  parameter / first-moment trees after the last step."""
  import jax
  import jax.numpy as jnp
  step = make_step(model, lr, batch_size, rows_of, compute_dtype,
                   half_batch, precision)
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
