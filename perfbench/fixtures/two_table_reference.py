"""The plain reference of the test-only ``two_table`` family: the two-table
linear classifier in numpy float64 — forward, mean cross-entropy, the
gradient in closed form, its own Adam. It imports nothing of the program.
``compute_dtype='bfloat16'`` rounds weights and rows to bfloat16 before the
forward (the control); ``half_batch`` plants "half of the batch left out";
``precision`` is accepted and means nothing here (float64 has one)."""
import numpy as np

B1, B2, EPS = 0.9, 0.999, 1e-8


def init_params(dims, seed):
  rng = np.random.default_rng(int(seed) % (2 ** 31 - 1))
  kernel = lambda d: (rng.standard_normal((d, dims['out'])) /
                      np.sqrt(d)).astype(np.float32)
  return {'user': {'kernel': kernel(dims['user'])},
          'item': {'kernel': kernel(dims['item'])},
          'bias': np.zeros(dims['out'], np.float32)}


def _grad(params, b, dtype, rows):
  import ml_dtypes
  cast = (lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float64)) \
      if dtype == 'bfloat16' else (lambda a: np.asarray(a, np.float64))
  xu, xi, y = cast(b['x_user'][:rows]), cast(b['x_item'][:rows]), \
      b['y'][:rows]
  logits = (xu @ cast(params['user']['kernel']) +
            xi @ cast(params['item']['kernel']) + cast(params['bias']))
  z = logits - logits.max(1, keepdims=True)
  logp = z - np.log(np.exp(z).sum(1, keepdims=True))
  d = np.exp(logp)
  d[np.arange(rows), y] -= 1
  d /= rows
  return -logp[np.arange(rows), y].mean(), {
      'user': {'kernel': xu.T @ d}, 'item': {'kernel': xi.T @ d},
      'bias': d.sum(0)}


def _map(f, *trees):
  """``f`` over the leaves of same-shaped ``{'user', 'item', 'bias'}``."""
  return {'user': {'kernel': f(*[t['user']['kernel'] for t in trees])},
          'item': {'kernel': f(*[t['item']['kernel'] for t in trees])},
          'bias': f(*[t['bias'] for t in trees])}


def follow(lr, params0, batches, compute_dtype='float32', half_batch=False,
           precision='highest'):
  """``(losses, first gradient, params, first moment)`` after the batches."""
  params = _map(lambda a: np.asarray(a, np.float64), params0)
  mu = _map(np.zeros_like, params)
  nu = _map(np.zeros_like, params)
  losses, g0 = [], None
  for t, b in enumerate(batches, 1):
    rows = b['y'].shape[0] // (2 if half_batch else 1)
    loss, g = _grad(params, b, compute_dtype, rows)
    losses.append(loss)
    g0 = g if g0 is None else g0
    mu = _map(lambda m, x: B1 * m + (1 - B1) * x, mu, g)
    nu = _map(lambda v, x: B2 * v + (1 - B2) * x * x, nu, g)
    params = _map(lambda p, m, v: p - lr * (m / (1 - B1 ** t)) /
                  (np.sqrt(v / (1 - B2 ** t)) + EPS), params, mu, nu)
  return np.asarray(losses), g0, params, mu
