"""Test-only family ``two_table`` (tests/perfbench_tests/test_seam.py): the
smallest cell that is NOT one homogeneous CSR and NOT SAGE or GAT, to hold
``run.py`` and ``control.py`` to the seam. Two node types with a feature
table each, typed edges user -> item (every user likes ``likes_per_user``
items), a batch of user seeds with one sampled liked item apiece, and a
two-table linear classifier ``logits = x_user W_user + x_item W_item + b``.

The program's part is the typed collate (``ops.gather_rows``, one gather per
node type), ``TrainState`` and optax's Adam; the plain reference is
``two_table_reference.py`` beside this file, which imports neither. The
exact numbers are this family's own: ``bad_pairs`` (a sampled (user, item)
that is no typed edge), ``bad_user_rows`` / ``bad_item_rows`` (gathered rows
that are not the tables' rows for those ids).
"""
import numpy as np

from perfbench.fixtures import two_table_reference as reference


class Cell:

  def __init__(self, cfg, traffic, log):
    import jax.numpy as jnp
    d, m = cfg['dataset'], cfg['model']
    rng = np.random.default_rng(cfg['graph_seed'])
    self.user_feat = rng.standard_normal(
        (d['num_users'], d['user_dim'])).astype(np.float32)
    self.item_feat = rng.standard_normal(
        (d['num_items'], d['item_dim'])).astype(np.float32)
    self.likes = rng.integers(0, d['num_items'],
                              (d['num_users'], d['likes_per_user']))
    self.label = rng.integers(0, d['num_classes'],
                              d['num_users']).astype(np.int32)
    self.tables = dict(user=jnp.asarray(self.user_feat),
                       item=jnp.asarray(self.item_feat))
    self.dims = dict(user=d['user_dim'], item=d['item_dim'],
                     out=d['num_classes'])
    self.batch = int(m['batch_size'])
    self.lr = float(m['lr'])
    self.steps_per_call = int(cfg['steps_per_call'])

  def shapes(self):
    return dict(batch=self.batch, dims=self.dims)

  def make_loader(self, seed):
    """Endless typed batches: a seeded permutation of the users, for each
    one of the items it likes, both tables gathered by the program."""
    import jax.numpy as jnp
    from graphlearn_tpu import ops
    rng = np.random.default_rng(int(seed))
    while True:
      perm = rng.permutation(self.likes.shape[0])
      for g in range(perm.size // self.batch):
        user = perm[g * self.batch:(g + 1) * self.batch]
        item = self.likes[user, rng.integers(0, self.likes.shape[1],
                                             user.size)]
        ids = dict(user=jnp.asarray(user, jnp.int32),
                   item=jnp.asarray(item, jnp.int32))
        yield dict(
            user=ids['user'], item=ids['item'],
            x_user=ops.gather_rows(self.tables['user'], None, ids['user']),
            x_item=ops.gather_rows(self.tables['item'], None, ids['item']),
            y=jnp.asarray(self.label[user]))

  def make_state(self, seed):
    import jax
    import optax
    from graphlearn_tpu.models import train as train_lib
    params = jax.tree.map(jax.numpy.asarray,
                          reference.init_params(self.dims, seed))
    tx = optax.adam(self.lr)
    state = train_lib.TrainState(params, tx.init(params),
                                 jax.numpy.zeros((), jax.numpy.int32))
    return state, tx, jax.device_get(params)

  def make_step(self, tx):
    import jax
    import jax.numpy as jnp
    import optax
    from graphlearn_tpu.models import train as train_lib

    def loss_fn(params, b):
      logits = (b['x_user'] @ params['user']['kernel'] +
                b['x_item'] @ params['item']['kernel'] + params['bias'])
      logp = jax.nn.log_softmax(logits)
      return -jnp.take_along_axis(logp, b['y'][:, None], 1).mean()

    @jax.jit
    def step(state, b):
      loss, grads = jax.value_and_grad(loss_fn)(state.params, b)
      updates, opt_state = tx.update(grads, state.opt_state, state.params)
      params = optax.apply_updates(state.params, updates)
      return train_lib.TrainState(params, opt_state, state.step + 1), loss

    return step

  def step_flops(self, nodes, edges):
    rows = nodes[0]
    return 3 * 2 * rows * (self.dims['user'] + self.dims['item']) * \
        self.dims['out']

  def valid_counts(self, batches):
    n = float(np.mean([np.asarray(b['user']).size for b in batches]))
    return dict(nodes=[n, n], edges=[n], buffer_rows=2 * self.batch)

  def exact_numbers(self, batches, n):
    out = dict(bad_pairs=0, bad_user_rows=0, bad_item_rows=0)
    for b in batches[:n]:
      user, item = np.asarray(b['user']), np.asarray(b['item'])
      out['bad_pairs'] += int((self.likes[user] != item[:, None]).all(1)
                              .sum())
      out['bad_user_rows'] += int(
          (np.asarray(b['x_user']) != self.user_feat[user]).any(1).sum())
      out['bad_item_rows'] += int(
          (np.asarray(b['x_item']) != self.item_feat[item]).any(1).sum())
    return out

  def follower(self, params0, batches):
    ref_in = [dict(x_user=self.user_feat[np.asarray(b['user'])],
                   x_item=self.item_feat[np.asarray(b['item'])],
                   y=self.label[np.asarray(b['user'])]) for b in batches]
    return lambda lr=self.lr, **kw: reference.follow(lr, params0, ref_in,
                                                     **kw)
