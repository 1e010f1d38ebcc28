"""The one home of ``shard_map`` for this package.

Every call site imports it from here (the ``compat-shard-map`` lint rule
enforces that), so the spelling of the replication-check keyword lives
in one place. Resolution is deferred to the call so importing this
package never forces jax in.
"""


def shard_map(*args, check_replication=None, **kwargs):
  """``jax.shard_map`` with ``check_replication`` mapped onto its
  ``check_vma`` keyword.

  Programs whose replicated outputs come from collectives inside
  ``lax.scan`` (the scanned-epoch trainers) pass False: the static
  replication checker cannot see through the scan carry, while the
  values are replicated by construction (every shard computes the same
  pmean)."""
  import jax
  if check_replication is not None:
    kwargs['check_vma'] = check_replication
  return jax.shard_map(*args, **kwargs)
