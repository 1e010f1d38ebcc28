"""The honesty number of the link cell: device ms per step of the chunk
program's ops under NONE of ``glt.sample`` / ``glt.collate`` /
``glt.train`` — the loop's own overhead, key folding, what XLA hoisted out
of the loop, fusions across a layer boundary. With ``link_sample_ms``,
``link_collate_ms`` and ``link_train_ms`` it adds up to the chunk program's
busy time (``scan_unscoped_ms``' body over another cell). None with a
program that has no scope."""
from perfbench import scope_reduce

LAYER = 'epoch executors'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return scope_reduce.layer_ms(run, scope_reduce.UNSCOPED)
