"""Device ms per step of the mesh's collectives: self time of the
``all-to-all`` / ``all-reduce`` ops (and the ``-start`` / ``-done`` halves
of asynchronous ones) under ``glt.sample/hop<h>/exchange``,
``glt.collate/exchange`` and ``glt.train/allreduce`` in the chunk program,
mean over the chips; the split by scope is on the ``mesh_reduce`` line.
``peaks.json`` has no interconnect figure, so this is a time, not a share
of a peak. None with a program that names none of the three scopes."""
from perfbench import mesh_reduce

LAYER = 'mesh exchange'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return mesh_reduce.exchange_ms(run)
