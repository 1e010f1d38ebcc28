"""Device ms per step of the model — forward, backward, the gradient all-reduce and the update — INSIDE the mesh cell's own chunk
program: self time of the ``XLA Ops`` events under ``glt.train``, per
chip, mean over the chips of the traced slice (perfbench/mesh_reduce.py;
the maximum over chips is on its ``mesh_reduce`` line). None with a
program that has no such scope."""
from perfbench import mesh_reduce

LAYER = 'model'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return mesh_reduce.layer_ms(run, 'glt.train')
