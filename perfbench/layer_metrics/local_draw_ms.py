"""Device ms per step of the shard-local neighbour draws: self time under
``glt.sample/hop<h>/draw`` summed over the hops in the mesh chunk program,
mean over the chips (the sum of the ``hop<h>/draw`` figures on the
``mesh_reduce`` line; per hop on the ``mesh_parts_reduce`` line). None with
a program that names no part."""
from perfbench import mesh_parts_reduce as parts

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  return parts.draw_ms(run, (parts.WHOLE,))
