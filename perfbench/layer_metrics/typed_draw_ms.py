"""Device ms per step of the typed neighbour DRAWS inside the typed
window's own chunk program: self time under
``glt.sample/hop<h>/<relation>/draw`` summed over hops and relations — the
part of ``typed_sample_ms`` that ``ops.uniform_sample`` is (the split per
draw is on ``typed_reduce``'s line). None with a program that names no
typed draw."""
from perfbench import typed_reduce

LAYER = 'sampling'
UNIT = 'ms/step'
MOVES = 'seeds_per_s'


def read(run):
  split = typed_reduce.split(run)
  draws = [ms for key, ms in (split or {}).get('relation', {}).items()
           if key.endswith('/draw')]
  return sum(draws) if draws else None
