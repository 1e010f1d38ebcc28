"""Rule compat-shard-map: shard_map resolves ONLY through utils/compat.

``utils/compat.py`` is the package's one ``shard_map`` home: it owns the
spelling of the replication-check keyword (``check_replication`` ->
``check_vma``), so a jax rename is a one-file change. A direct import
anywhere else splits that knowledge across the call sites again.
"""
import ast
from typing import List

from . import astutil
from .core import Config, Finding, ParsedModule

RULE = 'compat-shard-map'

_MSG = ('direct {what} — shard_map must resolve through '
        'utils/compat.py (the one home of the replication-check '
        'keyword); import `from ..utils.compat import shard_map` instead')


def check_package(modules: List[ParsedModule], config: Config):
  out: List[Finding] = []
  for mod in modules:
    if mod.relpath == config.compat_module:
      continue
    for node in ast.walk(mod.tree):
      what = None
      if isinstance(node, ast.Import):
        for a in node.names:
          if a.name.startswith('jax.experimental.shard_map'):
            what = f'`import {a.name}`'
      elif isinstance(node, ast.ImportFrom):
        m = (node.module or '')
        if m.startswith('jax.experimental.shard_map'):
          what = f'`from {m} import ...`'
        elif m == 'jax' and any(a.name == 'shard_map'
                                for a in node.names):
          what = '`from jax import shard_map`'
        elif m == 'jax.experimental' and any(a.name == 'shard_map'
                                             for a in node.names):
          what = '`from jax.experimental import shard_map`'
      elif isinstance(node, ast.Attribute):
        dn = astutil.dotted_name(node)
        if dn in ('jax.shard_map', 'jax.experimental.shard_map',
                  'jax.experimental.shard_map.shard_map'):
          what = f'use of `{dn}`'
      if what:
        out.append(Finding(RULE, mod.path, mod.relpath, node.lineno,
                           node.col_offset + 1, _MSG.format(what=what)))
  return out
