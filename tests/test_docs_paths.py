"""Every file a document names exists.

One case per document (README.md, docs/**/*.md, scripts/*.sh, the verify
skill): each ``*.py`` / ``*.sh`` / ``*.json`` / ``*.md`` path it names
with a directory part must resolve — from the repo root, from the
document's own directory, or as the tail of a tracked file's path (the
package shorthand ``loader/pipeline.py``) — and the root scripts
``bench.py`` / ``chip_smoke.py`` are checked by bare name. Absolute paths
(the reference checkout) are not the repo's to keep. The generic form of
test_perfbench.py::test_benchmark_json_names_only_files_that_exist: a
document that cites a deleted harness as its evidence fails here.
"""
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SKIP_DIRS = {'.git', '.xla_cache', 'chiprun_out', '_chip', 'build',
             '__pycache__'}
ROOT_SCRIPTS = ('bench.py', 'chip_smoke.py')
PATH = re.compile(
    r'(?<![\w/.-])((?:\.\./)*(?:[\w.-]+/)*[\w.-]+\.(?:py|sh|json|md))\b')


def _documents():
  docs = ['README.md', '.claude/skills/verify/SKILL.md']
  docs += glob.glob('docs/**/*.md', root_dir=REPO, recursive=True)
  docs += glob.glob('scripts/*.sh', root_dir=REPO)
  return sorted(docs)


@functools.cache
def _repo_files():
  out = []
  for d, subdirs, files in os.walk(REPO):
    subdirs[:] = [s for s in subdirs if s not in SKIP_DIRS]
    out += [os.path.relpath(os.path.join(d, f), REPO) for f in files]
  return out


@pytest.mark.parametrize('doc', _documents())
def test_named_paths_exist(doc):
  with open(os.path.join(REPO, doc)) as f:
    text = f.read()
  files = _repo_files()
  here = os.path.dirname(doc)
  missing = set()
  for p in {m.group(1) for m in PATH.finditer(text)}:
    if '/' not in p and p not in ROOT_SCRIPTS:
      continue      # a bare file name: which directory is not said
    if not (os.path.exists(os.path.join(REPO, p))
            or os.path.exists(os.path.normpath(os.path.join(REPO, here, p)))
            or any(t.endswith('/' + p) for t in files)):
      missing.add(p)
  assert not missing, f'{doc} names files that do not exist: {sorted(missing)}'
