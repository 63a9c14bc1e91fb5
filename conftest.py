"""Collection root.  ``_archive/`` (git-ignored) may hold a snapshot of an
older tree whose test modules share basenames with ``tests/`` and
``perfbench/tests/``; collecting both makes pytest refuse the twins, and
the snapshot's modules put its own ``src`` first on ``sys.path``.  This
file imports nothing, so ``tests/conftest.py`` still sets ``XLA_FLAGS``
before jax loads."""

collect_ignore_glob = ["_archive/*"]
