"""The repository's benchmark: one command, cells found by name in data files.

Nothing here is imported by ``ray_tpu``. See ``run.py`` and ``PERF.md``.
"""
