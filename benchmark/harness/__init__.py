"""The benchmark's yardstick: generators, windows, trace reduction, peaks,
work model, plain reference and the comparison that decides ``correct``.

Nothing here knows a cell by name.  A cell ``<config>.<mix>`` is
``configs/<config>.json`` under ``traffic/<mix>.json``; per-layer metrics are
the files of ``metrics/``.  See ``cells.py``.
"""
