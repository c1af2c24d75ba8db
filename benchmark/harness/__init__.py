"""The benchmark's yardstick: generators, windows, trace reduction, peaks,
work model, plain reference and the comparison that decides ``correct``.

Nothing here knows a cell by name, and no window knows a model.  A cell
``<config>.<mix>`` is ``configs/<config>.json`` under ``traffic/<mix>.json``;
per-layer metrics are the files of ``metrics/``; what is the model's (row
width, initial rows, score, work) is the module of ``models/`` that the
configuration's file names.  See ``cells.py``.
"""
