"""Readers of the benchmark's metrics, one file per quantity.

The metric ``<name>`` is read by ``<name>.py`` where that file exists, else
by the file of its quantity, the part of the name before the first dot
(``device_idle_pct.solve`` by ``device_idle_pct.py``), so that one reader
serves a quantity that is split by the end-to-end metric it moves. Each
reader has ``read(outcome) -> float | None``; it returns None where the run
has nothing to read (no device in the trace, no kernel events, no solves),
and the metric is then left out of the result.
"""
