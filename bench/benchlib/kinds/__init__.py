"""Drivers, one per traffic ``kind``.  Each module defines ``Driver(cell,
seed, *, variant, log)`` with ``setup()``, ``counters()``,
``window(seconds, tracer) -> end-to-end metrics``, ``release()``,
``check() -> {name: {"value", "limit"}}``, and the attributes
``attempted``, ``failed`` and ``samples``."""
