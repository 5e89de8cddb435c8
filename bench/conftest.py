"""Tiny scales of the drivers added after ``bench/tests/conftest.py``:
its ``make_tiny_root`` cuts each configuration to the scale of the
driver of the configuration's last cell, looked up in ``TINY_SCALE``,
which names only the first two drivers. Loaded by pytest before it, for
every test under ``bench/``."""
from bench.tests import conftest as tiny

tiny.TINY_SCALE.setdefault("sssp", 10)
tiny.TINY_SCALE.setdefault("depths", 10)
