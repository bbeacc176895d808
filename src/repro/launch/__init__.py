"""Command-line entry points: the dedup driver (``python -m
repro.launch.dedup``) and the compile-cache setup it shares with
``chip_smoke.py``.
"""
