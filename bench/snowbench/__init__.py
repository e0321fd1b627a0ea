"""The Snow chip benchmark's harness, generators, trace reduction and
plain references (``bench/run.py`` is the entry point)."""
