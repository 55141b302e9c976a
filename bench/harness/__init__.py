"""The serving benchmark's harness: everything that measures, and nothing
that is measured.  ``bench/run.py`` is the entry point; the system under
test is imported from ``src/repro`` only by ``serve`` and ``weights``."""
