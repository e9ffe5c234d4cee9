"""The benchmark harness: everything that measures, and nothing that is
measured.  See ``bench/run.py`` for the entry point."""
