"""CPU tests of the harness: ``python -m pytest benchmark/tests``."""
