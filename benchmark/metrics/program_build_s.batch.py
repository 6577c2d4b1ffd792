"""Per-layer metric ``program_build_s.batch``: layer "programs", moves
``setup_s`` (better lower, source program_counter). Seconds in first calls
of the engine's programs, all before the window."""
from benchmark.harness.inside import program_build_s as read  # noqa: F401

UNIT = "s"
LAYER = "programs"
MOVES = "setup_s"
BETTER = "lower"
SOURCE = "program_counter"
