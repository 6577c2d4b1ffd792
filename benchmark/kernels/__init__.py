"""The least operations and bytes of one kernel call from its shapes, one
file per kernel, with the pattern that finds it in the trace."""
