"""The benchmark's harness: loader, load generators, trace reduction and
the validator of the last output line. Everything that belongs to one
configuration, traffic mix, cell or per-layer metric lives in a file of
its own beside this package, found by name."""
