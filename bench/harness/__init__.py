"""Shared machinery of the benchmark: index maker, load generator, plain
reference, executor wrapper, trace reduction, work count and peaks."""
