"""The benchmark's harness: cells by name, traffic, reference, trace."""
