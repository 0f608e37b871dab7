"""The benchmark's harness: data, traffic, reference, trace reduction."""
