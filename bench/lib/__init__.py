"""The benchmark's harness: generators, traffic kinds, checks and trace reduction."""
